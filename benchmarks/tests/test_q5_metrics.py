"""The cell sf10_q5_serial as the benchmark reads it: the
sorted join layout's device time (`join_sorted_device_ms_per_query`),
the rows a lookup join sent against a sorted build
(`join_sorted_probe_rows_per_query`) and the share of HBM's rate the
layout's least bytes reach (`join_sorted_hbm_roofline_share`), each
None from a program without its module or its series; the plain
reference of Q5 against `baseline_proxy.q5`; the float32 control,
which has to come out as not correct; and the cell's own rehearsal,
traced and untraced.

    python -m pytest benchmarks/tests        (not part of tier-1)
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)
import control  # noqa: E402

from benchmarks.harness import reference_data  # noqa: E402
from benchmarks.harness.files import read_json  # noqa: E402
from benchmarks.harness.window import Statement  # noqa: E402
from benchmarks.tests.test_device_families import _reader, _run  # noqa: E402
from benchmarks.tests.test_rehearsal import (  # noqa: E402
    _broken_run, _run as _command)
from presto_tpu.telemetry import kernels  # noqa: E402

CELL = "sf10_q5_serial"
ROWS = "presto_tpu_join_probe_rows_total"
BYTES = "presto_tpu_join_sorted_bytes_total"
PEAKS = read_json("harness", "peaks.json")["TPU v5 lite"]


def _record(statements, trace=None):
    run = _run(trace)
    run.peaks = PEAKS
    run.statements = [Statement("q5", 0, i, 5.0 * i, 5.0 * i + 4.0,
                                True, correct=True)
                      for i in range(statements)]
    # what the program counts beside them
    run.counters = {'presto_tpu_join_builds_total{layout="direct"}':
                    4.0 * statements,
                    'presto_tpu_join_builds_total{layout="sorted"}':
                    1.0 * statements}
    return run


def test_sorted_probe_rows_per_completed_statement():
    read = _reader("join_sorted_probe_rows_per_query")
    assert read(_record(2)) is None         # the parent: no such series
    run = _record(0)                        # no statement completed
    run.counters[ROWS + '{layout="sorted"}'] = 0.0
    assert read(run) is None
    # Q5 at SF10, two statements: about 9.7M lines of 1994's orders
    # probe the supplier build; a window whose joins probed direct
    # tables alone reads 0, not None
    run = _record(2)
    run.counters.update({ROWS + '{layout="sorted"}': 2 * 9716450.0,
                         ROWS + '{layout="direct"}': 2 * 70000000.0})
    assert read(run) == 9716450.0
    run.counters.pop(ROWS + '{layout="sorted"}')
    assert read(run) == 0.0


@pytest.fixture(scope="module")
def by_module():
    """A recorded by_module of two traced Q5 statements (the names the
    program registers when it builds these kernels)."""
    for family, part in (("join_build", "sorted"), ("join_build", "direct"),
                         ("join_probe", "sorted"), ("join_probe", None),
                         ("join_probe", "materialize"),
                         ("fragment", "join_probe_sorted")):
        kernels.jit(lambda x: x, family, part)
    return {
        "busy_s": 9.0, "window_s": 10.0, "devices": 1,
        "by_module": [["jit_join_probe_materialize", 3.0],
                      ["jit_join_probe_sorted", 2.0],
                      ["jit_join_probe", 1.5],
                      ["jit_join_build_direct", 0.5],
                      ["jit_fragment_join_probe_sorted", 0.25],
                      ["jit_join_build_sorted", 0.25]],
        "idle_gaps": [],
        "marks": [(0.0, 5.0, "q5#0"), (5.0, 10.0, "q5#1")],
    }


def test_sorted_device_ms_of_the_sorted_modules_alone(by_module):
    read = _reader("join_sorted_device_ms_per_query")
    # (2.0 + 0.25 + 0.25) s over two statements
    assert read(_run(by_module)) == pytest.approx(1250.0)
    assert read(_run(None)) is None         # an untraced run
    assert read(_run(dict(by_module, marks=[]))) is None
    # the parent runs the sorted probe inside jit_join_probe and builds
    # nothing sorted in a window without a multi-key join: no 0
    parent = dict(by_module, by_module=[
        [m, s] for m, s in by_module["by_module"] if "sorted" not in m])
    assert read(_run(parent)) is None
    # the join_probe group still holds the unfused sorted probe
    assert _reader("join_probe_device_ms_per_query")(
        _run(by_module)) == pytest.approx(3250.0)


def test_sorted_roofline_share(by_module):
    read = _reader("join_sorted_hbm_roofline_share")
    run = _record(4, by_module)
    assert read(run) is None                # no counter: the parent
    run.counters[BYTES] = 4 * 409.5e6       # 409.5 MB a statement
    # two traced statements: 819 MB at 819 GB/s is 1 ms of the 2.5 s
    # the sorted modules took
    assert read(run) == pytest.approx(100 * 1e-3 / 2.5)
    assert read(_record(4, None)) is None   # an untraced run
    run.peaks = None                        # a rehearsal
    assert read(run) is None
    run = _record(4, dict(by_module, by_module=[
        [m, s] for m, s in by_module["by_module"] if "sorted" not in m]))
    run.counters[BYTES] = 4 * 409.5e6
    assert read(run) is None                # no sorted module ran


def _queries():
    return {"q5": read_json("queries", "q5.json")}


@pytest.mark.parametrize("seed", [5, 2150000011])
def test_reference_equals_baseline_proxy(seed):
    """benchmarks/reference/q5.py is a copy: on sf0_1 it answers what
    baseline_proxy.q5 answers, the nation's name decoded, in the same
    order and to the last bits a sum's order moves."""
    import baseline_proxy
    import presto_tpu  # noqa: F401
    from presto_tpu.connectors.tpch import TpchGenerator
    gen = TpchGenerator(0.1, seed=seed)
    got, _ = reference_data.reference_rows(gen, _queries())
    tables = baseline_proxy.load_tables(
        gen, ["lineitem", "orders", "customer", "supplier", "nation",
              "region"])
    names = reference_data.dictionary(gen, "nation", "name")
    want = [(names[r["n_name"]], r["rev_sum"])
            for r in baseline_proxy.q5(tables, gen).to_pylist()]
    assert len(want) == 5                   # the five ASIA nations
    # Acero sums in the order its threads reach the rows
    assert [n for n, _ in got["q5"]] == [n for n, _ in want]
    assert [v for _, v in got["q5"]] == pytest.approx(
        [v for _, v in want], rel=1e-13)


@pytest.mark.parametrize("seed", [11, 2150000011, 5])
def test_float32_reference_of_q5_is_not_correct(seed):
    """`extendedprice` and `discount` narrowed to float32 move each
    nation's revenue by about 5e-9 of itself at sf0_1: over the 1e-9
    limit, so a lower precision fails the cell."""
    numbers, correct, per = control.control_numbers(0.1, seed, _queries())
    assert correct is False
    assert numbers["rows_differ"]["value"] == 0
    differs, gap = per["q5"]
    assert not differs
    assert numbers["max_rel_err"]["limit"] < gap < 1e-6


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cells_rehearsal(trace):
    p = _command("--workload", CELL, "--seed", "2150000036", "--seconds",
                 "2", "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] > 0
    got = line["metrics"]
    if not trace:
        assert set(got) == {"qps", "setup_s"}
        return
    # sf0_1: about 97,000 lines of 1994's orders probe the two-key
    # supplier build; four of five builds are direct
    assert 90000 < got["join_sorted_probe_rows_per_query"]["value"] \
        < 105000
    assert got["join_direct_build_share"]["value"] == 80.0
    # no device on the CPU
    assert not {"join_sorted_device_ms_per_query",
                "join_sorted_hbm_roofline_share"} & set(got)


def test_altered_nation_is_not_correct(monkeypatch, capsys):
    """test_rehearsal.py's "a key altered" adds 1 to every integer of
    an answer, and Q5's answer has none (its cell's case of that test
    fails as found): here its key, the nation's name, is altered in
    the same place, and the rows differ."""
    line = _broken_run(monkeypatch, capsys, CELL, lambda rows: [
        [v + "X" if isinstance(v, str) else v for v in r] for r in rows])
    assert line["correct"] is False
    assert line["compared"]["rows_differ"]["value"] > 0
    assert line["failed"] > 0
