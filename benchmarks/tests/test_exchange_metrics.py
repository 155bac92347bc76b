"""The mesh cell's six readers on hand-made run records: counters'
growth to rows and bytes, the (n-1)/n that leaves a chip and the
division by the n chips, None where the program has no such counter,
the trace no such module or the configuration no mesh; then on the
numbers a four-chip run recorded (data/mesh4_run.json), where the
share of the ICI roofline has to stay under 100. Last, the cell's
rehearsal on the CPU's virtual devices (conftest.py): the line says 4
chips asked, and every new metric a program counter or span feeds is
in it (test_rehearsal.py's own case for this cell stops at its
`chips_asked == 1`, written when every cell had one chip)."""

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.harness.window import RunRecord, Statement  # noqa: E402
from benchmarks.tests.test_device_families import _reader  # noqa: E402
from presto_tpu.telemetry import kernels  # noqa: E402

ROWS = "presto_tpu_exchange_all_to_all_rows_total"
BYTES = "presto_tpu_exchange_all_to_all_bytes_total"
MOVED = "presto_tpu_transfer_bytes_total"
MESH = 'presto_tpu_mesh_queries_total{status="ok"}'
V5E = {"hbm_bytes": 1 << 34, "hbm_bytes_per_s": 819e9}


def _bench_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmarks", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(trace=None, mesh=4, completed=4, peaks=V5E):
    config = {"properties": {"mesh_devices": mesh} if mesh else {}}
    run = RunRecord("cell", {}, config, {}, peaks=peaks)
    run.trace = trace
    for i in range(completed):
        s = Statement("q3", 0, i, float(i), float(i + 1), True)
        s.correct = True
        run.statements.append(s)
    return run


@pytest.fixture(scope="module")
def trace():
    # the names the program has registered by the time a trace exists
    for family in ("spmd_shuffle", "spmd_fragment", "exchange_partition",
                   "join_probe"):
        kernels.jit(lambda x: x, family)
    # by_module is the mean over the cell's chips; two whole statements
    return {
        "busy_s": 3.0, "window_s": 8.0, "devices": 4,
        "busy_s_by_device": [4.5, 2.5, 2.5, 2.5],
        "by_module": [["jit_join_probe", 2.0],
                      ["jit_spmd_shuffle(3)", 0.3],
                      ["jit_spmd_fragment", 0.1],
                      ["jit__reduce_sum", 0.05]],
        "idle_gaps": [],
        "marks": [(0.0, 4.0, "q3#0"), (4.0, 8.0, "q3#1")],
    }


def test_exchange_host_ms_is_the_ledgers_two_categories():
    run = _run()
    run.ledger_ns = {"exchange.all_to_all": 6e8, "exchange": 2e8,
                     "dispatch": 9e9}
    assert _bench_run().read_metric("exchange_ms_per_query", run) == 200.0
    assert _bench_run().read_metric(
        "exchange_ms_per_query", _run(completed=0)) is None


def test_exchange_device_ms_per_traced_statement(trace):
    read = _reader("exchange_device_ms_per_query")
    assert read(_run(trace)) == pytest.approx(1e3 * (0.3 + 0.1) / 2)
    assert read(_run(None)) is None


def test_exchange_rows_are_the_counters_growth_per_statement():
    read = _reader("exchange_rows_per_query")
    run = _run()
    assert read(run) is None            # a program without the counter
    run.counters[ROWS] = 16_000_000.0
    assert read(run) == 4_000_000.0
    assert read(_run(completed=0)) is None


def test_ici_share_counts_what_leaves_a_chip_over_the_waves_time(trace):
    read = _reader("ici_roofline_share")
    run = _run(trace)
    assert read(run) is None            # no counter
    run.counters[BYTES] = 4 * 128e6     # 128 MB a statement, 4 completed
    # 2 traced statements: 256 MB on the wire, 3/4 of it leaves its
    # chip, a quarter of that from each of 4 chips, at 200 GB/s;
    # over the 0.4 s a chip spent in the exchange's modules
    least_s = 256e6 * 3 / 4 / 4 / 200e9
    assert read(run) == pytest.approx(100 * least_s / 0.4)
    for other in (_run(None), _run(trace, mesh=None), _run(trace, mesh=1),
                  _run(trace, peaks=None), _run(trace, completed=0)):
        other.counters[BYTES] = 4 * 128e6
        assert read(other) is None
    no_module = dict(trace, by_module=[["jit_join_probe", 2.0]])
    bare = _run(no_module)
    bare.counters[BYTES] = 1.0
    assert read(bare) is None


def test_scan_transfer_is_zero_on_a_mesh_and_nothing_without_one():
    read = _reader("scan_transfer_bytes_per_query")
    run = _run()
    run.counters['presto_tpu_transfer_bytes_total{direction="d2h"}'] = 9.0
    assert read(run) is None            # the window ran no mesh statement
    run.counters[MESH] = 4.0
    assert read(run) == 0.0
    run.counters[MOVED + '{direction="d2d"}'] = 600.0
    run.counters[MOVED + '{direction="h2d"}'] = 200.0
    assert read(run) == 200.0
    idle = _run(completed=0)
    idle.counters[MESH] = 4.0
    assert read(idle) is None


def test_busy_imbalance_is_the_busiest_chip_over_the_mean(trace):
    read = _reader("device_busy_imbalance")
    assert read(_run(trace)) == pytest.approx(4.5 / 3.0)
    assert read(_run(None)) is None
    assert read(_run(dict(trace, busy_s_by_device=[2.0]))) is None
    assert read(_run(dict(trace, busy_s_by_device=[0.0] * 4))) is None


def test_the_recorded_four_chip_run():
    """What one traced run of sf1_join_mesh4 on a four-chip v5e printed
    (the `trace:` line of its stderr, its counters through the line's
    metrics): every reader finds something, the exchange is far from
    its roofline and never over it."""
    with open(os.path.join(HERE, "data", "mesh4_run.json")) as f:
        rec = json.load(f)
    for module, _ in rec["trace"]["by_module"]:
        name = module.split("(")[0]
        if name.startswith("jit_spmd_") or name == "jit_exchange_partition":
            kernels.jit(lambda x: x, name[len("jit_"):])
    n = rec["traced_statements"]
    span = rec["trace"]["window_s"]
    trace = dict(rec["trace"], idle_gaps=[], marks=[
        (i * span / n, (i + 1) * span / n, f"q3#{i}") for i in range(n)])
    run = _run(trace, completed=rec["completed"])
    run.counters = {BYTES: rec["exchange_bytes_per_query"] * rec["completed"],
                    ROWS: rec["exchange_rows_per_query"] * rec["completed"],
                    MESH: float(rec["completed"])}
    share = _reader("ici_roofline_share")(run)
    assert 0 < share <= 100
    assert share == pytest.approx(rec["metrics"]["ici_roofline_share"],
                                  rel=1e-6)
    assert _reader("exchange_device_ms_per_query")(run) == pytest.approx(
        rec["metrics"]["exchange_device_ms_per_query"], rel=1e-6)
    assert _reader("device_busy_imbalance")(run) == pytest.approx(
        rec["metrics"]["device_busy_imbalance"], rel=1e-6)
    assert _reader("exchange_rows_per_query")(run) == pytest.approx(
        rec["exchange_rows_per_query"])
    assert _reader("scan_transfer_bytes_per_query")(run) == 0.0


@pytest.mark.parametrize("trace", [0, 1])
def test_the_mesh_cell_rehearses_on_four_virtual_devices(trace):
    from benchmarks.tests.test_rehearsal import BENCH, _reported, _run \
        as _rehearse
    p = _rehearse("--workload", "sf1_join_mesh4", "--seed", "3000000019",
                  "--seconds", "2", "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert line["device"]["chips_asked"] == 4
    assert line["device"]["chips_used"] == "rehearsal: not checked"
    kind = "per_layer" if trace else "end_to_end"
    device_metrics = {m["name"] for m in BENCH["per_layer"]
                      if m["source"] == "device_trace"} | {"peak_hbm_share"}
    assert set(line["metrics"]) == \
        _reported(kind, "sf1_join_mesh4") - device_metrics
    if trace:
        got = {k: v["value"] for k, v in line["metrics"].items()}
        assert got["exchange_rows_per_query"] > 0
        assert got["exchange_ms_per_query"] > 0
        assert got["scan_transfer_bytes_per_query"] == 0
        assert got["page_cache_hit_share"] == 100
