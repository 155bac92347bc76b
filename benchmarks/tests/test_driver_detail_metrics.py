"""The six per-layer metrics PR 39 appended, on hand-made runs: the
share of the idle seconds no span names (`idle_unnamed_share`), the
split of the ledger's `driver.step` between the operators' hand-offs
and the loops (`operator_host_ms_per_query`, `driver_loop_ms_per_query`),
the drivers' passes (`driver_passes_per_query`,
`driver_moved_pass_share`) and the waves a statement
(`exchange_waves_per_query`); each None against a run shaped like the
parent's, whose program has no such series.

    python -m pytest benchmarks/tests        (not part of tier-1)
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.harness.files import read_json  # noqa: E402
from benchmarks.harness.window import Statement  # noqa: E402
from benchmarks.tests.test_device_families import _reader, _run  # noqa: E402

SIX = ("sf1_join_serial", "sf10_scan_serial", "sf1_short_concurrent",
       "sf1_join_mesh4", "sf10_join_serial", "sf1_q18_serial")
NEW = {"idle_unnamed_share": SIX, "operator_host_ms_per_query": SIX,
       "driver_loop_ms_per_query": SIX, "driver_passes_per_query": SIX,
       "driver_moved_pass_share": SIX,
       "exchange_waves_per_query": ("sf1_join_mesh4",)}
DETAIL = ('presto_tpu_ledger_detail_ns_total'
          '{category="%s",detail="%s"}')
PASSES = 'presto_tpu_driver_passes_total{moved="%s"}'
WAVES = "presto_tpu_exchange_all_to_all_waves_total"


def _record(statements, trace=None):
    """A window of `statements` completed Q3s as the parent's program
    leaves it: the ledger's categories, the waves, no detail, no pass."""
    run = _run(trace)
    run.statements = [Statement("q3", 0, i, 1.0 * i, 1.0 * i + 0.8, True,
                                correct=True)
                      for i in range(statements)]
    run.ledger_ns = {"driver.step": 200e6 * statements,
                     "driver.quantum": 50e6 * statements,
                     "exchange.all_to_all": 220e6 * statements}
    run.counters = {
        'presto_tpu_ledger_ns_total{category="driver.step"}':
            200e6 * statements,
        WAVES: 4.0 * statements}
    return run


def _with_details(run):
    n = run.completed
    run.counters.update({
        DETAIL % ("driver.step", "hash_build.add_input"): 90e6 * n,
        DETAIL % ("driver.step", "hash_build.finish"): 60e6 * n,
        DETAIL % ("driver.step",
                  "fused[filter_project+lookup_join(inner)].add_input"):
            30e6 * n,
        # other categories' details are not the operators'
        DETAIL % ("prefetch", "scan:lineitem.get_output"): 40e6 * n,
        DETAIL % ("driver.quantum", "statement"): 50e6 * n,
        PASSES % "yes": 120.0 * n, PASSES % "no": 360.0 * n})
    return run


@pytest.mark.parametrize("name", sorted(NEW))
def test_entry_lists_its_cells_and_documents_itself(name):
    (entry,) = [m for m in read_json("..", "BENCHMARK.json")["per_layer"]
                if m["name"] == name]
    assert tuple(entry["workloads"]) == NEW[name]
    assert entry["moves"] == "qps"
    spec = read_json("metrics", f"{name}.json")
    assert spec["reader"] == "function" and len(spec["doc"]) > 80
    for key in ("layer", "unit", "source", "moves"):
        assert spec[key] == entry[key], (name, key)


def test_the_six_are_the_last_entries_and_nothing_else_moved():
    names = [m["name"] for m in
             read_json("..", "BENCHMARK.json")["per_layer"]]
    assert names[-6:] == [
        "idle_unnamed_share", "operator_host_ms_per_query",
        "driver_loop_ms_per_query", "driver_passes_per_query",
        "driver_moved_pass_share", "exchange_waves_per_query"]
    assert len(names) == len(set(names)) == 40


@pytest.mark.parametrize("name", [
    "operator_host_ms_per_query", "driver_loop_ms_per_query",
    "driver_passes_per_query", "driver_moved_pass_share"])
def test_nothing_to_read_from_the_parents_program(name):
    read = _reader(name)
    assert read(_record(3)) is None
    # nor where no statement completed (a share needs a pass)
    run = _with_details(_record(0))
    assert read(run) is None


def test_operators_and_loop_split_the_ledgers_driver_step():
    run = _with_details(_record(3))
    operators = _reader("operator_host_ms_per_query")(run)
    loop = _reader("driver_loop_ms_per_query")(run)
    assert operators == pytest.approx(180.0)
    assert loop == pytest.approx(20.0)
    # together: the ledger's driver.step per statement, to rounding
    assert operators + loop == pytest.approx(
        run.ledger_ns["driver.step"] / 1e6 / run.completed)


def test_passes_per_statement_and_the_share_that_moved():
    run = _with_details(_record(2))
    assert _reader("driver_passes_per_query")(run) == 480.0
    assert _reader("driver_moved_pass_share")(run) == 25.0
    # one value of `moved` alone is a whole family already
    del run.counters[PASSES % "no"]
    assert _reader("driver_passes_per_query")(run) == 120.0
    assert _reader("driver_moved_pass_share")(run) == 100.0


def test_waves_per_statement_reads_the_counter_the_parent_has():
    read = _reader("exchange_waves_per_query")
    assert read(_record(5)) == 4.0
    assert read(_record(0)) is None
    run = _record(5)
    del run.counters[WAVES]                 # one chip: no exchange
    assert read(run) is None


#: every label kind trace_reduce.reduce can charge a gap to
GAPS = [["ledger:driver.step/hash_build.finish", 1.5],    # named
        ["ledger:driver.step", 0.5],                      # bare
        ["ledger:exchange.all_to_all/sync", 1.0],         # named
        ["ledger:driver.quantum/statement", 0.75],        # named
        ["ledger:driver.quantum", 0.25],                  # bare
        ["kernel:pad", 2.0],                              # named
        ["compile:fragment", 0.5],                        # named
        ["ledger:planning", 0.5],                         # named
        ["q3@90%", 1.0],                  # a statement mark: no span
        ["no_statement_in_flight", 0.25],
        ["(no span open)", 0.25],
        ["device_1_ran_nothing", 1.5]]


def test_idle_unnamed_share_over_the_whole_list():
    read = _reader("idle_unnamed_share")
    assert read(_run(None)) is None                     # no trace
    assert read(_run({"idle_gaps": []})) is None        # never idle
    # the catch-alls, the marks and the chip that ran nothing: 3.75 s
    # of 10 s; a label with a detail counts as named
    assert read(_run({"idle_gaps": GAPS})) == pytest.approx(37.5)
    # past the ten labels a breakdown prints
    many = [[f"kernel:family_{i}", 1.0] for i in range(30)] \
        + [["ledger:driver.step", 10.0]]
    assert read(_run({"idle_gaps": many})) == pytest.approx(25.0)


def test_idle_unnamed_share_of_a_parent_shaped_trace():
    """The parent's labels carry no detail: its root frame and its
    loops are the two catch-alls, as the ledger's PR 38 lines show."""
    gaps = [["ledger:driver.step", 2.492], ["ledger:driver.quantum", 0.796],
            ["ledger:exchange.all_to_all", 1.063],
            ["ledger:device_wait", 0.526], ["kernel:agg_finalize", 0.227]]
    share = _reader("idle_unnamed_share")(_run({"idle_gaps": gaps}))
    assert share == pytest.approx(100 * 3.288 / 5.104)
