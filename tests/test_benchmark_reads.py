"""Every name the chip benchmark reads from the program, one case a
name. `benchmarks/run.py` differences `METRICS.snapshot()` over its
window (`RunRecord.counters`, `.ledger_ns`), reads the page-source
cache's `stats.hits`, `stats.misses` and `bytes` off the CacheManager,
and takes `queued_ms`, `wall_ms`, `served_ms` and the `scan:<table>`
operators from each statement's server-side stats; the readers under
`benchmarks/metrics/` name the series. `benchmarks/tests` is not
tier-1, so a renamed or dropped series would otherwise be found on the
chip, as a `null` under `per_layer`.

Each run below sends statements over POST /v1/statement and keeps what
the benchmark would read before and after; a case says which run, which
name, and what must hold of it. No case asserts a time."""

import os
import sys
import threading

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tpch_queries import QUERIES  # noqa: E402

from presto_tpu.cache import get_cache_manager, reset_cache_manager
from presto_tpu.server.coordinator import Coordinator, StatementClient
from presto_tpu.telemetry import ledger
from presto_tpu.telemetry.metrics import METRICS

STATEMENT_TIMEOUT_S = 300.0
PROPS = {"fragment_result_cache_enabled": False}

LEDGER = 'presto_tpu_ledger_ns_total{category="%s"}'
PROTOCOL = 'presto_tpu_protocol_ns_total{phase="%s"}'
TRANSFER = 'presto_tpu_transfer_bytes_total{direction="%s"}'
DETAIL = ('presto_tpu_ledger_detail_ns_total'
          '{category="%s",detail="%s"}')
PASSES = 'presto_tpu_driver_passes_total{moved="%s"}'


def _readings(coord=None):
    """What run.py reads at a window's edge: every counter series, and
    the page-source cache's three numbers under names of their own."""
    out = dict(METRICS.snapshot())
    page = get_cache_manager().page
    out["page.stats.hits"] = page.stats.hits
    out["page.stats.misses"] = page.stats.misses
    out["page.bytes"] = page.bytes
    if coord is not None:
        # the last statement's stats, as run.py takes them from
        # coord.queries after a traced window
        stats = list(coord.queries.values())[-1].stats
        for field in ("queued_ms", "wall_ms", "served_ms"):
            if field in stats:
                out[f"stats.{field}"] = stats[field]
        out["stats.scan:lineitem.output_rows"] = sum(
            int(op["output_rows"])
            for task in stats.get("tasks", [])
            for pipeline in task.get("pipelines", [])
            for op in pipeline if op.get("name") == "scan:lineitem")
    return out


def _serve(properties, statements, while_serving=None, cold=False):
    """{"before", "after"} around `statements` answered by a single-node
    Coordinator on tpch.tiny. The cache hierarchy is dropped first so
    the first scans miss, and with `cold` the kernel caches too, so the
    compile counters have something to count."""
    reset_cache_manager()
    if cold:
        from presto_tpu.execution.compile_cache import clear_kernel_caches
        clear_kernel_caches()
    coord = Coordinator([], "tpch", "tiny", single_node=True,
                        properties={**PROPS, **properties})
    coord.start()
    try:
        client = StatementClient(coord.url, user="benchmark-reads")
        before = _readings()
        for sql in statements:
            client.execute(sql, timeout=STATEMENT_TIMEOUT_S)
        if while_serving is not None:
            while_serving(coord, client)
        return {"before": before, "after": _readings(coord)}
    finally:
        coord.stop()
        reset_cache_manager()


@pytest.fixture(scope="module")
def one_chip():
    """Q1, Q3 and Q6 twice: the statements of the three one-chip
    cells, the second round against warm caches."""
    return _serve({}, [QUERIES[q] for q in (1, 3, 6)] * 2, cold=True)


@pytest.fixture(scope="module")
def q18():
    """Q18 twice: the statement of the cell sf1_q18_serial. On tiny
    no order's lines pass QUANTITY = 300, so the semi join keeps no
    row; everything before it runs as at any scale."""
    return _serve({}, [QUERIES[18]] * 2)


@pytest.fixture(scope="module")
def q5():
    """Q5 twice: the statement of the cell sf10_q5_serial. On tiny the
    optimizer puts the customer join last of the first four, so its
    build takes the two keys (custkey, nationkey) and the sorted
    layout; at SF10 the supplier's does. Both are multi-key builds."""
    return _serve({}, [QUERIES[5]] * 2)


@pytest.fixture(scope="module")
def mesh():
    """Q3 twice on the served four-device mesh of test_mesh_served.py;
    the second arrives while the mesh is held, so it waits for it."""
    def a_statement_that_waits(coord, client):
        runner = coord._runner()
        sent = threading.Thread(
            target=client.execute, args=(QUERIES[3],),
            kwargs={"timeout": STATEMENT_TIMEOUT_S}, daemon=True)
        assert runner._mesh_lock.acquire(timeout=STATEMENT_TIMEOUT_S)
        try:
            sent.start()
            sent.join(0.5)
        finally:
            runner._mesh_lock.release()
        sent.join(STATEMENT_TIMEOUT_S)
        assert not sent.is_alive()

    return _serve({"mesh_devices": 4}, [QUERIES[3]],
                  a_statement_that_waits)


@pytest.fixture(scope="module")
def mesh_small_batches():
    """A table written in six batches by one task and read back by
    four: three of them read pages that live on another chip."""
    return _serve({"mesh_devices": 4, "batch_rows": 256}, [
        "create table memory.default.benchmark_reads as "
        "select orderkey, totalprice from orders",
        "select count(*), sum(totalprice) "
        "from memory.default.benchmark_reads"])


@pytest.fixture(scope="module")
def host_copy():
    """No connector hands a task a host batch on tiny, so the copy is
    made here: `parallel.mesh.place` of a host batch under a query's
    ledger, the one site that charges `h2d` on the served path."""
    from presto_tpu.batch import Batch
    from presto_tpu.parallel.mesh import place
    from presto_tpu.types import BIGINT
    batch = jax.device_get(
        Batch.from_numpy({"x": np.arange(1 << 16)}, {"x": BIGINT}))
    before = _readings()
    query = ledger.QueryLedger()
    token = ledger.install(query)
    try:
        place(batch, jax.devices()[1])
    finally:
        ledger.uninstall(token)
    after = _readings()
    # LocalRunner.execute adds a finished ledger's categories to
    # presto_tpu_ledger_ns_total one by one, under their own names
    for category, ns in query.snapshot().items():
        if ns:
            after[LEDGER % category] = \
                after.get(LEDGER % category, 0) + ns
    return {"before": before, "after": after}


def _grew(run, series):
    assert series in run["after"], f"{series} is no series of the run"
    assert run["after"][series] > run["before"].get(series, 0), series


def _family_grew(run, family):
    """As RunRecord.counter reads it: summed over the labels."""
    def total(readings):
        return sum(v for k, v in readings.items()
                   if k == family or k.startswith(family + "{"))
    assert total(run["after"]) > total(run["before"]), family


def _details_grew(run, prefix):
    """As harness/driver_detail.py reads a category's details: every
    series of the family that starts with the category's label."""
    def total(readings):
        return sum(v for k, v in readings.items()
                   if k.startswith(prefix))
    assert total(run["after"]) > total(run["before"]), prefix


def _present(run, series):
    assert isinstance(run["after"].get(series), (int, float)), series


def _declared(run, series):
    """A category no statement of this suite charges: it is one of the
    ledger's, so a document that carries it is rendered and counted."""
    category = series.split('"')[1]
    assert LEDGER % category == series
    assert category in ledger.CATEGORIES


def _case(run, name, holds=_grew, why=""):
    return pytest.param(run, name, holds,
                        id=f"{run}-{name}" + (f"-{why}" if why else ""))


#: (run, name as the benchmark reads it, what must hold of it, and why
#: wherever the name cannot grow over statements on tiny)
READS = [
    *[_case("one_chip", LEDGER % c) for c in (
        "planning", "driver.step", "driver.quantum",
        "driver.reassembly", "prefetch", "scan", "dispatch",
        "device_wait", "d2h")],
    _case("host_copy", LEDGER % "h2d",
          why="by-place-alone:no-host-batch-reaches-a-task-on-tiny"),
    _case("mesh", LEDGER % "exchange.all_to_all"),
    _case("mesh", LEDGER % "exchange", _declared,
          why="declared:no-http-exchange-on-a-mesh"),
    *[_case("one_chip", PROTOCOL % p)
      for p in ("accept", "encode", "result_wait")],
    _case("one_chip", "presto_tpu_xla_compiles_total", _family_grew),
    _case("one_chip", "presto_tpu_xla_compile_seconds_total",
          _family_grew),
    _case("one_chip", "presto_tpu_kernel_compiles_total", _family_grew),
    _case("one_chip", "presto_tpu_kernel_calls_total", _family_grew),
    _case("one_chip", "page.stats.hits"),
    _case("one_chip", "page.stats.misses"),
    _case("one_chip", "page.bytes"),
    _case("host_copy", TRANSFER % "h2d", why="by-place-alone"),
    _case("mesh_small_batches", TRANSFER % "d2d"),
    _case("one_chip", 'presto_tpu_join_builds_total{layout="direct"}'),
    *[_case(run, 'presto_tpu_join_probe_lanes_total{stage="%s"}' % s)
      for run in ("one_chip", "mesh")
      for s in ("searched", "materialized")],
    # the three join_build_* metrics (PR 34): rows over lanes, and the
    # finish's wall; batches and slots are read by whoever asks
    *[_case(run, 'presto_tpu_join_build_%s_total{layout="direct"}' % n)
      for run in ("one_chip", "mesh")
      for n in ("rows", "lanes", "batches")],
    *[_case(run, name) for run in ("one_chip", "mesh")
      for name in ("presto_tpu_join_direct_table_slots_total",
                   "presto_tpu_join_build_finish_ns_total")],
    # join_build_packed_lane_share (PR 35): Q3's builds fit their rung,
    # so nothing is packed and the series grows by 0: it has to be there
    *[_case(run, 'presto_tpu_join_build_packed_lanes_total'
            '{layout="direct"}', _present,
            why="present:builds-that-fit-their-rung-pack-nothing")
      for run in ("one_chip", "mesh")],
    # the cell sf1_q18_serial (PR 37): the semi join's build is the
    # one that stays sorted, the streaming aggregation and the semi
    # join add their rows at the statement's drain
    _case("q18", 'presto_tpu_join_builds_total{layout="sorted"}'),
    _case("q18", 'presto_tpu_join_direct_fallback_total'
          '{reason="join_type"}'),
    _case("q18", 'presto_tpu_kernel_calls_total{kernel="agg_stream"}'),
    _case("q18", "presto_tpu_agg_stream_rows_total"),
    _case("q18", "presto_tpu_agg_stream_groups_total"),
    _case("q18", "presto_tpu_semi_join_probe_rows_total"),
    _case("q18", "presto_tpu_semi_join_matched_rows_total", _present,
          why="present:no-order-passes-300-on-tiny"),
    # the cell sf10_q5_serial: a two-key build stays sorted,
    # and the lookup joins add their probe rows by layout, and what
    # the sorted layout reads, at the statement's drain
    _case("q5", 'presto_tpu_join_builds_total{layout="sorted"}'),
    _case("q5", 'presto_tpu_join_direct_fallback_total'
          '{reason="multi_key"}'),
    *[_case("q5", 'presto_tpu_join_probe_rows_total{layout="%s"}' % lay)
      for lay in ("sorted", "direct")],
    _case("q5", "presto_tpu_join_sorted_bytes_total"),
    _case("mesh", "presto_tpu_exchange_all_to_all_rows_total"),
    _case("mesh", "presto_tpu_exchange_all_to_all_bytes_total"),
    _case("mesh", "presto_tpu_exchange_all_to_all_waves_total"),
    _case("mesh", 'presto_tpu_mesh_queries_total{status="ok"}'),
    _case("mesh", "presto_tpu_mesh_lock_wait_ns_total"),
    # the ledger's details and the driver's passes (PR 39):
    # operator_host_ / driver_loop_ms_per_query read every detail of
    # driver.step, driver_passes_per_query / driver_moved_pass_share
    # both values of `moved`; the named ones are what the trace's
    # labels and PERF.md's tables are made of
    *[_case(run, 'presto_tpu_ledger_detail_ns_total'
            '{category="driver.step",', _details_grew)
      for run in ("one_chip", "mesh")],
    *[_case("one_chip", DETAIL % d) for d in (
        ("driver.step", "hash_build.add_input"),
        ("driver.step", "hash_build.finish"),
        ("prefetch", "scan:lineitem.get_output"),
        ("driver.quantum", "statement"),
        ("driver.quantum", "executor"))],
    *[_case("mesh", DETAIL % d) for d in (
        ("driver.step", "exchange_sink.add_input"),
        ("driver.quantum", "mesh_round"),
        ("exchange.all_to_all", "assemble"),
        ("exchange.all_to_all", "dispatch"),
        ("exchange.all_to_all", "sync"),
        ("exchange.all_to_all", "slice"))],
    *[_case(run, PASSES % m) for run in ("one_chip", "mesh")
      for m in ("yes", "no")],
    # the exchange's own placements: a direction apart from the
    # scans' d2d, which scan_transfer_bytes_per_query keeps reading
    _case("mesh", TRANSFER % "exchange_d2d"),
    _case("mesh", LEDGER % "d2d"),
    _case("one_chip", "stats.queued_ms", _present,
          why="present:one-client-never-queues"),
    _case("one_chip", "stats.wall_ms"),
    _case("one_chip", "stats.served_ms"),
    _case("one_chip", "stats.scan:lineitem.output_rows"),
]


@pytest.mark.parametrize("run, name, holds", READS)
def test_the_benchmark_finds_the_name_it_reads(run, name, holds, request):
    holds(request.getfixturevalue(run), name)
