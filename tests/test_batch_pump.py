"""Batch-pump battery (operators/driver.py): the pipelined data
plane's fast path must be invisible except in the clock.

Oracles: (1) byte-identity — every query answers identically pump-on
vs pump-off (serving mix fast, the full TPC-H suite in the slow lane);
(2) lifecycle — cancel and deadline land mid-pump at quantum
boundaries, and the `executor.quantum` chaos site fires under a
pumping driver; (3) zero new kernels — the pump re-uses the exact
kernel families the pair loop compiled (it moves batches differently,
it must not compute differently)."""

import threading
import time

import pytest

from presto_tpu.execution.task_executor import (
    TaskExecutor, set_task_executor,
)
from presto_tpu.operators import driver as driver_mod
from presto_tpu.runner.local import LocalRunner, QueryError
from presto_tpu.telemetry.metrics import METRICS

NO_CACHE = {"plan_cache_enabled": False,
            "fragment_result_cache_enabled": False,
            "page_source_cache_enabled": False}

#: small batches => many splits through the pump, so lifecycle events
#: land mid-stream instead of racing a single-split query
SLOW_PROPS = {**NO_CACHE, "batch_rows": 1024}

#: pump-ELIGIBLE shape (scan -> agg fold -> emit): the lifecycle
#: tests below must land their events inside the pump fast path, so
#: the query has to take it
SQL_AGG = ("select returnflag, count(*) c, sum(quantity) q "
           "from lineitem group by returnflag")

#: join + blocking sort: every driver shape here (build sink, probe
#: chain, sort-terminated final) is in the widened streamable set
SQL_JOIN = ("select o.orderpriority, count(*) c "
            "from orders o join customer c on o.custkey = c.custkey "
            "group by o.orderpriority order by o.orderpriority")

#: the dashboard mix: an aggregation-heavy repeat workload (scan+agg
#: q1/q6, a 3-way join q3, a join+group q13) — the shape a BI
#: dashboard refresh sends at a serving cluster
DEFAULT_MIX = ("q1", "q3", "q6", "q13")


@pytest.fixture
def pump_state():
    """Restore the process-wide pump switch after each test."""
    prev = driver_mod.pump_enabled()
    yield
    driver_mod.set_pump(prev)


@pytest.fixture
def small_executor():
    ex = TaskExecutor(workers=2, quantum_ms=5,
                      level_thresholds_s=(0.0, 0.01, 0.05, 0.2, 1.0))
    prev = set_task_executor(ex)
    yield ex
    set_task_executor(prev)
    ex.shutdown()


def _pumped(n0: float) -> bool:
    return METRICS.get("presto_tpu_pump_drivers_total",
                       status="pump") > n0


def _run_suite(names, pump: bool):
    from presto_tpu.tools.verifier import load_suite
    suite = load_suite("tpch")
    driver_mod.set_pump(pump)
    r = LocalRunner("tpch", "tiny", properties=dict(NO_CACHE))
    return {n: r.execute(suite[n]).rows() for n in names}


def test_pump_identity_serving_mix(pump_state):
    """The serving mix answers byte-identically pump-on vs pump-off,
    and the on-run really engaged the pump."""
    off = _run_suite(DEFAULT_MIX, pump=False)
    n0 = METRICS.get("presto_tpu_pump_drivers_total", status="pump")
    on = _run_suite(DEFAULT_MIX, pump=True)
    assert _pumped(n0), "no driver took the pump fast path"
    assert on == off


def test_pump_join_and_sort_pipelines_pump(pump_state):
    """Join builds, probe chains, and sort-terminated pipelines are
    all in the widened streamable set: a join + ORDER BY query runs
    every one of its drivers through the pump, byte-identically."""
    driver_mod.set_pump(False)
    r = LocalRunner("tpch", "tiny", NO_CACHE)
    expected = r.execute(SQL_JOIN).rows()
    driver_mod.set_pump(True)
    n_pump0 = METRICS.get("presto_tpu_pump_drivers_total",
                          status="pump")
    n_step0 = METRICS.get("presto_tpu_pump_drivers_total",
                          status="step")
    r2 = LocalRunner("tpch", "tiny", NO_CACHE)
    assert r2.execute(SQL_JOIN).rows() == expected
    assert METRICS.get("presto_tpu_pump_drivers_total",
                       status="pump") > n_pump0
    assert METRICS.get("presto_tpu_pump_drivers_total",
                       status="step") == n_step0, \
        "a driver shape in the join query declined the pump"


@pytest.mark.slow
def test_pump_identity_full_tpch(pump_state):
    """The whole TPC-H suite pump-on vs pump-off (the slow lane's
    exhaustive byte-identity sweep)."""
    from presto_tpu.tools.verifier import load_suite
    names = sorted(load_suite("tpch"))
    off = _run_suite(names, pump=False)
    on = _run_suite(names, pump=True)
    for n in names:
        assert on[n] == off[n], n


def test_pump_zero_new_kernels(pump_state):
    """The zero-new-kernels oracle: every kernel family the pump-on
    run compiles was already minted by the pump-off run — the pump
    must never change WHAT is computed, only when batches move."""
    _run_suite(DEFAULT_MIX, pump=False)
    fam_off = set(METRICS.by_label(
        "presto_tpu_kernel_compiles_total", "kernel"))
    before = METRICS.by_label(
        "presto_tpu_kernel_compiles_total", "kernel")
    _run_suite(DEFAULT_MIX, pump=True)
    fresh = set(METRICS.delta_by_label(
        "presto_tpu_kernel_compiles_total", "kernel", before))
    assert fresh <= fam_off, f"pump minted new kernels: {fresh - fam_off}"


def _arm_stall(delay_s=0.05):
    from presto_tpu.execution import faults

    def sleeper(ctx):
        time.sleep(delay_s)
        return False
    return faults.arm("operator.add_input", trigger="always",
                      predicate=sleeper)


def test_pump_cancel_lands_mid_pump(pump_state, small_executor):
    """Cancel flips while the pump is streaming splits: the quantum
    checkpoint surfaces kind="cancelled" (the pump honors quanta, it
    does not run the source dry in one sitting)."""
    from presto_tpu.execution import faults
    driver_mod.set_pump(True)
    flag = threading.Event()
    r = LocalRunner("tpch", "tiny", properties=dict(SLOW_PROPS))
    r.execute(SQL_AGG)  # warm kernels: the cancel run is all drive
    _arm_stall(0.05)
    try:
        n0 = METRICS.get("presto_tpu_pump_drivers_total",
                         status="pump")
        timer = threading.Timer(0.15, flag.set)
        timer.start()
        with pytest.raises(QueryError) as ei:
            r.execute(SQL_AGG, cancel=flag.is_set)
        assert ei.value.kind == "cancelled"
        assert _pumped(n0)
    finally:
        timer.cancel()
        faults.disarm()


def test_pump_deadline_lands_mid_pump(pump_state, small_executor):
    """query_max_run_time_ms expires mid-pump -> structured
    deadline_exceeded within a few quanta."""
    from presto_tpu.execution import faults
    driver_mod.set_pump(True)
    _arm_stall(0.05)
    try:
        r = LocalRunner("tpch", "tiny", properties={
            **SLOW_PROPS, "query_max_run_time_ms": 150})
        t0 = time.monotonic()
        with pytest.raises(QueryError) as ei:
            r.execute(SQL_AGG)
        assert ei.value.kind == "deadline_exceeded"
        assert time.monotonic() - t0 < 30.0
    finally:
        faults.disarm()


def test_pump_chaos_quantum_site(pump_state, small_executor):
    """The `executor.quantum` chaos site fires under a pumping driver
    and fails the query cleanly; the executor survives and the next
    statement answers byte-identically to pump-off."""
    from presto_tpu.execution import faults
    driver_mod.set_pump(False)
    r = LocalRunner("tpch", "tiny", properties=dict(SLOW_PROPS))
    expected = r.execute(SQL_AGG).rows()
    driver_mod.set_pump(True)
    inj = faults.arm("executor.quantum", trigger="nth", n=3)
    _arm_stall(0.02)
    try:
        with pytest.raises(faults.InjectedFault):
            r.execute(SQL_AGG)
        assert inj.fired == 1
        faults.disarm()
        assert r.execute(SQL_AGG).rows() == expected
        snap = small_executor.snapshot()
        assert snap["tasks"] == 0 and snap["running_drivers"] == 0
    finally:
        faults.disarm()


@pytest.mark.parametrize("sql", [SQL_AGG, SQL_JOIN], ids=["agg", "join"])
def test_traced_statement_takes_the_pump(pump_state, sql):
    """A `query_trace_enabled` statement runs the loop every other
    statement runs (PR 39: the recorder's `op:` events come from the
    one hand-off, so the trace gate left `_pump_ok`): its drivers
    count under status="pump", none under "step", its recorder holds
    the per-hand-off events, and it answers what the untraced one
    answers."""
    driver_mod.set_pump(True)
    expected = LocalRunner("tpch", "tiny", NO_CACHE).execute(sql).rows()
    r = LocalRunner("tpch", "tiny", properties={
        **NO_CACHE, "query_trace_enabled": True})
    n_pump0 = METRICS.get("presto_tpu_pump_drivers_total", status="pump")
    n_step0 = METRICS.get("presto_tpu_pump_drivers_total", status="step")
    res = r.execute(sql)
    assert res.rows() == expected
    assert _pumped(n_pump0), "the traced statement declined the pump"
    assert METRICS.get("presto_tpu_pump_drivers_total",
                       status="step") == n_step0
    ops = {ev["name"] for ev in res.trace_events
           if ev.get("cat") == "operator"}
    assert any(n.startswith("op:scan:") and n.endswith(".get_output")
               for n in ops), ops
    assert any(n.endswith(".add_input") for n in ops), ops
    # hand-offs, not polls: a get_output that returned nothing and a
    # finish leave no event
    assert not any(n.endswith(".finish") for n in ops), ops


def test_passes_are_counted_by_whether_they_moved(pump_state):
    """Every loop's passes reach presto_tpu_driver_passes_total when
    the driver closes: the pump's splits are passes that moved, and a
    pair walk counts by what it returned."""
    def passes():
        return {m: METRICS.get("presto_tpu_driver_passes_total", moved=m)
                for m in ("yes", "no")}
    r = LocalRunner("tpch", "tiny", properties=dict(SLOW_PROPS))
    for pump in (True, False):
        driver_mod.set_pump(pump)
        before = passes()
        splits0 = METRICS.get("presto_tpu_pump_splits_total")
        r.execute(SQL_AGG)
        after = passes()
        moved = after["yes"] - before["yes"]
        splits = METRICS.get("presto_tpu_pump_splits_total") - splits0
        # every split is a pass that moved; the pair loop moves the
        # same batches in walks of its own
        assert moved >= max(splits, 2), (pump, moved, splits)
        assert bool(splits) == pump
        assert after["no"] >= before["no"]
