"""History-based adaptive optimization (presto_tpu/history): the
measure -> remember -> replan loop.

Contracts under test (docs/ADAPTIVE.md):
  * byte-identity: history-driven plans change HOW, never WHAT — every
    query answers identically with history on (first and re-planned
    executions) and off
  * q6 fuses FULLY on its second execution purely via measured
    selectivity (the static 0.33-family estimate wrongly gated it —
    it cannot see the scan's pushed-down constraint already pruned)
  * a measured chain still under the gate threshold upgrades to FULL
    fusion with an in-trace compaction sized by the measurement, and
    an overflowing compaction retries cleanly without it
  * persistence: a restarted runner loads the store from disk and
    plans from history with ZERO re-measurement
  * invalidation: INSERT bumps the table version, making stale
    history unreachable (fingerprints fold the version in)
  * commit discipline: failed, cancelled, and fault-armed runs record
    nothing
  * observability: system.runtime.plan_history, EXPLAIN provenance
    annotations, the history counters, and the sanitize auditor
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from tpch_queries import QUERIES  # noqa: E402

NO_CACHES = {
    "plan_cache_enabled": False,
    "fragment_result_cache_enabled": False,
    "page_source_cache_enabled": False,
}


@pytest.fixture(autouse=True)
def _fresh_store():
    from presto_tpu import history
    history.reset_history_store()
    yield
    history.reset_history_store()


def _runner(schema="tiny", **props):
    from presto_tpu.runner.local import LocalRunner
    return LocalRunner("tpch", schema, {**NO_CACHES, **props})


def _agg_entries(res):
    return [e for e in res.fusion_report["fragments"]
            if "aggregation" in (e["terminal"] or "")]


# ---------------------------------------------------------------------------
# store unit behavior


def test_store_merge_decay_and_generation():
    from presto_tpu.history.store import HistoryStore
    s = HistoryStore()
    assert s.commit([{"key": "k1", "rows": 100, "in_rows": 1000}])
    g1 = s.generation()
    e = s.get("k1")
    assert e["rows"] == 100 and e["in_rows"] == 1000
    # a confirming re-measurement decays in WITHOUT a generation bump
    assert not s.commit([{"key": "k1", "rows": 102,
                          "in_rows": 1000}])
    assert s.generation() == g1
    e = s.get("k1")
    assert 100 < e["rows"] < 102 and e["n"] == 2
    # a material move (>20% relative) bumps the generation
    assert s.commit([{"key": "k1", "rows": 500, "in_rows": 1000}])
    assert s.generation() == g1 + 1


def test_store_bounds_and_eviction():
    from presto_tpu.history import store as st
    s = st.HistoryStore()
    n = st.HISTORY_MAX_ENTRIES + 50
    s.commit([{"key": f"k{i}", "rows": i} for i in range(n)])
    assert len(s) == st.HISTORY_MAX_ENTRIES
    assert s.evictions == 50
    assert s.bytes == sum(st.entry_bytes(k)
                          for k, _ in s.entries())
    assert s.bytes <= st.HISTORY_MAX_BYTES
    # oldest keys evicted first (LRU)
    assert s.get("k0") is None and s.get(f"k{n - 1}") is not None


def test_history_auditor_catches_ledger_drift():
    from presto_tpu.history.store import HistoryStore
    from presto_tpu.sanitize.auditors import audit_history_stores
    s = HistoryStore()
    s.commit([{"key": "k1", "rows": 1}])
    assert audit_history_stores() == []
    s.bytes += 123  # corrupt the ledger
    violations = audit_history_stores()
    assert violations and violations[0].subsystem == "history"
    s.bytes -= 123


# ---------------------------------------------------------------------------
# recording + feedback on the local runner


def test_records_measured_rows_and_selectivity():
    from presto_tpu import history
    r = _runner()
    r.execute(QUERIES[6])
    store = history.get_history_store(create=False)
    assert store is not None and len(store) >= 3
    sels = [e["rows"] / e["in_rows"] for _, e in store.entries()
            if e.get("in_rows")]
    # the q6 filter's measured surviving fraction (over the
    # constraint-pruned scan output) — a real measurement, not 0.33^k
    assert sels and all(0.0 < s <= 1.0 for s in sels)


def test_second_execution_plans_from_history():
    from presto_tpu.planner.stats import StatsEstimator
    from presto_tpu import history
    r = _runner()
    r.execute(QUERIES[6])
    # the OPTIMIZED plan (constraint pushdown included) is what was
    # measured — fingerprints cover the scan's pushed constraint
    from presto_tpu.planner.local_planner import prune_unused_columns
    from presto_tpu.planner.optimizer import optimize
    plan = optimize(r.create_plan(QUERIES[6]), r.catalogs,
                    session=r.session)
    prune_unused_columns(plan)
    view = history.view_for(r.catalogs, r.session.properties)
    assert view is not None
    est = StatsEstimator(r.catalogs, history=view)
    scan = plan
    while scan.sources():
        scan = scan.sources()[0]
    est.estimate(scan)
    assert est.provenance_of(scan) == "history"


def test_explain_renders_provenance():
    r = _runner()
    before = "\n".join(
        row[0] for row in r.execute("explain " + QUERIES[6]).rows())
    assert "[static]" in before and "[history]" not in before
    r.execute(QUERIES[6])
    after = "\n".join(
        row[0] for row in r.execute("explain " + QUERIES[6]).rows())
    assert "[history]" in after and "sel=" in after


def test_plan_history_system_table():
    r = _runner()
    r.execute(QUERIES[6])
    rows = r.execute(
        "select fingerprint, output_rows, selectivity, observations "
        "from system.runtime.plan_history").rows()
    assert rows and all(row[1] >= 0 and row[3] >= 1 for row in rows)
    assert any(row[2] is not None for row in rows)  # a selectivity


def test_history_metrics_counters():
    from presto_tpu.telemetry.metrics import METRICS
    r = _runner()
    rec0 = METRICS.total("presto_tpu_history_records_total")
    hit0 = METRICS.total("presto_tpu_history_hits_total")
    r.execute(QUERIES[6])
    assert METRICS.total("presto_tpu_history_records_total") > rec0
    r.execute(QUERIES[6])
    assert METRICS.total("presto_tpu_history_hits_total") > hit0


# ---------------------------------------------------------------------------
# the q6 acceptance oracle + the in-trace compaction upgrade


def test_q6_fuses_fully_on_second_execution_sf0_1():
    """The acceptance bar: q6 on the serving scale factor is gated by
    the STATIC estimate (which cannot see the scan's pushed-down
    shipdate constraint already pruned the input), and fuses FULLY on
    its second execution purely via the measured selectivity —
    byte-identical to history off."""
    r = _runner("sf0_1")
    res1 = r.execute(QUERIES[6])
    (e1,) = _agg_entries(res1)
    assert e1["fused"] is None and e1["reason"] == "selective_chain"
    assert e1["sel_provenance"] == "static"
    res2 = r.execute(QUERIES[6])
    (e2,) = _agg_entries(res2)
    assert e2["fused"] and "aggregation" in e2["fused"], e2
    assert e2["reason"] is None  # FULL, not PARTIAL
    assert e2["sel_provenance"] == "history"
    off = _runner("sf0_1", history_based_optimization=False)
    res3 = off.execute(QUERIES[6])
    (e3,) = _agg_entries(res3)
    assert e3["fused"] is None  # still gated without history
    assert res1.rows() == res2.rows() == res3.rows()


def test_measured_selective_chain_compacts_in_trace():
    """A chain measured well under the gate threshold (shielded from
    constraint pushdown by a subquery projection) upgrades to FULL
    fusion with a history-sized in-trace compaction."""
    r = _runner("sf0_1")
    sql = ("select sum(extendedprice) from "
           "(select extendedprice, quantity q from lineitem) "
           "where q < 5")
    res1 = r.execute(sql)
    (e1,) = _agg_entries(res1)
    assert e1["reason"] == "selective_chain"  # PARTIAL chain collapse
    res2 = r.execute(sql)
    (e2,) = _agg_entries(res2)
    assert e2["reason"] is None and e2["sel_provenance"] == "history"
    assert 0 < e2["history_compact"] < 1  # compacted in-trace
    assert res1.rows() == res2.rows()


def test_compact_overflow_retries_without_history_fusion():
    """A store poisoned to claim near-zero selectivity sizes the
    compaction bucket far too small: the deferred overflow check must
    fail the fused attempt and the retry (history fusion off) must
    still answer byte-identically."""
    from presto_tpu import history
    r = _runner("sf0_1")
    sql = ("select sum(extendedprice) from "
           "(select extendedprice, quantity q from lineitem) "
           "where q < 5")
    res1 = r.execute(sql)
    store = history.get_history_store()
    with store._lock:
        for e in store._entries.values():
            if e.get("in_rows") and 0 < e["rows"] / e["in_rows"] < 0.25:
                e["rows"] = e["in_rows"] * 0.00005
        store._generation += 1
    res2 = r.execute(sql)
    (e2,) = _agg_entries(res2)
    # the surviving execution is the SAFE retry: gated PARTIAL chain
    assert e2["reason"] == "selective_chain", e2
    assert res1.rows() == res2.rows()


# ---------------------------------------------------------------------------
# byte-identity sweeps


_MIX = (1, 3, 5, 6, 9, 13, 18)


@pytest.mark.parametrize("qid", _MIX)
def test_history_on_off_byte_identity_mix(qid, identity_runners):
    on, off = identity_runners
    first = on.execute(QUERIES[qid]).rows()
    second = on.execute(QUERIES[qid]).rows()  # re-planned from history
    base = off.execute(QUERIES[qid]).rows()
    assert first == base and second == base


@pytest.fixture(scope="module")
def identity_runners():
    return (_runner(), _runner(history_based_optimization=False))


@pytest.mark.slow
def test_history_on_off_byte_identity_full_suite(identity_runners):
    on, off = identity_runners
    for qid in sorted(QUERIES):
        first = on.execute(QUERIES[qid]).rows()
        second = on.execute(QUERIES[qid]).rows()
        base = off.execute(QUERIES[qid]).rows()
        assert first == base and second == base, f"q{qid}"


# ---------------------------------------------------------------------------
# persistence + restart


def test_restart_roundtrip_zero_remeasurement(tmp_path):
    from presto_tpu import history
    d = str(tmp_path / "hist")
    # build the store through a history_dir-configured runner
    from presto_tpu.runner.local import LocalRunner
    r1 = LocalRunner("tpch", "tiny", dict(NO_CACHES),
                     history_dir=d)
    r1.execute(QUERIES[6])
    store = history.get_history_store(create=False)
    assert store is not None and len(store) > 0
    assert os.path.exists(os.path.join(d, "history.json"))
    entries_before = dict(store.entries())
    # "restart": drop the process-wide store, build a NEW runner on
    # the same dir — it must plan from MEASURED history immediately,
    # with zero fresh measurements required
    history.reset_history_store()
    r2 = LocalRunner("tpch", "tiny", dict(NO_CACHES),
                     history_dir=d)
    store2 = history.get_history_store(create=False)
    assert store2 is not None and store2 is not store
    assert dict(store2.entries()).keys() == entries_before.keys()
    assert store2.records == 0  # nothing re-measured yet
    text = "\n".join(
        row[0] for row in r2.execute("explain " + QUERIES[6]).rows())
    assert "[history]" in text
    # and the plans still answer identically
    assert r2.execute(QUERIES[6]).rows() == r1.execute(
        QUERIES[6]).rows()


def test_insert_bumps_version_and_stale_history_is_ignored():
    from presto_tpu import history
    r = _runner()
    r.execute("create table memory.default.t as "
              "select orderkey k, quantity v from tpch.tiny.lineitem")
    sql = "select count(*) from memory.default.t where v < 10"
    r.execute(sql)
    text = "\n".join(
        row[0] for row in r.execute("explain " + sql).rows())
    assert "[history]" in text
    n_before = len(history.get_history_store(create=False))
    # INSERT bumps the table version: every fingerprint over t changes
    r.execute("insert into memory.default.t values (1, 1.0)")
    text = "\n".join(
        row[0] for row in r.execute("explain " + sql).rows())
    assert "[history]" not in text  # stale history unreachable
    # re-execution re-measures under the NEW version
    r.execute(sql)
    assert len(history.get_history_store(create=False)) > n_before
    text = "\n".join(
        row[0] for row in r.execute("explain " + sql).rows())
    assert "[history]" in text


# ---------------------------------------------------------------------------
# commit discipline


def test_failed_and_cancelled_runs_record_nothing():
    from presto_tpu import history
    from presto_tpu.runner.local import QueryError
    r = _runner()
    with pytest.raises(QueryError):
        r.execute("select nosuchcol from lineitem")
    store = history.get_history_store(create=False)
    assert store is None or len(store) == 0
    # cancelled mid-drive: the kill raises out before the tap
    with pytest.raises(QueryError):
        r.execute(QUERIES[6], cancel=lambda: True)
    store = history.get_history_store(create=False)
    assert store is None or len(store) == 0


def test_fault_armed_runs_record_nothing():
    from presto_tpu import history
    from presto_tpu.execution import faults
    r = _runner()
    faults.arm("cache.put", trigger="nth", n=100000)
    try:
        r.execute(QUERIES[6])  # succeeds — but the registry is armed
    finally:
        faults.disarm()
    store = history.get_history_store(create=False)
    assert store is None or len(store) == 0
    # disarmed, the same query records normally
    r.execute(QUERIES[6])
    assert len(history.get_history_store(create=False)) > 0


# ---------------------------------------------------------------------------
# tools + serving bench


def test_history_report_tool(capsys):
    from presto_tpu.tools.history_report import main
    assert main(["--mix", "q6", "--json"]) == 0
    out = capsys.readouterr().out
    import json
    doc = json.loads(out)
    assert doc["all_identical"] is True
    assert "q6" in doc["queries"]
    assert doc["queries"]["q6"]["history_estimates"] > 0
    # dump mode renders the store populated by the diff runs
    assert main(["--dump"]) == 0
    assert "rows=" in capsys.readouterr().out
