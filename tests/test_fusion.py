"""Whole-fragment XLA compilation correctness
(docs/FRAGMENT_COMPILATION.md).

Oracles:
- byte-identity: every TPC-H tier-1 query produces IDENTICAL rows
  with `fragment_fusion_enabled` on vs off — the hard correctness bar
  (fusion changes the number of dispatches, never values or order).
- coverage: the serving mix (q1/q3/q6/q13) fuses its leaf fragments;
  silent fallback is the failure mode tools/fusion_report.py exists
  to catch, and every declined chain carries an explicit reason.
- fragment-result cache: fragment_record's commit-at-close semantics
  survive the single-call drive path — a fused fragment records on
  the first run and replays byte-identically on the second,
  including through a LIMIT terminal's early abandonment.
- lifecycle: cancel/deadline checkpoints still fire inside a fused
  fragment, and a fused LIMIT still abandons the scan early.
- amortization: a fused query compiles ZERO new kernels on a second,
  differently-sized split — the `fragment` family rides the shape-
  bucket ladder exactly like the unfused families.
- telemetry: two concurrent cold callers of one instrumented kernel
  BOTH classify their wall as compile (the two-cold-queries race
  hardened in telemetry/kernels.py).
"""

import threading

import pytest

from tpch_queries import QUERIES

#: serving caches off: these tests must observe real planning and
#: kernel execution, not cache replays
_NO_CACHES = {
    "plan_cache_enabled": False,
    "fragment_result_cache_enabled": False,
    "page_source_cache_enabled": False,
}


@pytest.fixture(scope="module")
def runners():
    """(fused runner, unfused runner) over the same tiny TPC-H data."""
    from presto_tpu.runner.local import LocalRunner
    on = LocalRunner("tpch", "tiny", properties=dict(_NO_CACHES))
    off = LocalRunner("tpch", "tiny",
                      properties={**_NO_CACHES,
                                  "fragment_fusion_enabled": False})
    return on, off


# ---------------------------------------------------------------------------
# byte-identity across the tier-1 TPC-H suite


@pytest.mark.parametrize("qn", sorted(QUERIES))
def test_tpch_fused_vs_unfused_identical(runners, qn):
    on, off = runners
    sql = QUERIES[qn]
    assert on.execute(sql).rows() == off.execute(sql).rows(), qn


# ---------------------------------------------------------------------------
# coverage: the serving mix fuses, fallbacks carry reasons


def test_serving_mix_fuses_leaf_fragments(runners):
    """q1/q3/q6/q13 — the dashboard mix — each fuse >= 1 leaf
    fragment (the regression guard tools/fusion_report.py
    --assert-fused runs from the command line)."""
    on, _ = runners
    for qn in (1, 3, 6, 13):
        fr = on.execute(QUERIES[qn]).fusion_report
        assert fr is not None and fr["fused"] >= 1, (qn, fr)


def test_fusion_report_rides_the_result(runners):
    on, off = runners
    fr = on.execute(QUERIES[6]).fusion_report
    assert fr["fused"] >= 1
    for e in fr["fragments"]:
        # every candidate fused, carries an explicit reason, or both
        # (PARTIAL: the chain collapsed, the terminal was kept out)
        assert e["fused"] is not None or e["reason"] is not None, e
    # pass disabled -> no report (the attribute stays None)
    assert off.execute(QUERIES[6]).fusion_report is None


def test_selective_chain_keeps_compaction(runners):
    """The fold-terminal selectivity gate (planner/fusion.py): q6's
    ~2%-selective filter chain must NOT fold into its aggregation —
    fused, the agg ran over full-width dead lanes and measured 1.5x
    SLOWER than compact-then-fold. The chain still collapses into one
    program; the terminal stays out, with the stable reason."""
    on, off = runners
    fr = on.execute(QUERIES[6]).fusion_report
    gated = [e for e in fr["fragments"]
             if e["reason"] == "selective_chain"]
    assert gated, fr
    for e in gated:
        # the terminal exists but was kept OUT of the fused label
        assert e["terminal"] is not None, e
        assert e["fused"] is None \
            or e["terminal"] not in e["fused"], e
    assert on.execute(QUERIES[6]).rows() \
        == off.execute(QUERIES[6]).rows()


def test_selectivity_gate_boundary(runners):
    """1/NDV equality selectivities straddle the quarter threshold:
    shipmode (7-value dictionary, 1/7 < 1/4) trips the gate;
    returnflag (3-value dictionary, 1/3 >= 1/4) folds into the agg."""
    on, off = runners
    low_sql = ("select count(*) from lineitem "
               "where shipmode = 'AIR'")
    hi_sql = ("select count(*) from lineitem "
              "where returnflag = 'A'")
    low = on.execute(low_sql).fusion_report
    assert any(e["reason"] == "selective_chain"
               for e in low["fragments"]), low
    hi = on.execute(hi_sql).fusion_report
    assert any(e["fused"] and "aggregation" in e["fused"]
               for e in hi["fragments"]), hi
    for sql in (low_sql, hi_sql):
        assert on.execute(sql).rows() == off.execute(sql).rows()


def test_spillable_build_falls_back(runners):
    """A spill-eligible join build (spill allowed AND a finite memory
    budget) must NOT absorb its upstream chain into the probe trace —
    the spill partitioner reads key columns host-side."""
    from presto_tpu.runner.local import LocalRunner
    r = LocalRunner("tpch", "tiny",
                    properties={**_NO_CACHES, "spill_enabled": True,
                                "hbm_budget_bytes": 1 << 34})
    sql = ("select o.orderdate, l.extendedprice * l.discount v "
           "from lineitem l join orders o on l.orderkey = o.orderkey "
           "where l.extendedprice * l.discount > 3000 "
           "order by v desc, o.orderdate limit 5")
    res = r.execute(sql)
    reasons = res.fusion_report["fallback"]
    assert reasons.get("spillable_build", 0) >= 1, res.fusion_report
    # and the un-spillable default fuses the same probe chain. History
    # feedback pinned OFF: the spillable run above MEASURED this
    # chain's selectivity (~0.2, under the gate threshold), and a
    # measured-selective chain correctly declines probe fusion — this
    # test is about the spill decision, not the gate
    on = LocalRunner("tpch", "tiny",
                     properties={**_NO_CACHES,
                                 "history_based_optimization": False})
    fr = on.execute(sql).fusion_report
    assert fr["fallback"].get("spillable_build", 0) == 0
    assert any(e["fused"] and "lookup_join" in e["fused"]
               for e in fr["fragments"]), fr
    assert res.rows() == on.execute(sql).rows()


def test_explain_analyze_renders_fused_node(runners):
    on, _ = runners
    res = on.execute(
        "explain analyze select returnflag, count(*) from lineitem "
        "where quantity > 10 group by returnflag")
    text = "\n".join(row[0] for row in res.rows())
    assert "fused[filter_project+aggregation" in text, text


def test_filtered_out_rows_never_form_groups():
    """Regression: the fused agg kernel must group on the CHAIN's
    narrowed row_valid, not the scan batch's — a group value that
    exists only among filtered-out rows must not surface as an empty
    group (caught live by system.metadata.tables: catalogs filtered
    out still emitted their schemas with count 0)."""
    from presto_tpu.runner.local import LocalRunner
    on = LocalRunner("memory", "default", properties=dict(_NO_CACHES))
    off = LocalRunner("memory", "default",
                      properties={**_NO_CACHES,
                                  "fragment_fusion_enabled": False})
    off.catalogs.register("memory", on.catalogs.connector("memory"))
    # group value 99 exists ONLY where v <= 0 (filtered out)
    on.execute("CREATE TABLE gg1 AS SELECT "
               "CASE WHEN custkey % 3 = 0 THEN 99 "
               "ELSE custkey % 3 END g, "
               "CASE WHEN custkey % 3 = 0 THEN -1.0 "
               "ELSE acctbal END v "
               "FROM tpch.tiny.customer")
    sql = ("SELECT g, count(*) c FROM gg1 WHERE v > 0 "
           "GROUP BY g ORDER BY g")
    a, b = on.execute(sql), off.execute(sql)
    assert a.fusion_report["fused"] >= 1
    assert a.rows() == b.rows()
    assert all(g != 99 for g, _ in a.rows())


# ---------------------------------------------------------------------------
# fragment-result cache interaction


def test_fragment_cache_commit_and_replay_fused():
    """The single-call drive path keeps fragment_record's contract:
    commit at close() after a natural finish, replay byte-identical —
    including through a fused LIMIT's early abandonment."""
    from presto_tpu.cache import get_cache_manager
    from presto_tpu.runner.local import LocalRunner
    r = LocalRunner("tpch", "tiny",
                    properties={"plan_cache_enabled": False,
                                "page_source_cache_enabled": False})
    plain = LocalRunner("tpch", "tiny",
                        properties={**_NO_CACHES,
                                    "fragment_fusion_enabled": False})
    mgr = get_cache_manager()
    for sql in (
        # fused[filter_project+aggregation] fragment
        "select returnflag, count(*) c, sum(quantity) q from lineitem "
        "where quantity > 10 group by returnflag order by returnflag",
        # fused[filter_project+limit] fragment: the LIMIT abandons the
        # scan mid-fragment, but ITS OWN output is complete — record
        # commits it at close and replay serves the same rows
        "select quantity from lineitem where quantity > 30 "
        "order by quantity, orderkey, linenumber limit 5",
    ):
        hits0 = mgr.fragment.stats.snapshot()["hits"]
        first = r.execute(sql).rows()
        assert mgr.fragment.stats.snapshot()["hits"] == hits0
        second = r.execute(sql).rows()
        # the second run REPLAYED the recorded fragment...
        assert mgr.fragment.stats.snapshot()["hits"] > hits0, sql
        # ...byte-identically, and both match the unfused uncached run
        assert first == second == plain.execute(sql).rows(), sql


# ---------------------------------------------------------------------------
# lifecycle inside a fused fragment


def test_fused_limit_abandons_scan():
    """LIMIT early-termination survives fusion: with small batches the
    fused[filter_project+limit] operator stops pulling scan batches
    within a couple of driver rounds of the limit."""
    import re
    from presto_tpu.runner.local import LocalRunner
    r = LocalRunner("tpch", "tiny", properties=dict(_NO_CACHES))
    r.session.properties["batch_rows"] = 4096
    res = r.execute(
        "explain analyze select orderkey from lineitem "
        "where quantity > 0 limit 3")
    text = "\n".join(row[0] for row in res.rows())
    m = re.search(r"fused\[filter_project(?:\*\d+)?\+limit\] "
                  r"\[id=\d+\]  rows: ([\d,]+) -> 3", text)
    assert m, text
    m = re.search(r"scan:lineitem \[id=\d+\]  rows: 0 -> ([\d,]+)",
                  text)
    assert m, text
    # tiny lineitem holds 60175 rows; an abandoning scan stops after a
    # handful of 4096-row batches (async flag: a couple rounds' slack)
    assert int(m.group(1).replace(",", "")) < 30000, text


def test_cancel_checkpoint_inside_fused_fragment(runners):
    """A pre-cancelled query dies with the structured kind even though
    its whole leaf fragment is one fused dispatch (the checkpoint is
    the drive loop's, not any single operator's)."""
    from presto_tpu.runner.local import QueryError
    on, _ = runners
    sql = QUERIES[6]
    assert on.execute(sql).fusion_report["fused"] >= 1  # it DOES fuse
    ev = threading.Event()
    ev.set()
    with pytest.raises(QueryError) as ei:
        on.execute(sql, cancel=ev.is_set)
    assert ei.value.kind == "cancelled"


def test_deadline_checkpoint_inside_fused_fragment():
    from presto_tpu.execution import faults
    from presto_tpu.runner.local import LocalRunner, QueryError
    r = LocalRunner("tpch", "tiny",
                    properties={**_NO_CACHES,
                                "query_max_run_time_ms": 250})
    r.session.properties["batch_rows"] = 2048

    def sleeper(ctx):
        import time
        time.sleep(0.05)
        return False
    faults.arm("operator.add_input", trigger="always",
               predicate=sleeper)
    try:
        with pytest.raises(QueryError) as ei:
            r.execute("select returnflag, count(*) from lineitem "
                      "where quantity > 10 group by returnflag")
        assert ei.value.kind == "deadline_exceeded"
    finally:
        faults.disarm()


# ---------------------------------------------------------------------------
# compile amortization: the `fragment` family rides the bucket ladder


def test_fused_second_sized_split_zero_new_kernels():
    """A fused query compiles zero new kernels on a second,
    differently-sized split (same bucket): the fragment-family traces
    amortize exactly like the unfused families they replace."""
    from presto_tpu.runner.local import LocalRunner
    from presto_tpu.telemetry.metrics import METRICS

    r = LocalRunner("memory", "default",
                    properties={**_NO_CACHES,
                                "kernel_shape_buckets": True})
    r.execute("CREATE TABLE fz1 AS SELECT custkey a, acctbal b "
              "FROM tpch.tiny.customer LIMIT 100")
    r.execute("INSERT INTO fz1 SELECT custkey + 20000, acctbal "
              "FROM tpch.tiny.customer LIMIT 150")
    sql = ("SELECT a % 10 g, sum(b) s FROM fz1 WHERE b > 0 "
           "GROUP BY a % 10 ORDER BY g LIMIT 5")
    fam0 = METRICS.by_label("presto_tpu_kernel_compiles_total",
                            "kernel")
    res = r.execute(sql)
    assert res.fusion_report["fused"] >= 1          # it DOES fuse
    assert res.query_stats["kernel_compiles"] > 0   # cold
    # the cold compiles include the fragment family — the fused chain
    # is what compiled, not the standalone filter_project/agg_step
    delta = METRICS.delta_by_label(
        "presto_tpu_kernel_compiles_total", "kernel", fam0)
    assert delta.get("fragment", 0) > 0, delta
    assert r.execute(sql).query_stats["kernel_compiles"] == 0  # warm
    # grow from a TINY source: genuinely different raw capacity, same
    # kernel bucket
    r.execute("INSERT INTO fz1 SELECT regionkey + 10000, 1.5 "
              "FROM tpch.tiny.region")
    assert r.execute(sql).query_stats["kernel_compiles"] == 0


# ---------------------------------------------------------------------------
# concurrent compile detection (telemetry/kernels.py hardening)


def test_concurrent_cold_callers_both_book_compile():
    """The two-cold-queries race: B compiles (the jit cache grows
    mid-call); A — blocked on the compile the whole time — samples its
    `before` AFTER the growth, so its own before/after straddle no
    growth. The active-set marking must classify BOTH walls as
    compile, and the retrace counter must charge the trace ONCE."""
    from presto_tpu.telemetry import kernels as tk
    from presto_tpu.telemetry.metrics import METRICS

    class FakeJit:
        def __init__(self):
            self.size = 0

        def _cache_size(self):
            return self.size

    jit = FakeJit()
    b_inside = threading.Event()
    a_inside = threading.Event()
    release_b = threading.Event()
    release_a = threading.Event()

    def kernel(caller):
        if caller == "B":
            b_inside.set()
            assert release_b.wait(10)
            jit.size = 1           # the compile lands
        else:
            a_inside.set()
            assert release_a.wait(10)  # "blocked on the compile lock"
        return caller

    fam = "test_concurrent_race"
    wrapped = tk.instrument_kernel(kernel, fam, jits=[jit])

    def snap(name):
        return METRICS.by_label(name, "kernel").get(fam, 0)

    compiles0 = snap("presto_tpu_kernel_compiles_total")
    execute0 = snap("presto_tpu_kernel_execute_ns_total")
    retrace0 = METRICS.by_label("presto_tpu_kernel_retrace_total",
                                "kernel").get(fam, 0)

    tb = threading.Thread(target=wrapped, args=("B",))
    tb.start()
    assert b_inside.wait(10)
    # the growth becomes visible BEFORE A samples `before`
    jit.size = 1
    ta = threading.Thread(target=wrapped, args=("A",))
    ta.start()
    assert a_inside.wait(10)
    jit.size = 0            # restore so B's own call sees the growth
    release_b.set()
    tb.join(10)
    release_a.set()
    ta.join(10)
    assert not tb.is_alive() and not ta.is_alive()

    assert snap("presto_tpu_kernel_compiles_total") - compiles0 == 2
    # NO execute ns booked: A's compile-blocked wall is compile cost
    assert snap("presto_tpu_kernel_execute_ns_total") == execute0
    # ...but the trace itself is charged exactly once
    assert METRICS.by_label("presto_tpu_kernel_retrace_total",
                            "kernel").get(fam, 0) - retrace0 == 1


def test_two_concurrent_cold_queries_stay_consistent():
    """Integration shape of the same race: two threads cold-execute
    the same statement against one shared kernel LRU. Both must
    succeed with identical rows, book their compile time as compile,
    and leave the warm path clean (zero compiles afterwards)."""
    from presto_tpu.runner.local import LocalRunner
    a = LocalRunner("memory", "default", properties=dict(_NO_CACHES))
    b = LocalRunner("memory", "default", properties=dict(_NO_CACHES))
    b.catalogs.register("memory", a.catalogs.connector("memory"))
    a.execute("CREATE TABLE cc1 AS SELECT custkey k, acctbal v "
              "FROM tpch.tiny.customer")
    sql = ("SELECT k % 7 g, count(*) n, sum(v) s FROM cc1 "
           "WHERE v > 0 GROUP BY k % 7 ORDER BY g")
    out = {}

    def run(name, runner):
        out[name] = runner.execute(sql)

    ta = threading.Thread(target=run, args=("a", a))
    tb = threading.Thread(target=run, args=("b", b))
    ta.start(); tb.start()
    ta.join(60); tb.join(60)
    assert out["a"].rows() == out["b"].rows()
    # between them the cold pair really compiled...
    assert (out["a"].query_stats["kernel_compiles"]
            + out["b"].query_stats["kernel_compiles"]) > 0
    # ...and the race left the shared wrappers consistent: warm runs
    # on both runners are compile-free
    assert a.execute(sql).query_stats["kernel_compiles"] == 0
    assert b.execute(sql).query_stats["kernel_compiles"] == 0


def test_session_property_registered():
    from presto_tpu.session_properties import validate_set
    assert validate_set("fragment_fusion_enabled", False) is False
    with pytest.raises(ValueError):
        validate_set("fragment_fusion_enabled", "yes")


# ---------------------------------------------------------------------------
# selectivity stamping beyond FilterNode-derived FPs (PR 8 satellite)


SQL_SELECTIVE_JOIN_FILTER = (
    "select sum(l.extendedprice) from lineitem l join orders o "
    "on l.orderkey = o.orderkey and l.quantity + o.custkey < 50 "
    "and l.quantity * o.custkey < 100 "
    "where l.quantity + o.totalprice < 10000")

SQL_MILD_JOIN_FILTER = (
    "select sum(l.extendedprice) from lineitem l join orders o "
    "on l.orderkey = o.orderkey "
    "and (l.quantity + o.custkey < 1000 or l.quantity >= 1) "
    "where l.quantity + o.totalprice < 100000 "
    "or o.totalprice >= 0")


def test_join_filter_fp_carries_selectivity(runners):
    """The JoinNode.filter FilterProject (planner ~775) prefuses into
    the probe WITH a selectivity estimate — previously None (always
    fuse), which left the gate blind behind join filters."""
    from presto_tpu.operators.join_ops import LookupJoinOperatorFactory
    from presto_tpu.planner.local_planner import LocalExecutionPlanner
    from presto_tpu.planner.optimizer import optimize
    on, _ = runners
    plan = optimize(on.create_plan(SQL_SELECTIVE_JOIN_FILTER),
                    on.catalogs)
    lp = LocalExecutionPlanner(on.catalogs, on.session).plan(plan)
    probes = [f for pipe in lp.pipelines for f in pipe
              if isinstance(f, LookupJoinOperatorFactory)]
    assert probes, "query must plan a lookup join"
    # two default-selectivity conjuncts: 0.33^2, well under the gate
    assert probes[0].fused_selectivity is not None
    assert probes[0].fused_selectivity < 0.25


def test_selective_join_filter_gates_fold_terminal(runners):
    """Regression: a selective join filter (prefused into the probe)
    must gate the chain it feeds into the aggregation — the chain's
    own mild 0.33 estimate alone would fold (>= 0.25), only the
    INHERITED probe selectivity trips the gate. Byte-identity with
    fusion off is the hard bar."""
    on, off = runners
    res = on.execute(SQL_SELECTIVE_JOIN_FILTER)
    gated = [e for e in res.fusion_report["fragments"]
             if e["terminal"] and "aggregation" in e["terminal"]
             and e["reason"] == "selective_chain"]
    assert gated, res.fusion_report
    assert res.rows() == off.execute(SQL_SELECTIVE_JOIN_FILTER).rows()


def test_mild_join_filter_still_folds(runners):
    """Contrast: with MILD estimates on both the prefused join filter
    and the WHERE chain (OR predicates, ~0.55 each — product ~0.30),
    the gate stays open and the chain folds into the aggregation."""
    on, off = runners
    res = on.execute(SQL_MILD_JOIN_FILTER)
    folded = [e for e in res.fusion_report["fragments"]
              if e["terminal"] and "aggregation" in e["terminal"]
              and e["fused"]]
    assert folded, res.fusion_report
    assert res.rows() == off.execute(SQL_MILD_JOIN_FILTER).rows()
