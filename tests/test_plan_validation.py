"""PlanChecker battery (planner/validation.py): seeded plan
corruptions must be caught and attributed to the right pass; real
plans — every tier-1 TPC-H query and the serving mix — must validate
clean at every pass boundary with byte-identical results."""

import dataclasses

import pytest

from presto_tpu.expr.ir import Call, InputRef, Literal
from presto_tpu.planner import nodes as N
from presto_tpu.planner.validation import (
    CHECKER, PlanValidationError, expr_deterministic,
    plan_deterministic, validation_enabled,
)
from presto_tpu.runner.local import LocalRunner, Session
from presto_tpu.types import BIGINT, BOOLEAN
from tests.tpch_queries import QUERIES

#: the dashboard mix (scan+agg q1/q6, 3-way join q3, join+group q13)
SERVING_MIX = (1, 3, 6, 13)


@pytest.fixture(scope="module")
def runner():
    return LocalRunner("tpch", "tiny")


def _plan(runner, sql):
    """analyzed + optimized plan (validation already ran on both
    boundaries inside _plan_query's helpers; this rebuilds fresh so
    corruption tests own the object)."""
    from presto_tpu.planner.optimizer import optimize
    return optimize(runner.create_plan(sql), runner.catalogs)


def _violations(exc: PlanValidationError):
    return {v.rule for v in exc.violations}


def _find(root, node_type):
    stack, seen = [root], set()
    while stack:
        n = stack.pop()
        if id(n) in seen:
            continue
        seen.add(id(n))
        if isinstance(n, node_type):
            return n
        stack.extend(n.sources())
    raise AssertionError(f"plan has no {node_type.__name__}")


# ---------------------------------------------------------------------------
# seeded corruptions (the >= 10 battery) — each asserts BOTH the rule
# and the pass attribution


def test_corrupt_dangling_filter_symbol(runner):
    plan = _plan(runner, "select name from nation where nationkey > 3")
    f = _find(plan, N.FilterNode)
    f.predicate = Call("greater_than", (
        InputRef("no_such_symbol", BIGINT), Literal(3, BIGINT)),
        BOOLEAN)
    with pytest.raises(PlanValidationError) as ei:
        CHECKER.check_plan(plan, "optimizer")
    assert ei.value.pass_name == "optimizer"
    assert "dangling-symbol" in _violations(ei.value)


def test_corrupt_duplicate_output_symbol(runner):
    plan = _plan(runner, "select name, regionkey from nation")
    scan = _find(plan, N.TableScanNode)
    scan.output = scan.output + (scan.output[0],)
    with pytest.raises(PlanValidationError) as ei:
        CHECKER.check_plan(plan, "analysis")
    assert ei.value.pass_name == "analysis"
    assert "duplicate-output-symbol" in _violations(ei.value)


def test_corrupt_plan_cycle(runner):
    plan = _plan(runner, "select name from nation where nationkey > 3")
    f = _find(plan, N.FilterNode)
    f.source = plan  # link a node to its own ancestor
    with pytest.raises(PlanValidationError) as ei:
        CHECKER.check_plan(plan, "optimizer")
    assert "plan-cycle" in _violations(ei.value)


def test_corrupt_project_unassigned_output(runner):
    plan = _plan(runner, "select nationkey + 1 as k from nation")
    p = _find(plan, N.ProjectNode)
    p.output = p.output + (N.Field("phantom_col", BIGINT),)
    with pytest.raises(PlanValidationError) as ei:
        CHECKER.check_plan(plan, "optimizer")
    assert "dangling-symbol" in _violations(ei.value)


def test_corrupt_join_criterion(runner):
    plan = _plan(runner, """
        select n.name from nation n, region r
        where n.regionkey = r.regionkey""")
    j = _find(plan, N.JoinNode)
    l, r = j.criteria[0]
    j.criteria[0] = ("bogus_probe_key", r)
    with pytest.raises(PlanValidationError) as ei:
        CHECKER.check_plan(plan, "optimizer")
    assert "dangling-symbol" in _violations(ei.value)


def _exchanged(runner, sql, session=None):
    from presto_tpu.planner.exchanges import add_exchanges
    from presto_tpu.planner.local_planner import prune_unused_columns
    plan = _plan(runner, sql)
    prune_unused_columns(plan)
    return add_exchanges(plan, runner.catalogs,
                         session or runner.session)


def test_corrupt_unknown_exchange_scheme(runner):
    plan = _exchanged(runner, "select count(*) from lineitem")
    ex = _find(plan, N.ExchangeNode)
    ex.scheme = "shuffle"  # not an engine scheme
    with pytest.raises(PlanValidationError) as ei:
        CHECKER.check_plan(plan, "exchanges")
    assert ei.value.pass_name == "exchanges"
    assert "unknown-exchange-scheme" in _violations(ei.value)


def test_corrupt_gather_with_partition_keys(runner):
    plan = _exchanged(runner, "select count(*) from lineitem")
    ex = _find(plan, N.ExchangeNode)
    assert ex.scheme == "gather"
    ex.partition_keys = [ex.source.output[0].symbol]
    with pytest.raises(PlanValidationError) as ei:
        CHECKER.check_plan(plan, "exchanges")
    assert "exchange-keys" in _violations(ei.value)


def test_corrupt_exchange_schema_drift(runner):
    plan = _exchanged(runner, "select count(*) from lineitem")
    ex = _find(plan, N.ExchangeNode)
    ex.output = (N.Field("not_the_source_schema", BIGINT),)
    with pytest.raises(PlanValidationError) as ei:
        CHECKER.check_plan(plan, "exchanges")
    assert "exchange-schema" in _violations(ei.value)


def test_corrupt_repartition_key_not_produced(runner):
    plan = _exchanged(runner, """
        select suppkey, sum(quantity) from lineitem group by suppkey""")
    # the partial->final repartition on the group key
    ex = next(n for n in _walk(plan)
              if isinstance(n, N.ExchangeNode)
              and n.scheme == "repartition")
    ex.partition_keys = ["no_such_key"]
    with pytest.raises(PlanValidationError) as ei:
        CHECKER.check_plan(plan, "exchanges")
    assert "exchange-keys" in _violations(ei.value)


def _walk(root):
    stack, seen = [root], set()
    while stack:
        n = stack.pop()
        if id(n) in seen:
            continue
        seen.add(id(n))
        yield n
        stack.extend(n.sources())


def _fragmented(runner, sql):
    from presto_tpu.planner.exchanges import fragment_plan
    return fragment_plan(_exchanged(runner, sql))


def test_corrupt_duplicate_fragment_id(runner):
    fplan = _fragmented(runner, "select count(*) from lineitem")
    some = next(iter(fplan.fragments.values()))
    fplan.fragments[max(fplan.fragments) + 7] = some  # id collision
    with pytest.raises(PlanValidationError) as ei:
        CHECKER.check_fragments(fplan, "exchanges")
    assert "duplicate-fragment-id" in _violations(ei.value)


def test_corrupt_duplicate_exchange_id(runner):
    fplan = _fragmented(runner, "select count(*) from lineitem")
    xid, edge = next(iter(fplan.edges.items()))
    fplan.edges[xid + 101] = edge  # same edge under a second id
    with pytest.raises(PlanValidationError) as ei:
        CHECKER.check_fragments(fplan, "exchanges")
    assert "duplicate-exchange-id" in _violations(ei.value)


def test_corrupt_edge_partitioning_mismatch(runner):
    fplan = _fragmented(runner, """
        select suppkey, sum(quantity) from lineitem group by suppkey""")
    edge = next(e for e in fplan.edges.values()
                if e.scheme == "repartition")
    edge.partition_keys = ["not_a_producer_symbol"]
    with pytest.raises(PlanValidationError) as ei:
        CHECKER.check_fragments(fplan, "exchanges")
    assert "edge-partitioning" in _violations(ei.value)


def test_corrupt_remote_source_scheme(runner):
    fplan = _fragmented(runner, "select count(*) from lineitem")
    rs = None
    for frag in fplan.fragments.values():
        try:
            rs = _find(frag.root, N.RemoteSourceNode)
            break
        except AssertionError:
            continue
    assert rs is not None
    rs.scheme = "broadcast" if rs.scheme != "broadcast" else "gather"
    with pytest.raises(PlanValidationError) as ei:
        CHECKER.check_fragments(fplan, "exchanges")
    assert "edge-partitioning" in _violations(ei.value)


def test_corrupt_dangling_remote_source(runner):
    fplan = _fragmented(runner, "select count(*) from lineitem")
    rs = None
    for frag in fplan.fragments.values():
        try:
            rs = _find(frag.root, N.RemoteSourceNode)
            break
        except AssertionError:
            continue
    rs.exchange_id = 424242
    with pytest.raises(PlanValidationError) as ei:
        CHECKER.check_fragments(fplan, "exchanges")
    assert "dangling-remote-source" in _violations(ei.value)


# -- fusion barrier legality (pipeline level) --------------------------


class _Fac:
    def __init__(self, operator_id):
        self.operator_id = operator_id


def test_corrupt_chain_across_barrier():
    # pre-fusion: fp(1) -> record-barrier(2) -> fp(3) -> agg(4);
    # corrupted fusion absorbed the barrier AND the far fp into 4
    snapshot = [[(1, True, "filter_project"),
                 (2, False, "fragment_record"),
                 (3, True, "filter_project"),
                 (4, False, "aggregation")]]
    pipelines = [[_Fac(4)]]
    with pytest.raises(PlanValidationError) as ei:
        CHECKER.check_fusion(snapshot, pipelines, {1: 4, 2: 4, 3: 4},
                             pass_name="fusion")
    assert ei.value.pass_name == "fusion"
    assert "fusion-barrier" in _violations(ei.value)


def test_corrupt_fusion_dropped_operator():
    snapshot = [[(1, True, "filter_project"),
                 (2, False, "spool_sink"),
                 (3, False, "aggregation")]]
    pipelines = [[_Fac(3)]]  # the spool sink silently vanished
    with pytest.raises(PlanValidationError) as ei:
        CHECKER.check_fusion(snapshot, pipelines, {1: 3})
    assert "fusion-dropped-operator" in _violations(ei.value)


def test_corrupt_fusion_nonadjacent():
    # fp(1) and fp(3) fused into 4 across the unfused operator 2
    snapshot = [[(1, True, "filter_project"),
                 (2, False, "limit"),
                 (3, True, "filter_project"),
                 (4, False, "aggregation")]]
    pipelines = [[_Fac(2), _Fac(4)]]
    with pytest.raises(PlanValidationError) as ei:
        CHECKER.check_fusion(snapshot, pipelines, {1: 4, 3: 4})
    assert "fusion-nonadjacent" in _violations(ei.value)


# -- determinism classification ---------------------------------------


def test_corrupt_nondeterministic_marked_cacheable(runner,
                                                   monkeypatch):
    """The checker cross-checks the audited classification against
    the fingerprint path: a nondeterministic subtree that still
    produces a cache key is a corruption."""
    plan = _plan(runner, "select name from nation where nationkey > 1")
    f = _find(plan, N.FilterNode)
    f.predicate = Call("greater_than", (
        Call("random", (), BIGINT), Literal(1, BIGINT)), BOOLEAN)
    assert not plan_deterministic(f)
    # uncorrupted: fingerprint refuses, checker is satisfied
    CHECKER.check_plan(plan, "optimizer", catalogs=runner.catalogs)
    # corrupt the fingerprint path into claiming cacheability
    import presto_tpu.cache.fingerprint as fp
    monkeypatch.setattr(fp, "fragment_fingerprint",
                        lambda *a, **k: ("frag:bogus", [], 1))
    with pytest.raises(PlanValidationError) as ei:
        CHECKER.check_plan(plan, "optimizer",
                           catalogs=runner.catalogs)
    assert "cache-determinism" in _violations(ei.value)


def test_expr_determinism_classification():
    det = Call("abs", (Literal(1, BIGINT),), BIGINT)
    nondet = Call("random", (), BIGINT)
    assert expr_deterministic(det)
    assert not expr_deterministic(nondet)
    assert expr_deterministic(None)


# ---------------------------------------------------------------------------
# end-to-end pass attribution: a pass that breaks the plan is named


def test_attribution_optimizer_pass(runner, monkeypatch):
    import presto_tpu.planner.optimizer as opt
    real = opt.optimize

    def breaking_optimize(plan, catalogs=None, session=None):
        plan = real(plan, catalogs)
        f = _find(plan, N.FilterNode)
        f.predicate = Call("greater_than", (
            InputRef("ghost", BIGINT), Literal(0, BIGINT)), BOOLEAN)
        return plan

    monkeypatch.setattr(opt, "optimize", breaking_optimize)
    fresh = LocalRunner("tpch", "tiny",
                        properties={"plan_cache_enabled": False})
    with pytest.raises(PlanValidationError) as ei:
        fresh.execute("select name from nation where nationkey > 3")
    assert ei.value.pass_name == "optimizer"


def test_attribution_respects_session_gate(runner, monkeypatch):
    """plan_validation_enabled = false skips every checkpoint — the
    corrupted plan fails later (or not at all), never as a
    PlanValidationError."""
    import presto_tpu.planner.optimizer as opt
    real = opt.optimize

    def breaking_optimize(plan, catalogs=None, session=None):
        plan = real(plan, catalogs)
        f = _find(plan, N.FilterNode)
        f.predicate = Call("greater_than", (
            InputRef("ghost", BIGINT), Literal(0, BIGINT)), BOOLEAN)
        return plan

    monkeypatch.setattr(opt, "optimize", breaking_optimize)
    fresh = LocalRunner("tpch", "tiny", properties={
        "plan_cache_enabled": False,
        "plan_validation_enabled": False})
    with pytest.raises(Exception) as ei:
        fresh.execute("select name from nation where nationkey > 3")
    assert not isinstance(ei.value, PlanValidationError)


def test_validation_enabled_gate():
    assert validation_enabled(Session("tpch", "tiny", {}))
    assert not validation_enabled(
        Session("tpch", "tiny", {"plan_validation_enabled": False}))


# ---------------------------------------------------------------------------
# zero violations on real plans, at every checked boundary


def test_all_tpch_plans_validate_clean(runner):
    """Every tier-1 TPC-H query: analyzed, optimized, exchanged and
    fragmented plans all pass the checker (plan-only — execution
    covers the local_planner/fusion boundaries below)."""
    from presto_tpu.planner.exchanges import (
        add_exchanges, fragment_plan,
    )
    from presto_tpu.planner.local_planner import prune_unused_columns
    from presto_tpu.planner.optimizer import optimize
    for qnum, sql in sorted(QUERIES.items()):
        plan = runner.create_plan(sql)
        CHECKER.check_plan(plan, f"analysis:q{qnum}")
        plan = optimize(plan, runner.catalogs)
        CHECKER.check_plan(plan, f"optimizer:q{qnum}",
                           catalogs=runner.catalogs)
        prune_unused_columns(plan)
        CHECKER.check_plan(plan, f"prune:q{qnum}")
        plan = add_exchanges(plan, runner.catalogs, runner.session)
        CHECKER.check_plan(plan, f"exchanges:q{qnum}")
        fplan = fragment_plan(plan)
        CHECKER.check_fragments(fplan, f"fragments:q{qnum}")


def test_serving_mix_byte_identity_with_validation():
    """The serving-mix queries (q1/q3/q6/q13) execute with validation
    ON (the default — local_planner + fusion boundaries included) and
    produce byte-identical rows to validation OFF."""
    on = LocalRunner("tpch", "tiny")
    off = LocalRunner("tpch", "tiny", properties={
        "plan_validation_enabled": False})
    for qnum in SERVING_MIX:
        sql = QUERIES[qnum]
        rows_on = on.execute(sql).rows()
        rows_off = off.execute(sql).rows()
        assert rows_on == rows_off, f"q{qnum} diverged"
        assert repr(rows_on) == repr(rows_off), f"q{qnum} bytes"


def test_validation_overhead_is_plan_level_only(runner):
    """The checker never mutates: validating the same plan twice
    yields the same rendering (cheap canary for in-place edits)."""
    plan = _plan(runner, QUERIES[6])
    before = N.plan_text(plan)
    CHECKER.check_plan(plan, "optimizer", catalogs=runner.catalogs)
    CHECKER.check_plan(plan, "optimizer", catalogs=runner.catalogs)
    assert N.plan_text(plan) == before
