"""An IN/EXISTS semi join sinks below the joins under it onto the input
that carries its key (optimizer._sink_semi_join), and a semi join
plans no dynamic filter of its own in its fragment (a filter there
would repeat the probe's membership search lane for lane)."""

import os
import re
import sys

import pytest

from presto_tpu.planner import nodes as N
from presto_tpu.planner.optimizer import optimize
from presto_tpu.telemetry.metrics import METRICS
from presto_tpu.types import BIGINT

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tpch_queries import QUERIES  # noqa: E402

SINKS = "presto_tpu_semi_join_sinks_total"


def _sinks(before):
    return METRICS.snapshot().get(SINKS, 0) - before.get(SINKS, 0)


def _values(symbols):
    return N.ValuesNode([], tuple(N.Field(s, BIGINT) for s in symbols))


#: (join type, semi key, shared left input, where it lands: None =
#: stays above the join, else (input, key there))
CASES = {
    "probe_key": ("inner", "b", False, ("left", "b")),
    "probe_criterion_key": ("inner", "a", False, ("left", "a")),
    "build_key_equated": ("inner", "c", False, ("left", "a")),
    "build_key_unequated": ("inner", "d", False, ("right", "d")),
    "left_join_preserved": ("left", "b", False, ("left", "b")),
    "left_join_nullable": ("left", "d", False, None),
    "right_join_nullable": ("right", "b", False, None),
    "full_join": ("full", "b", False, None),
    "cross_join": ("cross", "b", False, None),
    "shared_input": ("inner", "b", True, None),
}


@pytest.mark.parametrize("negate", (False, True), ids=("in", "not_in"))
@pytest.mark.parametrize("case", list(CASES))
def test_semi_join_sinks_onto_the_input_with_its_key(case, negate):
    """Semi join over Join(L[a, b], R[c, d]) on a = c: the probe's own
    key and a build key the criterion equates land on the probe, a
    build key nothing equates on the build; outer joins' nullable
    sides, FULL, cross and a shared input leave it above."""
    jt, key, shared, want = CASES[case]
    left, right = _values(["a", "b"]), _values(["c", "d"])
    join = N.JoinNode(jt, left, right,
                      [] if jt == "cross" else [("a", "c")],
                      tuple(left.output) + tuple(right.output))
    filt = _values(["f"])
    semi = N.SemiJoinNode(join, filt, key, "f", negate,
                          tuple(join.output))
    root = semi
    if shared:
        agg = N.AggregationNode(left, [], [], "single",
                                tuple(left.output))
        sym_map = {f.symbol: f.symbol for f in join.output}
        root = N.UnionNode([semi, agg], [sym_map, sym_map],
                           tuple(join.output))
    before = METRICS.snapshot()
    out = optimize(root)
    top = out.inputs[0] if shared else out
    if want is None:
        assert top is semi and semi.source is join
        assert (join.left, join.right) == (left, right)
        assert _sinks(before) == 0
        return
    side, sunk_key = want
    assert top is join
    below = getattr(join, side)
    other = right if side == "left" else left
    assert isinstance(below, N.SemiJoinNode)
    assert below.source is (left if side == "left" else right)
    assert below.source_key == sunk_key
    assert below.filtering_source is filt
    assert (below.filtering_key, below.negate) == ("f", negate)
    assert getattr(join, "right" if side == "left" else "left") is other
    assert [f.symbol for f in join.output] == ["a", "b", "c", "d"]
    assert _sinks(before) == 1


def _walk(node, path=()):
    yield node, path
    for s in node.sources():
        yield from _walk(s, path + (node,))


def test_q18_semi_join_sits_on_the_lineitem_scan():
    """Q18 at tiny: the semi join passes both joins (orders, then
    customer) and probes lineitem's scan, keyed on lineitem's
    orderkey; the subquery keeps its own scan."""
    from presto_tpu.runner import LocalRunner
    r = LocalRunner("tpch", "tiny")
    before = METRICS.snapshot()
    plan = optimize(r.create_plan(QUERIES[18]), r.catalogs,
                    session=r.session)
    assert _sinks(before) == 2
    (semi, path), = [(n, p) for n, p in _walk(plan)
                     if isinstance(n, N.SemiJoinNode)]
    scan = semi.source
    assert isinstance(scan, N.TableScanNode)
    assert scan.handle.table == "lineitem"
    assert dict(scan.assignments)[semi.source_key] == "orderkey"
    joins = [n for n in path if isinstance(n, N.JoinNode)]
    assert len(joins) == 2 and path[-1] is joins[-1]
    assert [l for l, _ in joins[-1].criteria] == [semi.source_key]
    assert not any(isinstance(n, N.JoinNode) for n, _ in _walk(semi))


A = "(values (1, 10), (2, 20), (3, 30), (null, 40)) a(k, x)"
B = "(values (1, 'p'), (2, 'q'), (3, 'r'), (5, 's')) b(k2, v)"


@pytest.mark.parametrize("sub", (
    "select z from (values (1), (null)) t(z)",
    "select z from (values (1), (7)) t(z)"), ids=("null", "no_null"))
@pytest.mark.parametrize("join", ("inner", "left"))
def test_not_in_sunk_returns_the_rows_of_the_plan_left_above(join, sub):
    """NOT IN through a join, on the build's key (inner) or the
    preserved side's (LEFT), with and without a NULL in the subquery:
    the sunk plan returns what the same statement returns with a
    LIMIT between join and semi join, which keeps the semi join
    above."""
    from presto_tpu.runner import LocalRunner
    r = LocalRunner("tpch", "tiny")
    key = "b.k2" if join == "inner" else "a.k"
    sunk_sql = (f"select a.k, a.x, b.v from {A} {join} join {B} "
                f"on a.k = b.k2 where {key} not in ({sub})")
    kept_sql = (f"select k, x, v from (select a.k, a.x, b.v, "
                f"{key} as key from {A} {join} join {B} on a.k = b.k2 "
                f"limit 100) j where key not in ({sub})")
    before = METRICS.snapshot()
    sunk = sorted(r.execute(sunk_sql).rows(), key=str)
    assert _sinks(before) == 1
    before = METRICS.snapshot()
    kept = sorted(r.execute(kept_sql).rows(), key=str)
    assert _sinks(before) == 0
    assert sunk == kept == [(2, 20, "q"), (3, 30, "r")]


def _scan_rows(runner, sql, table):
    res = runner.execute("explain analyze " + sql)
    text = "\n".join(r[0] for r in res.rows())
    m = re.search(rf"scan:{table} \[id=\d+\]\s+rows: [\d,]+ -> ([\d,]+)",
                  text)
    assert m, text
    return int(m.group(1).replace(",", ""))


#: the same five customers filter orders three ways: (statement, joins
#: a semi join passes, dynamic filters the plan wires to a scan,
#: orders pruned at the scan: None where that depends on which build
#: finishes first)
SHAPES = {
    "inner_join": (
        "select o.orderkey from orders o join customer c "
        "on o.custkey = c.custkey where c.custkey <= 5", 0, 1, True),
    "semi_join": (
        "select o.orderkey from orders o where o.custkey in "
        "(select custkey from customer where custkey <= 5)", 0, 0, False),
    # the IN sinks onto the orders scan, under the join: the join's
    # filter still reaches the scan through it, and prunes what the
    # scan emits once the join's build has published it (the pair
    # loop may pull the scan as soon as the semi join's build is in)
    "inner_join_over_semi_join": (
        "select o.orderkey from orders o join customer c "
        "on o.custkey = c.custkey where c.custkey <= 5 and o.orderkey "
        "in (select orderkey from lineitem where quantity > 10)",
        1, 1, None),
}


@pytest.mark.parametrize("shape", list(SHAPES))
def test_only_inner_joins_plan_a_local_dynamic_filter(shape):
    """An inner join's build wires a dynamic filter to the orders scan,
    through a semi join sunk beneath it too, and prunes it; a semi
    join wires none, and its probe reads every order. Each answers
    what dynamic_filtering=false answers."""
    from presto_tpu.planner.local_planner import LocalExecutionPlanner
    from presto_tpu.runner import LocalRunner
    sql, sinks, n_wired, pruned = SHAPES[shape]
    on = LocalRunner("tpch", "tiny",
                     {"fragment_result_cache_enabled": False})
    off = LocalRunner("tpch", "tiny", {"dynamic_filtering": False})
    got = sorted(on.execute(sql).rows())
    assert got == sorted(off.execute(sql).rows()) and got
    planner = LocalExecutionPlanner(on.catalogs, on.session)
    before = METRICS.snapshot()
    planner.plan(optimize(on.create_plan(sql)))
    assert _sinks(before) == sinks
    wired = [spec for specs in planner._df_scans.values()
             for spec in specs]
    assert len(wired) == n_wired
    if pruned is None:
        return
    total = on.execute("select count(*) from orders").rows()[0][0]
    emitted = _scan_rows(on, sql, "orders")
    assert emitted < total / 10 if pruned else emitted == total
