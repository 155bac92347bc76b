"""TPC-H Q18 on the served path, small, on the CPU (the cell
`sf1_q18_serial` sends the same statement at SF1 on the chip).

What the statement makes the engine do, each held here against an
oracle that shares nothing with it: a streaming aggregation over a
scan its connector declares sorted (the boundary group carried from
batch to batch), a semi join whose build keeps the sorted layout, and
two joins in which every probe row finds its build row (the aligned
probe's back at K = capacity). The counters the benchmark reads for
the cell must grow by what the statement did.
"""

import datetime
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from presto_tpu.batch import Batch, empty_batch, kernel_capacity
from presto_tpu.connectors.spi import TableHandle
from presto_tpu.operators.base import DriverContext
from presto_tpu.operators.join_ops import (
    HashBuildOperatorFactory, JoinBridge, LookupJoinOperatorFactory,
)
from presto_tpu.schema import ColumnSchema, RelationSchema
from presto_tpu.server.coordinator import Coordinator, StatementClient
from presto_tpu.telemetry.metrics import METRICS
from presto_tpu.types import BIGINT, DOUBLE

_NO_REPLAY = {"fragment_result_cache_enabled": False}
_EPOCH = datetime.date(1970, 1, 1)
SERIES = ("presto_tpu_agg_stream_rows_total",
          "presto_tpu_agg_stream_groups_total",
          "presto_tpu_semi_join_probe_rows_total",
          "presto_tpu_semi_join_matched_rows_total")


def _grew(before):
    return {k: v - before.get(k, 0)
            for k, v in METRICS.snapshot().items()
            if v != before.get(k, 0)}


def test_q18_served_equals_the_acero_reference():
    """Q18 over POST /v1/statement on tpch.sf0_1 with 65,536-row
    batches (10 lineitem batches, 3 builds): the Acero reference's
    rows, in the statement's order, doubles within 1e-9."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".."))
    import baseline_proxy
    from tpch_queries import QUERIES
    coord = Coordinator([], "tpch", "sf0_1", single_node=True,
                        properties={**_NO_REPLAY, "batch_rows": 65536})
    coord.start()
    try:
        before = METRICS.snapshot()
        columns, data = StatementClient(
            coord.url, user="q18-test").execute(QUERIES[18],
                                                timeout=600.0)
        grew = _grew(before)
        gen = coord._runner().catalogs.connector("tpch")._gens["sf0_1"]
    finally:
        coord.stop()
    tables = baseline_proxy.load_tables(
        gen, ["lineitem", "orders", "customer"])
    names = next(c.dictionary for c in gen.schema("customer").columns
                 if c.name == "name")
    want = [(names[r["name"]], r["custkey"], r["orderkey"],
             r["orderdate"], r["totalprice"], r["quantity_sum"])
            for r in baseline_proxy.q18(tables, gen).to_pylist()]
    assert [c["name"] for c in columns] == [
        "name", "custkey", "orderkey", "orderdate", "totalprice",
        "total_qty"]
    assert 0 < len(want) == len(data)
    for g, w in zip(data, want):       # in order: totalprice desc, date
        for gv, wv in zip(g, w):
            if isinstance(gv, str) and isinstance(wv, int):
                gv = (datetime.date.fromisoformat(gv) - _EPOCH).days
            if isinstance(wv, float):
                assert abs(gv - wv) <= 1e-9 * abs(wv), (g, w)
            else:
                assert gv == wv, (g, w)

    # the plan: orders and customer are direct builds, the semi join's
    # build stays sorted because of its join type
    assert grew['presto_tpu_join_builds_total{layout="direct"}'] == 2
    assert grew['presto_tpu_join_builds_total{layout="sorted"}'] == 1
    assert grew['presto_tpu_join_direct_fallback_total'
                '{reason="join_type"}'] == 1
    assert grew['presto_tpu_kernel_calls_total{kernel="agg_stream"}'] >= 10
    # every lineitem row streams into the subquery's aggregation and,
    # joined to its order and customer, reaches the semi join; one
    # group an order that has lines; the rows kept are the answer's
    li = tables["lineitem"]
    per_order = li.group_by(["orderkey"]).aggregate(
        [("quantity", "sum"), ("quantity", "count")])
    big = per_order.filter(
        np.asarray(per_order["quantity_sum"]) > 300.0)
    assert [grew[s] for s in SERIES] == [
        li.num_rows, per_order.num_rows, li.num_rows,
        sum(big["quantity_count"].to_pylist())]
    assert len(data) == big.num_rows


# -- the carried boundary group, on the memory connector --------------

LANES = 4096
#: (orderkey, lines, quantity of each): 7 pays 350 in one batch, 8
#: exactly 300 (not over), 9 and 11 straddle what the case makes them
#: straddle (350: over only when the carry holds; 300: never over)
ORDERS = [(1, 3, 10.0), (2, 6, 49.0), (7, 7, 50.0), (8, 6, 50.0),
          (9, 7, 50.0), (10, 2, 50.0), (11, 6, 50.0), (12, 7, 50.0),
          (13, 1, 5.0)]
KINDS = ("one_batch", "straddle", "last_empty", "first_empty",
         "spans_three", "dead_lanes")


def _line_batches(kind):
    """lineitem as [[(orderkey, quantity)] a stored batch], sorted by
    orderkey throughout; None marks a dead lane."""
    rows = [(k, q) for k, n, q in ORDERS for _ in range(n)]
    at9 = rows.index((9, 50.0))
    at11 = rows.index((11, 50.0))
    if kind == "one_batch":
        return [rows]
    if kind == "spans_three":
        # order 9: 2 lines end a batch, 3 are a whole batch (its first
        # group is its last), 2 start the next
        return [rows[:at9 + 2], rows[at9 + 2:at9 + 5], rows[at9 + 5:]]
    cut = [rows[:at9 + 4], rows[at9 + 4:at11 + 3], rows[at11 + 3:]]
    if kind == "last_empty":
        return cut + [[]]
    if kind == "first_empty":
        return [[]] + cut
    if kind == "dead_lanes":
        # a filtered-out lane on each side of every edge, holding a
        # key and a quantity that would change the answer if read
        return [b[:-1] + [None, b[-1], None] for b in cut[:1]] + \
            [[None, b[0], None] + b[1:] for b in cut[1:]]
    return cut


def _stored(rows):
    if not rows:
        return empty_batch([("orderkey", BIGINT, None),
                            ("quantity", DOUBLE, None)], LANES)
    keep = np.zeros(LANES, bool)
    keep[:len(rows)] = [r is not None for r in rows]
    rows = [r if r is not None else (9, 1000.0) for r in rows]
    b = Batch.from_pydict(
        {"orderkey": ([k for k, _ in rows], BIGINT),
         "quantity": ([q for _, q in rows], DOUBLE)}, capacity=LANES)
    return b.filter(jnp.asarray(keep))


def _create(conn, table, columns, batches):
    handle = TableHandle("memory", "default", table)
    conn.page_sink.create_table(handle, RelationSchema(
        [ColumnSchema(n, t) for n, t in columns]))
    for b in batches:
        conn.page_sink.append(handle, b)
    conn.page_sink.finish(handle)


@pytest.mark.parametrize("kind", KINDS)
def test_boundary_group_is_carried_across_batches(kind, monkeypatch):
    """The Q18 shape over a lineitem whose stored batches end inside
    an order: its lines pass QUANTITY = 300 only added up across the
    edge. The connector declares the table sorted (the test's doing:
    the memory connector declares nothing), so the subquery streams."""
    coord = Coordinator([], "memory", "default", single_node=True,
                        properties=dict(_NO_REPLAY))
    coord.start()
    try:
        conn = coord._runner().catalogs.connector("memory")
        monkeypatch.setattr(
            conn.metadata, "sorted_by",
            lambda h: ["orderkey"] if h.table == "lineitem" else None)
        _create(conn, "lineitem",
                [("orderkey", BIGINT), ("quantity", DOUBLE)],
                [_stored(rows) for rows in _line_batches(kind)])
        _create(conn, "orders",
                [("orderkey", BIGINT), ("totalprice", DOUBLE)],
                [Batch.from_pydict({
                    "orderkey": ([k for k, _, _ in ORDERS], BIGINT),
                    "totalprice": ([100.5 * k for k, _, _ in ORDERS],
                                   DOUBLE)})])
        before = METRICS.snapshot()
        _, data = StatementClient(coord.url, user="q18-carry").execute(
            "select o.orderkey, o.totalprice, sum(l.quantity) "
            "from orders o, lineitem l where o.orderkey in ("
            "  select orderkey from lineitem group by orderkey "
            "  having sum(quantity) > 300) "
            "and o.orderkey = l.orderkey "
            "group by o.orderkey, o.totalprice order by o.orderkey",
            timeout=600.0)
        grew = _grew(before)
    finally:
        coord.stop()
    assert data == [[k, 100.5 * k, n * q] for k, n, q in ORDERS
                    if n * q > 300]
    assert [k for k, _, _ in data] == [7, 9, 12]
    lines = sum(n for _, n, _ in ORDERS)
    assert grew['presto_tpu_kernel_calls_total{kernel="agg_stream"}'] \
        == len(_line_batches(kind))
    assert [grew[s] for s in SERIES] == [lines, len(ORDERS), lines, 21]


# -- a join in which every probe row finds its build row --------------

BUILD_BATCHES = 16
PROBE_LANES = 16384


def _full_match_build():
    """16 input batches of 4,096 live rows: 65,536 rows on the 65,536
    lane rung, every lane live (Q18's orders build at sf1: 1,500,000
    rows on the 4,194,304-lane rung, 36% live; at sf10 15,000,000 on
    the 16,777,216-lane one, 89%)."""
    rng = np.random.default_rng(18)
    keys = rng.permutation(BUILD_BATCHES * LANES) * 3
    rows = [(int(k), 7 * i, 0.5 * i) for i, k in enumerate(keys)]
    bridge = JoinBridge()
    op = HashBuildOperatorFactory(
        1, bridge, ("bk",), None,
        schema_cols=[("bk", BIGINT, None), ("bv", BIGINT, None),
                     ("bd", DOUBLE, None)],
        consumer_layouts=LookupJoinOperatorFactory.readable_layouts(
            "inner")).create(DriverContext())
    for i in range(BUILD_BATCHES):
        part = rows[i * LANES:(i + 1) * LANES]
        op.add_input(Batch.from_pydict(
            {"bk": ([r[0] for r in part], BIGINT),
             "bv": ([r[1] for r in part], BIGINT),
             "bd": ([r[2] for r in part], DOUBLE)}, capacity=LANES))
    op.finish()
    return bridge, rows


@pytest.mark.parametrize("probe", ("every_lane", "dead_lanes"))
def test_full_match_probe_equals_the_oracle(probe):
    """Every live probe row finds a build row, so the aligned probe's
    back runs at the batch's own width (K = capacity: nothing packs)
    and gathers the build's columns there; row for row, in probe
    order, against a dictionary."""
    bridge, build_rows = _full_match_build()
    table = bridge.table
    assert table.layout == "direct"
    assert table.batch.capacity == kernel_capacity(len(build_rows)) \
        == BUILD_BATCHES * LANES
    assert bool(np.asarray(table.batch.row_valid).all())
    by_key = {k: (bv, bd) for k, bv, bd in build_rows}
    rng = np.random.default_rng(36)
    op = LookupJoinOperatorFactory(
        2, bridge, ("pk",), "inner", ("pk", "pv"), ("bv", "bd"),
        build_keys=("bk",),
        probe_schema=[("pk", BIGINT, None), ("pv", BIGINT, None)]
    ).create(DriverContext())
    snap = METRICS.snapshot()
    out, want = [], []
    for base in (0, 10 ** 6, 2 * 10 ** 6):
        keys = (rng.integers(0, len(build_rows), PROBE_LANES) * 3)
        batch = Batch.from_pydict(
            {"pk": (keys.tolist(), BIGINT),
             "pv": (list(range(base, base + PROBE_LANES)), BIGINT)},
            capacity=PROBE_LANES)
        keep = np.ones(PROBE_LANES, bool)
        if probe == "dead_lanes":
            keep[rng.integers(0, PROBE_LANES, 40)] = False
            batch = batch.filter(jnp.asarray(keep))
        want.append([(int(k), base + i) + by_key[int(k)]
                     for i, k in enumerate(keys) if keep[i]])
        assert op.needs_input()
        op.add_input(batch)
        while (b := op.get_output()) is not None:
            out.append(b)
    op.finish()
    while not op.is_finished():
        while (b := op.get_output()) is not None:
            out.append(b)
    assert [b.capacity for b in out] == [PROBE_LANES] * 3
    assert [b.to_pylist() for b in out] == want
    lanes = {s: v for s, v in _grew(snap).items()
             if s.startswith("presto_tpu_join_probe_lanes_total")}
    assert lanes == {
        'presto_tpu_join_probe_lanes_total{stage="searched"}':
            3 * PROBE_LANES,
        'presto_tpu_join_probe_lanes_total{stage="materialized"}':
            3 * PROBE_LANES}
