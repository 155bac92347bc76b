"""TPC-H Q5 on the served path, small, on the CPU (the cell
`sf10_q5_serial` sends the same statement at SF10 on the chip).

What the statement makes the engine do that no other cell does, each
held here against an oracle that shares nothing with it: five lookup
joins in one probe pipeline, one of them on two keys, whose build
keeps the sorted-hash layout (a key of its probe side, the customer's
nation, comes out of an earlier join's build side). The counters the
benchmark reads for the cell must grow by what the statement did.
"""

import datetime
import os
import sys

import numpy as np
import pyarrow.compute as pc
import pytest

import jax.numpy as jnp

from presto_tpu.batch import Batch
from presto_tpu.operators.base import DriverContext
from presto_tpu.operators.join_ops import (
    HashBuildOperatorFactory, JoinBridge, LookupJoinOperatorFactory,
)
from presto_tpu.server.coordinator import Coordinator, StatementClient
from presto_tpu.telemetry.metrics import METRICS
from presto_tpu.types import BIGINT

_NO_REPLAY = {"fragment_result_cache_enabled": False}
_EPOCH = datetime.date(1970, 1, 1)
#: small enough that every scan and the supplier build (1,000 rows at
#: sf0_1) arrive in several batches
BATCH_ROWS = 512
#: the two keys' widths: BIGINT suppkey and nationkey
KEY_BYTES = 16


def _grew(before):
    return {k: v - before.get(k, 0)
            for k, v in METRICS.snapshot().items()
            if v != before.get(k, 0)}


def _days(iso):
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


def test_q5_served_equals_the_acero_reference():
    """Q5 over POST /v1/statement on tpch.sf0_1 in batches of 512 rows:
    the Acero reference's rows, in the statement's order, doubles
    within 1e-9; one build of five (supplier, on suppkey and the
    customer's nationkey) stays sorted because it has two keys, and it
    is probed by every line of 1994's orders joined to its customer."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".."))
    import baseline_proxy
    from tpch_queries import QUERIES
    coord = Coordinator([], "tpch", "sf0_1", single_node=True,
                        properties={**_NO_REPLAY,
                                    "batch_rows": BATCH_ROWS})
    coord.start()
    try:
        before = METRICS.snapshot()
        columns, data = StatementClient(
            coord.url, user="q5-test").execute(QUERIES[5], timeout=600.0)
        grew = _grew(before)
        gen = coord._runner().catalogs.connector("tpch")._gens["sf0_1"]
    finally:
        coord.stop()
    tables = baseline_proxy.load_tables(
        gen, ["lineitem", "orders", "customer", "supplier", "nation",
              "region"])
    names = next(c.dictionary for c in gen.schema("nation").columns
                 if c.name == "name")
    want = [(names[r["n_name"]], r["rev_sum"])
            for r in baseline_proxy.q5(tables, gen).to_pylist()]
    assert [c["name"] for c in columns] == ["name", "revenue"]
    assert 0 < len(want) == len(data)
    for (gn, gv), (wn, wv) in zip(data, want):    # revenue desc
        assert gn == wn
        assert abs(gv - wv) <= 1e-9 * abs(wv), (gn, gv, wv)

    # the plan: orders, customer, nation and region direct; supplier
    # sorted for its two keys
    assert grew['presto_tpu_join_builds_total{layout="direct"}'] == 4
    assert grew['presto_tpu_join_builds_total{layout="sorted"}'] == 1
    assert grew['presto_tpu_join_direct_fallback_total'
                '{reason="multi_key"}'] == 1
    # the supplier join's probe rows are the orders-customer output:
    # every line of an order of 1994 (each order has its customer)
    orders = tables["orders"]
    year = orders.filter(pc.and_(
        pc.greater_equal(orders["orderdate"], _days("1994-01-01")),
        pc.less(orders["orderdate"], _days("1995-01-01"))))
    lines = tables["lineitem"].join(
        year.select(["orderkey", "custkey"]), "orderkey",
        join_type="inner").join(
        tables["customer"].select(["custkey", "nationkey"]), "custkey",
        join_type="inner")
    kept = lines.join(
        tables["supplier"].select(["suppkey", "nationkey"]),
        keys=["suppkey", "nationkey"], join_type="inner")
    assert grew['presto_tpu_join_probe_rows_total{layout="sorted"}'] \
        == lines.num_rows
    assert grew['presto_tpu_join_sorted_bytes_total'] == (
        tables["supplier"].num_rows * KEY_BYTES
        + lines.num_rows * KEY_BYTES + kept.num_rows * 4)
    # every other lookup join probed a direct table: customer the
    # orders join's output, nation and region the supplier join's kept
    # rows, orders the lines of lineitem that the orders build's
    # dynamic filter let through (its own matches at least)
    direct = grew['presto_tpu_join_probe_rows_total{layout="direct"}'] \
        - lines.num_rows - 2 * kept.num_rows
    assert lines.num_rows <= direct <= tables["lineitem"].num_rows


# -- the two-key sorted lookup probe against a numpy oracle -----------

#: (k1, k2, payload) build rows: (1, 10) twice and (3, 30) three times
#: are duplicate composite keys; (2, 20) and (2, 21) share k1, (4, 20)
#: shares k2 with (2, 20); NULL in either key matches nothing
BUILD = [(1, 10, 100), (1, 10, 101), (2, 20, 200), (2, 21, 210),
         (3, 30, 300), (3, 30, 301), (3, 30, 302), (4, 20, 400),
         (None, 10, 900), (5, None, 901), (6, 60, 600)]
#: probe keys: full matches, one column equal only, NULLs either side
PROBE_KEYS = [(1, 10), (2, 20), (2, 21), (2, 22), (4, 21), (3, 30),
              (None, 10), (5, None), (6, 61), (7, 60), (6, 60),
              (None, None)]


def _build(rows, lanes):
    bridge = JoinBridge()
    op = HashBuildOperatorFactory(
        1, bridge, ("bk1", "bk2"), None,
        schema_cols=[("bk1", BIGINT, None), ("bk2", BIGINT, None),
                     ("bv", BIGINT, None)],
        consumer_layouts=LookupJoinOperatorFactory.readable_layouts(
            "inner")).create(DriverContext())
    for lo in range(0, len(rows), lanes):
        part = rows[lo:lo + lanes]
        op.add_input(Batch.from_pydict(
            {"bk1": ([r[0] for r in part], BIGINT),
             "bk2": ([r[1] for r in part], BIGINT),
             "bv": ([r[2] for r in part], BIGINT)}, capacity=lanes))
    op.finish()
    return bridge


def _oracle(build, probe):
    """(pk1, pk2, pv, bv) of every matching pair, in probe order."""
    out = []
    for pk1, pk2, pv in probe:
        for bk1, bk2, bv in build:
            if None not in (pk1, pk2, bk1, bk2) \
                    and (pk1, pk2) == (bk1, bk2):
                out.append((pk1, pk2, pv, bv))
    return out


@pytest.mark.parametrize("build", ("unique", "duplicates"))
def test_two_key_sorted_probe_equals_the_oracle(build):
    """A lookup join on two BIGINT keys against a sorted build, probed
    by batches on two rungs of the ladder (4,096 and 16,384 lanes: the
    262,144- and 1,048,576-lane batches of the cell, small): row for
    row against a nested loop. A unique build takes the aligned probe
    (at 16,384 lanes materialized late), a build with duplicate
    composite keys the general expansion."""
    rng = np.random.default_rng(5)
    rows = BUILD if build == "duplicates" else list(
        {(r[0], r[1]): r for r in BUILD}.values())
    bridge = _build(rows, 4)
    table = bridge.table
    assert table.layout == "sorted"
    assert table.unique_runs == (build == "unique")
    op = LookupJoinOperatorFactory(
        2, bridge, ("pk1", "pk2"), "inner", ("pk1", "pk2", "pv"),
        ("bv",), build_keys=("bk1", "bk2"), expansion_factor=4,
    ).create(DriverContext())
    out, probe_rows = [], []
    for lanes in (4096, 16384, 4096):
        keys = [PROBE_KEYS[i]
                for i in rng.integers(0, len(PROBE_KEYS), lanes // 2)]
        base = len(probe_rows)
        part = [(k1, k2, base + i) for i, (k1, k2) in enumerate(keys)]
        probe_rows += part
        batch = Batch.from_pydict(
            {"pk1": ([r[0] for r in part], BIGINT),
             "pk2": ([r[1] for r in part], BIGINT),
             "pv": ([r[2] for r in part], BIGINT)}, capacity=lanes)
        assert op.needs_input()
        op.add_input(batch)
        while (b := op.get_output()) is not None:
            out.append(b)
    op.finish()
    while not op.is_finished():
        while (b := op.get_output()) is not None:
            out.append(b)
    got = sorted(r for b in out for r in b.to_pylist())
    want = sorted(_oracle(rows, probe_rows))
    assert len(want) > len(probe_rows) // 4
    assert got == want
    assert op._layout == "sorted"
