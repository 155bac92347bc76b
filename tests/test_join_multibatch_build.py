"""A join build side of many input batches (HashBuildOperator.finish:
Batch.concat of every batch it was given, the layout chosen from what
the merged rows show, one table for the probes).

TPC-H Q3 at sf10 hands the orders build 16 batches of 1,048,576 lanes
that merge into one of 16,777,216; here the batches are 4,096 lanes
and the merged batch lands two ladder rungs above one of them (65,536
lanes), by 16 inputs (their capacities sum to the rung: the concat
packs in place) and by 5 (they do not: it packs, then pads). Every
case is probed and compared row for row with a plain oracle (a
dictionary; nothing of ops/join.py), and the counters that describe
the build (rows, lanes, batches, table slots, the finish's wall) must
grow by what the case built.
"""

import os
import sys

import numpy as np
import pytest

from presto_tpu.batch import Batch, empty_batch, kernel_capacity
from presto_tpu.operators.base import DriverContext
from presto_tpu.operators.join_ops import (
    HashBuildOperatorFactory, JoinBridge, LookupJoinOperatorFactory,
)
from presto_tpu.ops import join
from presto_tpu.telemetry.metrics import METRICS
from presto_tpu.types import BIGINT

#: lanes of one input batch, and live rows in each: 16 x 3,400 and
#: 5 x 3,400 both pass 16,384, so the merged batch is 65,536 lanes
LANES = 4096
ROWS = 3400
MERGED = 65536
KINDS = ("ordered", "shuffled", "duplicate_last", "spread", "null_keys",
         "empty_batch")
PREFIXES = ("presto_tpu_join_build", "presto_tpu_join_direct")


def _counters():
    return {k: v for k, v in METRICS.snapshot().items()
            if k.startswith(PREFIXES)}


def _grew(before):
    return {k: v - before.get(k, 0) for k, v in _counters().items()
            if v != before.get(k, 0)}


def _by_layout(name, layout):
    return f'presto_tpu_join_build_{name}_total{{layout="{layout}"}}'


def _build_rows(n_batches, kind):
    """[[(key, bv)] per input batch]; an empty list is a batch with no
    live row. Keys are multiples of three, unique unless the kind
    says otherwise."""
    rng = np.random.default_rng(11 * n_batches + KINDS.index(kind))
    n = n_batches * ROWS
    keys = (np.arange(n) * 3).tolist()
    if kind == "shuffled":
        keys = (rng.permutation(n) * 3).tolist()
    elif kind == "spread":
        # 8 x 65,536 slots cannot hold the last key
        keys[-1] = 8 * MERGED * 3
    elif kind == "null_keys":
        keys = [None if i % 97 == 5 else k for i, k in enumerate(keys)]
    rows = [(k, 7 * i) for i, k in enumerate(keys)]
    batches = [rows[i * ROWS:(i + 1) * ROWS] for i in range(n_batches)]
    if kind == "duplicate_last":
        # the one repeated key arrives in the last batch only
        batches[-1][-1] = (batches[0][10][0], batches[-1][-1][1])
    elif kind == "empty_batch":
        batches[n_batches // 2] = []
    return batches


def _input(rows):
    if not rows:
        return empty_batch([("k", BIGINT, None), ("bv", BIGINT, None)],
                           LANES)
    return Batch.from_pydict({"k": ([k for k, _ in rows], BIGINT),
                              "bv": ([v for _, v in rows], BIGINT)},
                             capacity=LANES)


def _build(batches, join_type):
    bridge = JoinBridge()
    op = HashBuildOperatorFactory(
        1, bridge, ("k",), None,
        schema_cols=[("k", BIGINT, None), ("bv", BIGINT, None)],
        consumer_layouts=LookupJoinOperatorFactory.readable_layouts(
            join_type)).create(DriverContext())
    for rows in batches:
        op.add_input(_input(rows))
    op.finish()
    return bridge.table


def _probe_rows(build, rng):
    """Probe keys: hits from every input batch, misses between and
    beyond the build keys, NULLs."""
    live = [k for rows in build for k, _ in rows if k is not None]
    hits = rng.choice(live, 1500).tolist()
    misses = (rng.integers(0, len(live), 500) * 3 + 1).tolist()
    keys = hits + misses + [None, -3, 3 * 10**9, live[0], live[-1]]
    keys = [keys[i] for i in rng.permutation(len(keys))]
    return [(k, i) for i, k in enumerate(keys)]


def _oracle(build, probe, join_type):
    """[(key, pv, bv)]: every build row of the key for every probe
    row, a NULL build side for an unmatched row of a left join."""
    by_key = {}
    for rows in build:
        for k, bv in rows:
            if k is not None:
                by_key.setdefault(k, []).append(bv)
    out = []
    for k, pv in probe:
        if k is not None and k in by_key:
            out += [(k, pv, bv) for bv in by_key[k]]
        elif join_type == "left":
            out.append((k, pv, None))
    return out


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("join_type", ("inner", "left"))
@pytest.mark.parametrize("n_batches", (16, 5))
def test_many_batch_build_answers_as_the_oracle(n_batches, join_type, kind):
    build = _build_rows(n_batches, kind)
    live_rows = sum(len(rows) for rows in build)
    before = _counters()
    table = _build(build, join_type)
    grew = _grew(before)

    layout = "sorted" if kind in ("duplicate_last", "spread") else "direct"
    assert table.layout == layout
    lanes = kernel_capacity(live_rows)
    assert table.batch.capacity == lanes
    if kind != "empty_batch" or n_batches == 16:
        assert lanes == MERGED          # two rungs above one input
    want = {
        f'presto_tpu_join_builds_total{{layout="{layout}"}}': 1,
        _by_layout("rows", layout): live_rows,
        _by_layout("lanes", layout): lanes,
        _by_layout("batches", layout): n_batches,
    }
    if layout == "direct":
        keys = [k for rows in build for k, _ in rows if k is not None]
        slots = join.direct_table_len(min(keys), max(keys), lanes)
        assert table.slot_of.shape == (slots,)
        want["presto_tpu_join_direct_table_slots_total"] = slots
    else:
        reason = "duplicate" if kind == "duplicate_last" else "spread"
        want['presto_tpu_join_direct_fallback_total'
             f'{{reason="{reason}"}}'] = 1
    finish_ns = grew.pop("presto_tpu_join_build_finish_ns_total")
    assert finish_ns > 0
    assert grew == want

    probe = _probe_rows(build, np.random.default_rng(3))
    pb = Batch.from_pydict({"k": ([k for k, _ in probe], BIGINT),
                            "pv": ([v for _, v in probe], BIGINT)})
    out, overflow, n_live = join.probe_join(
        table, pb, ("k",),
        (2 if kind == "duplicate_last" else 1) * pb.capacity, join_type,
        ("k", "pv"), ("bv",), ("k",))
    assert not bool(overflow)
    got = out.to_pylist()
    assert int(n_live) == len(got)
    expected = _oracle(build, probe, join_type)
    if kind == "duplicate_last":
        # a probe row's two build rows come in the table's order
        got, expected = sorted(got, key=repr), sorted(expected, key=repr)
    assert got == expected


def test_q3_served_with_a_build_side_of_many_batches():
    """Q3 over POST /v1/statement on tpch.sf0_1 with 32,768-row
    batches: the 150,000 orders reach their build in 5 batches. Equal
    to the Acero reference within 1e-9, in the statement's order."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".."))
    import baseline_proxy
    import chip_smoke
    from tpch_queries import QUERIES
    from presto_tpu.server.coordinator import Coordinator, StatementClient
    coord = Coordinator([], "tpch", "sf0_1", single_node=True,
                        properties={"fragment_result_cache_enabled": False,
                                    "batch_rows": 32768})
    coord.start()
    try:
        before = _counters()
        columns, data = StatementClient(
            coord.url, user="multibatch-test").execute(
                QUERIES[3], timeout=600.0)
        grew = _grew(before)
        gen = coord._runner().catalogs.connector("tpch")._gens["sf0_1"]
    finally:
        coord.stop()
    got = chip_smoke._engine_rows(columns, data)
    tables = baseline_proxy.load_tables(gen, baseline_proxy.TABLES)
    want = chip_smoke.reference_rows(gen, tables)[3]
    assert len(got) == len(want) == 10
    for g, w in zip(got, want):         # in order: revenue desc, date
        assert len(g) == len(w)
        for gv, wv in zip(g, w):
            if isinstance(wv, float):
                assert abs(gv - wv) <= 1e-9 * abs(wv), (g, w)
            else:
                assert gv == wv, (g, w)
    builds = sum(v for k, v in grew.items()
                 if k.startswith("presto_tpu_join_builds_total"))
    batches = sum(v for k, v in grew.items()
                  if k.startswith("presto_tpu_join_build_batches_total"))
    assert builds == 2
    # one build took at least 4 of them (the other at least 1)
    assert batches >= 4 + 1, grew
    assert grew["presto_tpu_join_build_finish_ns_total"] > 0
