"""A join build side of many input batches (HashBuildOperator.finish:
every batch it was given merged into one, the layout chosen from what
the merged rows show, one table for the probes).

TPC-H Q3 at sf10 hands the orders build 16 batches of 1,048,576 lanes
that merge into one of 16,777,216; here the batches are 4,096 lanes
and the merged batch lands two ladder rungs above one of them (65,536
lanes), by 16 inputs (their capacities sum to the rung: the lanes stay
where they arrived, Batch.concat_lanes, and nothing is packed) and by
5 (short of it: dead lanes follow, nothing is packed). Only inputs
whose lanes pass the rung (16 batches of 200 rows each, on the
4,096-lane rung; 5 of which one is empty, on the 16,384-lane one) are
packed, Batch.concat, because there the pack lets the batch shrink.
Every case is probed and compared row for row with a plain oracle (a
dictionary; nothing of ops/join.py), and the counters that describe
the build (rows, lanes, packed lanes, batches, table slots, the
finish's wall) must grow by what the case built.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

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
#: live rows in each batch of a SPARSE build: 16 x 200 land on the
#: 4,096-lane rung, a sixteenth of the inputs' lanes
SPARSE = 200
#: dead lanes interleaved in each batch of the kind "filtered"
DEAD = 600
KINDS = ("ordered", "shuffled", "duplicate_last", "spread", "null_keys",
         "empty_batch", "filtered", "filtered_duplicate")
#: kinds whose build cannot take the direct layout
SORTED = ("duplicate_last", "spread", "filtered_duplicate")
#: (input batches, live rows in each)
SHAPES = ((16, ROWS), (5, ROWS), (16, SPARSE))
PREFIXES = ("presto_tpu_join_build", "presto_tpu_join_direct")


def _counters():
    return {k: v for k, v in METRICS.snapshot().items()
            if k.startswith(PREFIXES)}


def _grew(before):
    return {k: v - before.get(k, 0) for k, v in _counters().items()
            if v != before.get(k, 0)}


def _by_layout(name, layout):
    return f'presto_tpu_join_build_{name}_total{{layout="{layout}"}}'


def _build_rows(n_batches, kind, per_batch=ROWS):
    """[[(key, bv)] per input batch]; an empty list is a batch with no
    live row. Keys are multiples of three, unique unless the kind
    says otherwise."""
    rng = np.random.default_rng(11 * n_batches + KINDS.index(kind))
    n = n_batches * per_batch
    keys = (np.arange(n) * 3).tolist()
    if kind == "shuffled":
        keys = (rng.permutation(n) * 3).tolist()
    elif kind == "spread":
        # 8 x 65,536 slots cannot hold the last key
        keys[-1] = 8 * MERGED * 3
    elif kind == "null_keys":
        keys = [None if i % 97 == 5 else k for i, k in enumerate(keys)]
    rows = [(k, 7 * i) for i, k in enumerate(keys)]
    batches = [rows[i * per_batch:(i + 1) * per_batch]
               for i in range(n_batches)]
    if kind in ("duplicate_last", "filtered_duplicate"):
        # the one repeated key arrives in the last batch only
        batches[-1][-1] = (batches[0][10][0], batches[-1][-1][1])
    elif kind == "empty_batch":
        batches[n_batches // 2] = []
    return batches


def _input(rows, kind="ordered"):
    if not rows:
        return empty_batch([("k", BIGINT, None), ("bv", BIGINT, None)],
                           LANES)
    keep = np.ones(LANES, bool)
    if kind.startswith("filtered"):
        # a filter upstream left dead lanes BETWEEN the live ones, and
        # each still holds a key some live row has: a reader that took
        # a dead lane for a row would see a duplicate or a wrong value
        at = np.linspace(1, len(rows) - 1, DEAD).astype(int)
        at += np.arange(DEAD)
        rows = list(rows)
        for i in at:
            rows.insert(i, (rows[0][0], -1))
        keep[at] = False
    b = Batch.from_pydict({"k": ([k for k, _ in rows], BIGINT),
                           "bv": ([v for _, v in rows], BIGINT)},
                          capacity=LANES)
    return b.filter(jnp.asarray(keep))


def _build(batches, join_type, kind="ordered"):
    bridge = JoinBridge()
    op = HashBuildOperatorFactory(
        1, bridge, ("k",), None,
        schema_cols=[("k", BIGINT, None), ("bv", BIGINT, None)],
        consumer_layouts=LookupJoinOperatorFactory.readable_layouts(
            join_type)).create(DriverContext())
    for rows in batches:
        op.add_input(_input(rows, kind))
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
@pytest.mark.parametrize("n_batches, per_batch", SHAPES,
                         ids=("16", "5", "16_sparse"))
def test_many_batch_build_answers_as_the_oracle(n_batches, per_batch,
                                                join_type, kind):
    build = _build_rows(n_batches, kind, per_batch)
    live_rows = sum(len(rows) for rows in build)
    before = _counters()
    table = _build(build, join_type, kind)
    grew = _grew(before)

    layout = "sorted" if kind in SORTED else "direct"
    assert table.layout == layout
    lanes = kernel_capacity(live_rows)
    assert table.batch.capacity == lanes
    if per_batch == SPARSE:
        assert lanes == LANES           # a sixteenth of the inputs'
    elif kind != "empty_batch" or n_batches == 16:
        assert lanes == MERGED          # two rungs above one input
    # packed only where that lets the merged batch shrink
    packed = n_batches * LANES > lanes
    assert packed == ((n_batches, per_batch) == (16, SPARSE)
                      or (n_batches, kind) == (5, "empty_batch"))
    want = {
        f'presto_tpu_join_builds_total{{layout="{layout}"}}': 1,
        _by_layout("rows", layout): live_rows,
        _by_layout("lanes", layout): lanes,
        _by_layout("batches", layout): n_batches,
    }
    # it grows by 0 where nothing was packed: the series is there
    assert _by_layout("packed_lanes", layout) in _counters()
    if packed:
        want[_by_layout("packed_lanes", layout)] = lanes
    if layout == "direct":
        keys = [k for rows in build for k, _ in rows if k is not None]
        slots = join.direct_table_len(min(keys), max(keys), lanes)
        assert table.slot_of.shape == (slots,)
        want["presto_tpu_join_direct_table_slots_total"] = slots
    else:
        reason = "spread" if kind == "spread" else "duplicate"
        want['presto_tpu_join_direct_fallback_total'
             f'{{reason="{reason}"}}'] = 1
    finish_ns = grew.pop("presto_tpu_join_build_finish_ns_total")
    assert finish_ns > 0
    assert grew == want
    if layout == "direct":
        # the direct table keeps its batch as the merge left it: every
        # lane where it arrived and dead lanes after, or a live prefix
        arrived = np.concatenate(
            [np.asarray(_input(rows, kind).row_valid) for rows in build])
        arrived = np.pad(arrived, (0, max(lanes - len(arrived), 0)))
        assert np.asarray(table.batch.row_valid).tolist() == (
            (np.arange(lanes) < live_rows) if packed else arrived).tolist()

    probe = _probe_rows(build, np.random.default_rng(3))
    pb = Batch.from_pydict({"k": ([k for k, _ in probe], BIGINT),
                            "pv": ([v for _, v in probe], BIGINT)})
    out, overflow, n_live = join.probe_join(
        table, pb, ("k",),
        (2 if "duplicate" in kind else 1) * pb.capacity, join_type,
        ("k", "pv"), ("bv",), ("k",))
    assert not bool(overflow)
    got = out.to_pylist()
    assert int(n_live) == len(got)
    expected = _oracle(build, probe, join_type)
    if "duplicate" in kind:
        # a probe row's two build rows come in the table's order
        got, expected = sorted(got, key=repr), sorted(expected, key=repr)
    assert got == expected


def _shapes(tree):
    """What a jit's cache keys on: the pytree's structure (static
    fields included) and every leaf's shape and dtype."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, [(x.shape, x.dtype) for x in leaves]


@pytest.mark.parametrize("kind", ("ordered", "filtered",
                                  "filtered_duplicate"))
def test_unpacked_table_has_the_packed_tables_shapes(kind):
    """The build the operator hands over (lanes in arrival order) and
    the one built from the packed merge of the same inputs: the same
    capacity, the same shapes leaf for leaf, the same answers, and the
    probe compiles nothing for the second it had not for the first."""
    build = _build_rows(16, kind)
    table = _build(build, "inner", kind)
    merged = Batch.concat([_input(rows, kind) for rows in build], MERGED)
    assert np.asarray(merged.row_valid).tolist() == (
        np.arange(MERGED) < 16 * ROWS).tolist()
    if kind == "filtered_duplicate":
        packed = join.build_for_backend(merged, ("k",))
    else:
        c = merged.columns["k"]
        packed = join.build_direct(merged, "k", join.key_stats_step(
            join.key_stats_init(), c.data, c.mask, merged.row_valid),
            table.slot_of.shape[0])
    assert table.batch.capacity == packed.batch.capacity == MERGED
    assert _shapes(table) == _shapes(packed)

    probe = _probe_rows(build, np.random.default_rng(5))
    pb = Batch.from_pydict({"k": ([k for k, _ in probe], BIGINT),
                            "pv": ([v for _, v in probe], BIGINT)})
    programs = (join._direct_jit, join._hash_jit, join._search_jit,
                join._expand_dispatch, join._probe_join_fused)

    def answer(t):
        out, _, _ = join.probe_join(
            t, pb, ("k",),
            (2 if t.layout == "sorted" else 1) * pb.capacity, "inner",
            ("k", "pv"), ("bv",), ("k",))
        return sorted(out.to_pylist())
    want = answer(packed)
    compiled = [p._cache_size() for p in programs]
    assert answer(table) == want == sorted(_oracle(build, probe, "inner"))
    assert [p._cache_size() for p in programs] == compiled


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
