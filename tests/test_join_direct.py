"""The direct build layout (one unique integer join key addressed by
`key - min`) against the sorted-hash layout and a plain oracle.

Every build goes through HashBuildOperator.finish, which is where the
layout is chosen from what the build side shows: key count, dtype,
the consumer's join type, the keys' spread and their uniqueness. The
probe then runs the aligned expansion over both tables; output slot i
is probe row i in both, so the answers are compared row for row, in
order, and with the oracle's.
"""

import numpy as np
import pytest

from presto_tpu.batch import Batch
from presto_tpu.execution.memory import MemoryPool
from presto_tpu.operators.base import DriverContext
from presto_tpu.operators.join_ops import (
    HashBuildOperatorFactory, JoinBridge, LookupJoinOperatorFactory,
    _remap_keys,
)
from presto_tpu.ops import join
from presto_tpu.telemetry.metrics import METRICS
from presto_tpu.types import BIGINT, DATE, DOUBLE, INTEGER, VARCHAR

I64 = np.iinfo(np.int64)
_NO_RESULT_REPLAY = {"fragment_result_cache_enabled": False}


def _counters():
    snap = METRICS.snapshot()
    return {k: v for k, v in snap.items() if k.startswith(
        ("presto_tpu_join_builds_total",
         "presto_tpu_join_direct_fallback_total"))}


def _grew(before):
    return {k: v - before.get(k, 0) for k, v in _counters().items()
            if v != before.get(k, 0)}


def _builds(layout):
    return f'presto_tpu_join_builds_total{{layout="{layout}"}}'


def _fallback(reason):
    return f'presto_tpu_join_direct_fallback_total{{reason="{reason}"}}'


def _build(batch, keys, consumer="inner", key_dicts=None, memory=None):
    """The table HashBuildOperator hands a `consumer` join."""
    bridge = JoinBridge()
    op = HashBuildOperatorFactory(
        1, bridge, keys, key_dicts,
        schema_cols=[(n, c.type, c.dictionary)
                     for n, c in batch.columns.items()],
        consumer_layouts=LookupJoinOperatorFactory.readable_layouts(
            consumer)).create(DriverContext(memory=memory))
    op.add_input(batch)
    op.finish()
    return bridge.table


def _oracle(build_rows, probe_rows, join_type):
    """[(probe key, pv, bv)] in probe order: the join by dictionary."""
    by_key = {k: bv for k, bv in build_rows if k is not None}
    out = []
    for k, pv in probe_rows:
        if k is not None and k in by_key:
            out.append((k, pv, by_key[k]))
        elif join_type == "left":
            out.append((k, pv, None))
    return out


def _probe(table, pb, join_type):
    out, overflow, live = join.probe_join(
        table, pb, ("k",), pb.capacity, join_type, ("k", "pv"), ("bv",),
        ("k",))
    assert not bool(overflow)
    rows = out.to_pylist()
    assert int(live) == len(rows)
    return rows


def _dataset(kind):
    """(build keys, probe keys, key type): build keys unique, None a
    NULL key."""
    rng = np.random.default_rng(7)
    if kind == "dense":
        return (rng.permutation(400).tolist(),
                rng.integers(-20, 420, 600).tolist(), BIGINT)
    if kind == "gaps":
        return ((rng.permutation(400) * 7 + 3).tolist(),
                rng.integers(0, 2900, 600).tolist(), BIGINT)
    if kind == "negative":
        return ((rng.permutation(300) * 3 - 600).tolist(),
                rng.integers(-700, 400, 500).tolist(), BIGINT)
    if kind == "int32":
        return ((rng.permutation(300) * 2 + 10**6).tolist(),
                (rng.integers(0, 700, 500) + 10**6 - 50).tolist(),
                INTEGER)
    if kind == "date":
        return ((rng.permutation(365) + 9000).tolist(),
                rng.integers(8950, 9400, 500).tolist(), DATE)
    if kind == "null_keys":
        build = rng.permutation(200).tolist()
        probe = rng.integers(0, 260, 400).tolist()
        return ([None if i % 9 == 4 else k for i, k in enumerate(build)],
                [None if i % 7 == 2 else k for i, k in enumerate(probe)],
                BIGINT)
    if kind == "out_of_range":
        # below min, above max, and where key - min wraps in int64
        return ((rng.permutation(100) + 1000).tolist(),
                [999, 1000, 1099, 1100, 0, -1, int(I64.min),
                 int(I64.min) + 1000, int(I64.max), int(I64.max) - 99,
                 1050, int(I64.min) + 1050], BIGINT)
    if kind == "near_int64_max":
        top = int(I64.max)
        return ([top, top - 5, top - 2], [top, top - 1, top - 2, top - 5,
                                          top - 6, int(I64.min), 0],
                BIGINT)
    if kind == "single_row":
        return [42], [41, 42, 43, None], BIGINT
    raise AssertionError(kind)


DATASETS = ("dense", "gaps", "negative", "int32", "date", "null_keys",
            "out_of_range", "near_int64_max", "single_row")


@pytest.mark.parametrize("join_type", ("inner", "left"))
@pytest.mark.parametrize("kind", DATASETS)
def test_direct_equals_sorted_and_oracle(kind, join_type):
    bkeys, pkeys, typ = _dataset(kind)
    build_rows = [(k, 10 * i) for i, k in enumerate(bkeys)]
    probe_rows = [(k, i) for i, k in enumerate(pkeys)]
    bb = Batch.from_pydict({"k": (bkeys, typ),
                            "bv": ([bv for _, bv in build_rows], BIGINT)})
    pb = Batch.from_pydict({"k": (pkeys, typ),
                            "pv": ([pv for _, pv in probe_rows], BIGINT)})
    before = _counters()
    direct = _build(bb, ("k",), join_type)
    assert direct.layout == "direct" and direct.unique_runs
    assert direct.sorted_hash is None and direct.hash2 is None
    assert _grew(before) == {_builds("direct"): 1}
    assert int(direct.valid_count) == sum(k is not None for k in bkeys)
    sorted_ = join.build_for_backend(bb, ("k",))
    assert sorted_.layout == "sorted" and sorted_.unique_runs
    got = _probe(direct, pb, join_type)
    assert got == _probe(sorted_, pb, join_type)
    assert got == _oracle(build_rows, probe_rows, join_type)


@pytest.mark.parametrize("join_type", ("inner", "left"))
def test_dictionary_coded_keys_after_the_unified_remap(join_type):
    bwords = ["pear", "apple", "fig", "kiwi"]
    pwords = ["fig", "lime", None, "apple", "plum", "fig"]
    unified = tuple(sorted(set(bwords) | {w for w in pwords if w}))
    bb = Batch.from_pydict({"k": (bwords, VARCHAR),
                            "bv": ([1, 2, 3, 4], BIGINT)})
    pb = _remap_keys(
        Batch.from_pydict({"k": (pwords, VARCHAR),
                           "pv": (list(range(6)), BIGINT)}),
        ("k",), [unified])
    direct = _build(bb, ("k",), join_type, key_dicts=[unified])
    assert direct.layout == "direct"
    sorted_ = join.build_for_backend(
        _remap_keys(bb, ("k",), [unified]), ("k",))
    got = _probe(direct, pb, join_type)
    assert got == _probe(sorted_, pb, join_type)
    assert got == _oracle(list(zip(bwords, [1, 2, 3, 4])),
                          list(zip(pwords, range(6))), join_type)


@pytest.mark.parametrize("join_type", ("inner", "left"))
def test_empty_build(join_type):
    empty = Batch.from_pydict({"k": ([], BIGINT), "bv": ([], BIGINT)})
    pkeys = [1, None, int(I64.max), int(I64.min), 0]
    pb = Batch.from_pydict({"k": (pkeys, BIGINT),
                            "pv": (list(range(5)), BIGINT)})
    probe_rows = list(zip(pkeys, range(5)))
    table = _build(empty, ("k",), join_type)      # dead lanes only
    assert table.layout == "direct"
    assert table.slot_of.shape == (1,)
    assert _probe(table, pb, join_type) == _oracle(
        [], probe_rows, join_type)


def test_build_side_that_never_saw_a_batch():
    bridge = JoinBridge()
    op = HashBuildOperatorFactory(
        1, bridge, ("k",), None,
        schema_cols=[("k", BIGINT, None), ("bv", BIGINT, None)],
        consumer_layouts=join.LAYOUTS).create(DriverContext())
    op.finish()
    assert bridge.table.layout == "direct"
    pb = Batch.from_pydict({"k": ([3, None], BIGINT),
                            "pv": ([0, 1], BIGINT)})
    assert _probe(bridge.table, pb, "inner") == []
    assert _probe(bridge.table, pb, "left") == [(3, 0, None),
                                                (None, 1, None)]


@pytest.mark.parametrize("join_type", ("inner", "left"))
def test_half_padded_build_of_several_batches(join_type):
    """Two input batches, each half dead lanes: the merged build keeps
    arrival order, and dead lanes address no slot."""
    rng = np.random.default_rng(11)
    keys = (rng.permutation(300) * 5).tolist()
    build_rows = [(k, i) for i, k in enumerate(keys)]
    halves = [Batch.from_pydict(
        {"k": (keys[lo:hi], BIGINT),
         "bv": ([bv for _, bv in build_rows[lo:hi]], BIGINT)},
        capacity=512) for lo, hi in ((0, 170), (170, 300))]
    bridge = JoinBridge()
    op = HashBuildOperatorFactory(
        1, bridge, ("k",), None, consumer_layouts=join.LAYOUTS
    ).create(DriverContext())
    for b in halves:
        op.add_input(b)
    op.finish()
    table = bridge.table
    assert table.layout == "direct"
    assert int(table.valid_count) == 300
    assert table.batch.capacity >= 2 * 300
    pkeys = rng.integers(-10, 1600, 700).tolist()
    pb = Batch.from_pydict({"k": (pkeys, BIGINT),
                            "pv": (list(range(700)), BIGINT)})
    assert _probe(table, pb, join_type) == _oracle(
        build_rows, list(zip(pkeys, range(700))), join_type)


@pytest.mark.parametrize("join_type", ("inner", "left"))
def test_float_probe_key_matches_nothing_in_either_layout(join_type):
    """The planner casts neither side of `bigint = double`: the sorted
    layout hashes the double's bit pattern, which no integer key
    shares, and the direct probe must answer the same."""
    bb = Batch.from_pydict({"k": ([1, 2, 3], BIGINT),
                            "bv": ([0, 1, 2], BIGINT)})
    pb = Batch.from_pydict({"k": ([2.0, 3.5, None], DOUBLE),
                            "pv": ([0, 1, 2], BIGINT)})
    direct = _build(bb, ("k",), join_type)
    assert direct.layout == "direct"
    got = _probe(direct, pb, join_type)
    assert got == _probe(join.build_for_backend(bb, ("k",)), pb,
                         join_type)
    assert got == ([] if join_type == "inner" else [
        (2.0, 0, None), (3.5, 1, None), (None, 2, None)])


def _fallback_case(name):
    """(build batch, keys, consumer join type) that must stay sorted."""
    if name == "duplicate":
        return (Batch.from_pydict({"k": ([5, 9, 5, 7], BIGINT),
                                   "bv": ([0, 1, 2, 3], BIGINT)}),
                ("k",), "inner")
    if name == "spread":
        # 4096 lanes once padded: 8 x 4096 slots cannot hold 32769
        return (Batch.from_pydict({"k": ([0, 8 * 4096], BIGINT),
                                   "bv": ([0, 1], BIGINT)}),
                ("k",), "inner")
    if name == "spread_int64":
        return (Batch.from_pydict(
            {"k": ([int(I64.min), int(I64.max)], BIGINT),
             "bv": ([0, 1], BIGINT)}), ("k",), "left")
    if name == "multi_key":
        return (Batch.from_pydict({"k": ([1, 2], BIGINT),
                                   "k2": ([3, 4], BIGINT),
                                   "bv": ([0, 1], BIGINT)}),
                ("k", "k2"), "inner")
    if name == "dtype":
        return (Batch.from_pydict({"k": ([1.5, 2.5], DOUBLE),
                                   "bv": ([0, 1], BIGINT)}),
                ("k",), "inner")
    if name == "join_type":
        return (Batch.from_pydict({"k": ([1, 2], BIGINT),
                                   "bv": ([0, 1], BIGINT)}),
                ("k",), "full")
    raise AssertionError(name)


@pytest.mark.parametrize("name", ("duplicate", "spread", "spread_int64",
                                  "multi_key", "dtype", "join_type"))
def test_fallback_keeps_the_sorted_layout(name):
    batch, keys, consumer = _fallback_case(name)
    before = _counters()
    table = _build(batch, keys, consumer)
    assert table.layout == "sorted"
    assert table.slot_of is None and table.sorted_hash is not None
    assert _grew(before) == {
        _builds("sorted"): 1, _fallback(name.split("_int64")[0]): 1}


def test_spread_just_inside_the_bound_is_direct():
    table = _build(Batch.from_pydict({"k": ([0, 8 * 4096 - 1], BIGINT),
                                      "bv": ([0, 1], BIGINT)}), ("k",))
    assert table.layout == "direct"
    assert table.slot_of.shape == (8 * 4096,)
    assert join.direct_table_len(0, join.DIRECT_MAX_SPREAD - 1,
                                 1 << 30) == join.DIRECT_MAX_SPREAD
    assert join.direct_table_len(0, join.DIRECT_MAX_SPREAD,
                                 1 << 30) is None


def test_duplicate_fallback_still_answers():
    bb = Batch.from_pydict({"k": ([5, 9, 5, 7], BIGINT),
                            "bv": ([0, 1, 2, 3], BIGINT)})
    pb = Batch.from_pydict({"k": ([5, 7, 8], BIGINT),
                            "pv": ([0, 1, 2], BIGINT)})
    out, overflow, _ = join.probe_join(
        _build(bb, ("k",)), pb, ("k",), 4 * pb.capacity, "inner",
        ("k", "pv"), ("bv",), ("k",))
    assert not bool(overflow)
    assert sorted(out.to_pylist()) == [(5, 0, 0), (5, 0, 2), (7, 1, 3)]


def test_table_bytes_are_reserved():
    pool = MemoryPool(1 << 30)
    bb = Batch.from_pydict({"k": (list(range(0, 2000, 2)), BIGINT),
                            "bv": (list(range(1000)), BIGINT)})
    from presto_tpu.execution.memory import batch_bytes
    table = _build(bb, ("k",), memory=pool)
    assert table.layout == "direct"
    assert table.slot_of.shape == (2048,)
    assert pool.reserved == batch_bytes(table.batch) + 4 * 2048


def test_another_min_compiles_nothing():
    """min and max are device values of the table, never static: the
    same shapes under another date literal or seed reuse every
    program (build, stats fold, probe)."""
    def run(offset):
        keys = (np.arange(500) * 3 + offset).tolist()
        bb = Batch.from_pydict({"k": (keys, BIGINT),
                                "bv": (list(range(500)), BIGINT)})
        pb = Batch.from_pydict(
            {"k": ((np.arange(800) + offset - 20).tolist(), BIGINT),
             "pv": (list(range(800)), BIGINT)})
        table = _build(bb, ("k",))
        assert table.layout == "direct"
        return _probe(table, pb, "inner")
    first = run(1000)
    compiles = METRICS.total("presto_tpu_xla_compiles_total")
    second = run(77_000_000)
    assert METRICS.total("presto_tpu_xla_compiles_total") == compiles
    assert [(pv, bv) for _, pv, bv in first] == \
        [(pv, bv) for _, pv, bv in second]


SQL = {
    "inner": "select o.orderkey, c.name from orders o join customer c "
             "on o.custkey = c.custkey where o.totalprice > 400000 "
             "order by 1",
    "left": "select o.orderkey, c.name from orders o left join "
            "(select * from customer where acctbal > 9000) c "
            "on o.custkey = c.custkey where o.totalprice > 400000 "
            "order by 1",
}


@pytest.mark.parametrize("join_type", ("inner", "left"))
def test_planned_join_takes_the_direct_layout(join_type, monkeypatch):
    from presto_tpu.runner import LocalRunner
    runner = LocalRunner("tpch", "tiny", properties=_NO_RESULT_REPLAY)
    before = _counters()
    direct = runner.execute(SQL[join_type]).rows()
    assert _grew(before) == {_builds("direct"): 1}
    monkeypatch.setattr(LookupJoinOperatorFactory, "readable_layouts",
                        staticmethod(lambda jt: ("sorted",)))
    runner = LocalRunner("tpch", "tiny", properties=_NO_RESULT_REPLAY)
    before = _counters()
    assert runner.execute(SQL[join_type]).rows() == direct
    assert _grew(before) == {_builds("sorted"): 1,
                             _fallback("join_type"): 1}
    assert direct


@pytest.mark.parametrize("sql, reason", [
    ("select count(*) from orders o full join customer c "
     "on o.custkey = c.custkey", "join_type"),
    ("select count(*) from orders where custkey in "
     "(select custkey from customer where acctbal > 5000)", "join_type"),
    ("select count(*) from lineitem l join partsupp ps on "
     "l.partkey = ps.partkey and l.suppkey = ps.suppkey", "multi_key"),
    ("select count(*) from customer c join orders o "
     "on c.custkey = o.custkey", "duplicate"),
], ids=("full", "semi", "two_keys", "fact_table_build"))
def test_planned_consumers_that_keep_the_sorted_layout(sql, reason):
    from presto_tpu.runner import LocalRunner
    runner = LocalRunner("tpch", "tiny", properties=dict(
        _NO_RESULT_REPLAY, join_reordering=False)
        if reason == "duplicate" else _NO_RESULT_REPLAY)
    before = _counters()
    runner.execute(sql)
    grew = _grew(before)
    assert grew.get(_fallback(reason)) == 1, grew
    assert _builds("direct") not in grew
