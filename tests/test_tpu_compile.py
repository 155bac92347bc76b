"""Compile guards: the main path's kernels must COMPILE for a TPU v5e.

Tier-1 runs on the CPU, so every trace-time platform fork
(ops/common.cpu_backend) only ever shows the suite its CPU side, and
XLA:TPU refuses programs XLA:CPU accepts (every bitcast from f64, for
one). These cases steer the forks to their TPU side with monkeypatch
and AOT-compile each kernel for a DESCRIBED v5e:2x2 — no chip
attached, nothing runs, no result or time is checked. A compile that
passes here is not a chip run (`python chip_smoke.py` is).

The topology is described inside the module-scoped fixture below and
nowhere else: only one process may load the TPU's library, so nothing
at import time, nothing in conftest.py, no child process, one file.
"""

import collections
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

#: rows per batch of the chip smoke and the bench (`batch_rows`)
BATCH = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any reason it can't
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent
    # cache but can never be read back without one: keep it off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_forks(monkeypatch):
    """Steer every trace-time platform fork to its TPU side."""
    from presto_tpu.ops import common
    monkeypatch.setattr(common, "cpu_backend", lambda: False)


def _place(tree, sharding):
    """Abstract twin of `tree` whose array leaves live on `sharding`."""
    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=sharding)
        return x
    return jax.tree_util.tree_map(leaf, tree)


def _compile(fn, args, sharding):
    compiled = jax.jit(fn).lower(*_place(args, sharding)).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0
    return compiled


def _sds(n, dtype):
    return jax.ShapeDtypeStruct((n,), dtype)


@pytest.mark.parametrize("name", ["hash64", "hash64b"])
def test_hash_of_double_key(one_chip, tpu_forks, name):
    """GROUP BY / join / DISTINCT / shuffle on a DOUBLE key: the f64 ->
    int64 key is arithmetic (common.float64_bits), not the bitcast
    XLA:TPU's X64 rewriter refuses."""
    from presto_tpu.ops import common
    _compile(getattr(common, name),
             (_sds(BATCH, jnp.float64), _sds(BATCH, jnp.bool_)),
             one_chip)


def test_expr_hash_of_double(one_chip, tpu_forks):
    from presto_tpu.expr import compile as expr_compile
    _compile(expr_compile._hash64,
             (_sds(BATCH, jnp.float64), _sds(BATCH, jnp.bool_)),
             one_chip)


def test_merge_total_order_f64(one_chip, tpu_forks):
    """The streaming merge of an ORDER BY on a DOUBLE."""
    from presto_tpu.ops import merge
    _compile(merge._total_order, (_sds(BATCH, jnp.float64),), one_chip)


def test_sort_on_double_key(one_chip, tpu_forks):
    """ORDER BY a DOUBLE, nulls last, over a live-row mask: the
    comparator sees 32-bit lanes only (common._narrow_sort_key)."""
    from presto_tpu.ops import common
    n = 1 << 12  # the bucket a query's final ORDER BY lands in

    def fn(d, m, v):
        return common.sort_rows([(d, m)], descending=[True], valid=v,
                                payloads=[d])
    _compile(fn, (_sds(n, jnp.float64), _sds(n, jnp.bool_),
                  _sds(n, jnp.bool_)), one_chip)


def test_partition_perm_and_compaction(one_chip, tpu_forks):
    from presto_tpu.ops import common

    def fn(valid, col):
        return col[common.partition_perm(valid)], \
            common.first_true_indices(valid, BATCH // 4, BATCH - 1)
    _compile(fn, (_sds(BATCH, jnp.bool_), _sds(BATCH, jnp.int64)),
             one_chip)


@pytest.mark.parametrize("reduce", ["sum", "min"])
def test_slot_reduce_onehot(one_chip, tpu_forks, reduce):
    """Q1's 12-slot direct aggregation: the TPU side is the one-hot
    masked reduce, not segment_*."""
    from presto_tpu.ops import hashagg
    _compile(
        lambda c, g: hashagg._slot_reduce(c, g, 12, reduce, jnp.float64),
        (_sds(BATCH, jnp.float64), _sds(BATCH, jnp.int32)), one_chip)


def test_q1_fused_step(one_chip, tpu_forks):
    """__graft_entry__.entry(): the flagship Q1 filter + project +
    grouped fold + finalize (took the TPU compiler 260 s before the
    sort and scan operands were narrowed, ISSUE 22)."""
    import __graft_entry__
    fn, args = __graft_entry__.entry()
    _compile(fn, args, one_chip)


def test_sorted_aggregation_step(one_chip, tpu_forks):
    """The high-cardinality (sort-based) aggregation Q3 runs: hash
    sort, boundaries, prefix-sum group ids, and the segment ends of
    _first_rows: one scatter, no search loop at any shape."""
    from presto_tpu.ops import hashagg
    from presto_tpu.types import BIGINT, DOUBLE
    n, cap = 1 << 14, 1 << 12  # the shapes Q3 traces at sf1
    aggs = (hashagg.make_sum(DOUBLE, DOUBLE), hashagg.make_count(BIGINT))

    def fn(valid, k1, k2, x):
        true = jnp.ones_like(valid)
        return hashagg.batch_aggregate(
            valid, [(k1, true), (k2, true)], [x, None],
            [valid, valid], aggs, cap)
    compiled = _compile(fn, (_sds(n, jnp.bool_), _sds(n, jnp.int64),
                             _sds(n, jnp.int32), _sds(n, jnp.float64)),
                        one_chip)
    assert " while(" not in compiled.as_text()


@pytest.mark.parametrize("family,cap", [("join_build", 1 << 16),
                                        ("join_probe", BATCH)])
def test_join_kernels(one_chip, tpu_forks, family, cap):
    """The device join build (hash, order by hash, radix metadata) and
    the inner probe, through their KernelContract trace points."""
    from presto_tpu.analysis.contracts import contract_for
    import presto_tpu.ops.join  # noqa: F401 — registers the contracts
    point = contract_for(family)[0].build(cap, {})
    _compile(point.fn, point.args, one_chip)


@pytest.mark.parametrize("program", ["stats", "build", "probe_inner",
                                     "probe_left"])
def test_direct_join_kernels_at_q3_size(one_chip, tpu_forks, program):
    """The direct layout's programs at the shapes Q3's first join has
    at sf1: a 1M-lane build side (date-filtered orders) whose keys
    spread over 2^21 slots, probed by 1M-row lineitem batches."""
    from presto_tpu.ops import join
    slots = 1 << 21
    if program == "stats":
        fn = join.key_stats_step.__wrapped__
        args = (_sds(3, jnp.int64), _sds(BATCH, jnp.int64),
                _sds(BATCH, jnp.bool_), _sds(BATCH, jnp.bool_))
    elif program == "build":
        batch, _ = join.abstract_batch(BATCH, join._probe_schema())
        fn = lambda b, st: join._build_direct(b, "pk", st, slots)  # noqa: E731
        args = (batch, _sds(3, jnp.int64))
    else:
        table, _ = join._abstract_direct_table(BATCH, slots)
        probe, _ = join.abstract_batch(BATCH, join._probe_schema())
        jt = program[len("probe_"):]
        fn = lambda t, p: join._probe_join_fused(  # noqa: E731
            t, p, ("pk",), None, BATCH, jt, ("pk", "pv"), ("bv",),
            ("bk",), "hash")
        args = (table, probe)
    text = _compile(fn, args, one_chip).as_text()
    assert ("sort(" in text) is False, "the direct layout sorts nothing"


def _aligned_front(table, probe):
    """The aligned probe's first program: the search, no build column."""
    from presto_tpu.ops import join
    return join.aligned_front(
        table, probe, ("pk",), join._candidates_enc(table, probe, ("pk",)),
        None, "inner", tuple(probe.names), ("bk",), "hash")


def _aligned_back(build, probe, brow, verified, live):
    """Its second: both sides' columns gathered at 65,536 lanes, the
    bucket of a 1M-lane lineitem batch's live count in Q3."""
    from presto_tpu.ops import join
    return join.aligned_back(build, probe, brow, verified, live, 1 << 16,
                             "inner", tuple(build.names))


def _q5_back(build, probe, brow, verified, live):
    """Q5's lineitem-orders back at SF10: about 160,000 of a 1M-lane
    batch's lines are of 1994, so it packs onto 262,144 lanes."""
    from presto_tpu.ops import join
    return join.aligned_back(build, probe, brow, verified, live, 1 << 18,
                             "inner", tuple(build.names))


@pytest.mark.parametrize("half", ["front", "back", "q5_back"])
def test_late_materialized_probe_at_q3_size(one_chip, tpu_forks, half):
    """The aligned probe's two programs at the shapes of Q3's lineitem
    join at sf1: the front searches a 1M-row batch and gathers no
    build column; the back packs 1,048,576 lanes into 65,536 and
    gathers 3 probe and 4 build columns there. And Q5's back at SF10:
    1,048,576 lanes into 262,144, 4 probe columns and 2 build columns
    gathered out of the 4M-lane orders build. Both backs find the live
    rows with one scatter of their ranks (flag_pack_form): no search
    loop, and no sort before the scatter."""
    from presto_tpu.ops import common, join
    from presto_tpu.types import BIGINT, DATE, DOUBLE, INTEGER
    table, _ = join._abstract_direct_table(BATCH, 1 << 21)
    build, _ = join.abstract_batch(BATCH, [
        ("bk", BIGINT), ("custkey", BIGINT), ("orderdate", DATE),
        ("shippriority", INTEGER)])
    probe, _ = join.abstract_batch(BATCH, [
        ("pk", BIGINT), ("extendedprice", DOUBLE),
        ("discount", DOUBLE)])
    width = {"back": 1 << 16, "q5_back": 1 << 18}.get(half)
    if half == "front":
        fn, args = _aligned_front, (table, probe)
    else:
        fn = _aligned_back
        if half == "q5_back":
            fn = _q5_back
            build, _ = join.abstract_batch(4 * BATCH, [
                ("orderkey", BIGINT), ("custkey", BIGINT)])
            probe, _ = join.abstract_batch(BATCH, [
                ("orderkey", BIGINT), ("suppkey", BIGINT),
                ("extendedprice", DOUBLE), ("discount", DOUBLE)])
        assert common.flag_pack_form(BATCH, width) == "scatter"
        args = (build, probe, _sds(BATCH, jnp.int32),
                _sds(BATCH, jnp.bool_),
                jax.ShapeDtypeStruct((), jnp.int64))
    text = _compile(fn, args, one_chip).as_text()
    assert ("sort(" in text) is False, "nothing here sorts"
    if half != "front":
        assert " while(" not in text, "no search loop in the back"
        assert _moves(text)[("scatter", width)] == 1
        out = jax.eval_shape(fn, *args)
        assert {x.shape for x in jax.tree_util.tree_leaves(out)} \
            == {(width,)}


@pytest.mark.parametrize("family", ["spmd_shuffle", "spmd_fragment"])
def test_exchange_shard_map_on_four_chips(topo, tpu_forks, monkeypatch,
                                          family):
    """The hash shuffle as ONE program over the four described chips:
    the compiler must keep the all_to_all, and every chip gets a
    shard."""
    from presto_tpu.analysis.contracts import contract_for
    from presto_tpu.parallel import shuffle
    from presto_tpu.parallel.mesh import worker_axis
    mesh = Mesh(np.array(topo.devices[:4]), (worker_axis,))
    monkeypatch.setattr(shuffle, "_contract_mesh", lambda: mesh)
    point = contract_for(family)[0].build(1 << 16, {})
    compiled = _compile(point.fn, point.args,
                        NamedSharding(mesh, P(worker_axis)))
    assert "all-to-all" in compiled.as_text()


#: TPC-H Q3 at sf10: the orders build side is 16 input batches of
#: BATCH lanes, merged into one batch on the ladder's 16M rung, and its
#: direct table has 2^24 slots (64 MB: past the chip's fast memory)
SF10_BUILD_LANES = 16 * BATCH
SF10_TABLE_SLOTS = 1 << 24


def _moves(text):
    """{(gather | scatter, lanes): count} over a compiled program's
    text: what it moves by index, and how wide."""
    found = collections.Counter()
    for m in re.finditer(
            r"= \w+\[(\d+)\][^=\n]*? (gather|scatter)\(", text):
        found[m.group(2), int(m.group(1))] += 1
    return dict(found)


def _sf10_orders(lanes):
    from presto_tpu.ops import join
    from presto_tpu.types import BIGINT, DATE, INTEGER
    return join.abstract_batch(lanes, [
        ("bk", BIGINT), ("custkey", BIGINT), ("orderdate", DATE),
        ("shippriority", INTEGER)])[0]


def _unjitted(fn):
    while hasattr(fn, "__wrapped__"):
        fn = fn.__wrapped__
    return fn


@pytest.mark.parametrize("program", [
    "key_stats", "concat_lanes", "concat_pack", "direct_build",
    "distinct_set", "front", "back"])
def test_q3_build_of_sixteen_batches_at_sf10_size(
        one_chip, tpu_forks, record_property, program):
    """The programs Q3's lineitem-orders join runs at sf10 and never
    at sf1 (rehearsal, PR 34: compile seconds on this sandbox beside
    each): the stats fold of a 1M-lane input (2 s), the 16-way
    concatenation of the inputs x 4 orders columns into 16,777,216
    lanes, which is all the build's merge does since PR 35
    (`Batch.concat_lanes`: copies, nothing moved by index),
    `_compact_jit` over a batch that wide (15 s; the sort and window
    operators' merge still packs, `Batch.concat`), the direct build
    over the un-packed batch into 2^24 slots (14 s), the
    dynamic filter's distinct set over the merged key column (its
    sort: 84 s, the longest), and the aligned probe's front against
    the 64 MB table (0.5 s) and back from 1M to 65,536 lanes over the
    16M-lane build batch (0.6 s)."""
    from presto_tpu import batch as batch_mod
    from presto_tpu.execution import dynamic_filters
    from presto_tpu.ops import join
    from presto_tpu.types import BIGINT, DOUBLE
    wide = SF10_BUILD_LANES
    lineitem = join.abstract_batch(BATCH, [
        ("pk", BIGINT), ("extendedprice", DOUBLE),
        ("discount", DOUBLE)])[0]
    if program == "key_stats":
        fn = _unjitted(join.key_stats_step)
        args = (_sds(3, jnp.int64), _sds(BATCH, jnp.int64),
                _sds(BATCH, jnp.bool_), _sds(BATCH, jnp.bool_))
        want = {}
    elif program == "concat_lanes":
        fn = lambda *bs: batch_mod.Batch.concat_lanes(  # noqa: E731
            bs, wide)
        args = tuple(_sf10_orders(BATCH) for _ in range(16))
        want = {}                       # no gather, no scatter: copies
    elif program == "concat_pack":
        # one scatter (partition_perm), then per column a gather of
        # the data (two 32-bit halves for a BIGINT) and of the mask,
        # and row_valid's: all at the merged width
        fn, args = _unjitted(batch_mod._compact_jit), (_sf10_orders(wide),)
        want = {("scatter", wide): 1, ("gather", wide): 11}
    elif program == "direct_build":
        fn = lambda b, st: _unjitted(join._build_direct)(  # noqa: E731
            b, "bk", st, SF10_TABLE_SLOTS)
        args = (_sf10_orders(wide), _sds(3, jnp.int64))
        want = {("scatter", SF10_TABLE_SLOTS): 1, ("gather", wide): 1}
    elif program == "distinct_set":
        fn = _unjitted(dynamic_filters.distinct_set)
        args = (_sds(wide, jnp.int64), _sds(wide, jnp.bool_))
        # the sorted key's two halves and its mask, then the packed
        # DF_SET_MAX slots
        want = {("gather", wide): 3,
                ("gather", dynamic_filters.DF_SET_MAX): 3}
    elif program == "front":
        table, _ = join._abstract_direct_table(wide, SF10_TABLE_SLOTS)
        fn, args = _aligned_front, (table, lineitem)
        want = {("gather", BATCH): 1}   # slot_of[key - min], no other
    else:
        fn = _aligned_back
        args = (_sf10_orders(wide), lineitem, _sds(BATCH, jnp.int32),
                _sds(BATCH, jnp.bool_),
                jax.ShapeDtypeStruct((), jnp.int64))
        # the live rows' indices by one scatter of their ranks
        # (flag_pack_form at 1M -> 65,536), then the columns' gathers;
        # none at a wider lane
        want = {("gather", 1 << 16): 21, ("scatter", 1 << 16): 1}
    t0 = time.perf_counter()
    compiled = _compile(fn, args, one_chip)
    seconds = time.perf_counter() - t0
    record_property("compile_seconds", round(seconds, 1))
    print(f"{program}: compiled for a described v5e in {seconds:.1f} s")
    text = compiled.as_text()
    assert _moves(text) == want
    # at 16M lanes XLA:TPU lowers a scatter through a sort of its
    # (index, lane) pairs, which it does not at sf1's 1M lanes
    # (test_direct_join_kernels_at_q3_size): the pack's and the
    # build's one scatter each; the distinct set's sort is its own
    assert text.count(" sort(") == (
        1 if program in ("concat_pack", "direct_build", "distinct_set")
        else 0)
    # one program's share of the chip's 16 GB: arguments, outputs and
    # temporaries (the merged batch is 0.49 GB of each)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 2 << 30


#: TPC-H Q18 at sf1 (the cell sf1_q18_serial): about half of lineitem's
#: scan batches overshoot 2^20 rows by a few thousand and ride this rung
#: a quarter live; the 1,500,000-row orders build sits on it too
WIDE = 4 * BATCH
#: the semi join's build side (the orders whose lines pass QUANTITY =
#: 300: under a hundred rows at sf1) lands on the ladder's smallest rung
SMALLEST_RUNG = 1 << 12
#: the 150,000-row customer build's rung
CUSTOMER_LANES = 1 << 18


def _sorted_table(lanes):
    """An abstract sorted-hash BuildTable over one unique BIGINT key
    (the semi join's build side carries no payload)."""
    from presto_tpu.ops import join
    from presto_tpu.types import BIGINT
    batch = join.abstract_batch(lanes, [("bk", BIGINT)])[0]
    k = join.choose_radix_bits(lanes)
    return join.BuildTable(
        _sds(lanes, jnp.int64), _sds(lanes, jnp.int64),
        _sds((1 << k) + 1, jnp.int64), _sds(lanes, jnp.int64),
        jax.ShapeDtypeStruct((), jnp.int64), batch, radix_bits=k,
        search_depth=8, unique_runs=True)


@pytest.mark.parametrize("program", [
    "presorted_step_4m", "presorted_step_1m", "agg_stream", "sorted_build", "semi_probe_4m",
    "back_full_width", "customer_back_full_width", "five_key_agg_step"])
def test_q18_programs_at_the_cells_size(one_chip, tpu_forks,
                                        record_property, program):
    """The programs Q18 runs in the cell sf1_q18_serial that no cell of
    the benchmark ran before PR 37 (rehearsal, PR 37: compile seconds
    on this sandbox in CHANGES.md): the streaming aggregation's
    presorted grouping of a lineitem batch at 4,194,304 and at
    1,048,576 lanes (a cummax, a prefix sum, one scatter of the
    boundary rows for the segment ends: no sort, and since PR 38 no
    `while`, the per-slot binary searches being gone) and its
    boundary fold with a carried group
    (`agg_stream`); the sorted-hash build of the semi join's build side
    on the smallest rung and the `semi_join` probe of a 4M-lane batch
    against it; the aligned probe's back at K = capacity (every
    lineitem row finds its order: 4M -> 4M lanes, nothing packed, 3
    orders columns gathered out of the 4,194,304-lane build batch; then
    1 customer column out of a 262,144-lane one); and the final
    aggregation's step over five keys, one a dictionary VARCHAR's int32
    code and one a DOUBLE."""
    from presto_tpu.operators import aggregation
    from presto_tpu.ops import hashagg, join
    from presto_tpu.types import BIGINT, DATE, DOUBLE, VARCHAR
    aggs = (hashagg.make_sum(DOUBLE, DOUBLE),)
    sorts = 0
    if program.startswith("presorted_step"):
        n = WIDE if program.endswith("4m") else BATCH
        # no sort of the rows; XLA:TPU lowers the scatter of 4,194,304
        # segment-end indices through a sort of its own (of the
        # indices: 12 s of compile), at 1,048,576 lanes it does not
        sorts = 1 if n == WIDE else 0

        def fn(valid, k, km, x, xm):
            return hashagg.presorted_aggregate(
                valid, [(k, km)], [x], [valid & xm], aggs, n)
        args = (_sds(n, jnp.bool_), _sds(n, jnp.int64),
                _sds(n, jnp.bool_), _sds(n, jnp.float64),
                _sds(n, jnp.bool_))
    elif program == "agg_stream":
        fn = lambda c, p: _unjitted(  # noqa: E731
            aggregation._stream_step_jit)(c, p, aggs)
        args = (jax.eval_shape(lambda: hashagg.init_state(
                    [BIGINT], aggs, 1)),
                jax.eval_shape(lambda: hashagg.init_state(
                    [BIGINT], aggs, BATCH)))
    elif program == "sorted_build":
        fn = lambda b: _unjitted(join._build_sorted)(  # noqa: E731
            b, ("bk",), join.choose_radix_bits(SMALLEST_RUNG))
        args = (join.abstract_batch(SMALLEST_RUNG, [("bk", BIGINT)])[0],)
        sorts = 1                       # the order by hash
    elif program == "semi_probe_4m":
        fn = lambda t, p: _unjitted(  # noqa: E731
            join._semi_unique_fused)(t, p, ("pk",))
        args = (_sorted_table(SMALLEST_RUNG),
                join.abstract_batch(WIDE, [
                    ("pk", BIGINT), ("quantity", DOUBLE)])[0])
    elif program == "back_full_width":
        fn = lambda b, s, brow, ok, live: join.aligned_back(  # noqa: E731
            b, s, brow, ok, live, WIDE, "inner",
            ("custkey", "orderdate", "totalprice"))
        args = (join.abstract_batch(WIDE, [
                    ("bk", BIGINT), ("custkey", BIGINT),
                    ("orderdate", DATE), ("totalprice", DOUBLE)])[0],
                join.abstract_batch(WIDE, [
                    ("pk", BIGINT), ("quantity", DOUBLE)])[0],
                _sds(WIDE, jnp.int32), _sds(WIDE, jnp.bool_),
                jax.ShapeDtypeStruct((), jnp.int64))
    elif program == "customer_back_full_width":
        fn = lambda b, s, brow, ok, live: join.aligned_back(  # noqa: E731
            b, s, brow, ok, live, WIDE, "inner", ("name",))
        args = (join.abstract_batch(CUSTOMER_LANES, [
                    ("bk", BIGINT), ("name", VARCHAR)])[0],
                join.abstract_batch(WIDE, [
                    ("pk", BIGINT), ("quantity", DOUBLE),
                    ("custkey", BIGINT), ("orderdate", DATE),
                    ("totalprice", DOUBLE)])[0],
                _sds(WIDE, jnp.int32), _sds(WIDE, jnp.bool_),
                jax.ShapeDtypeStruct((), jnp.int64))
    else:
        n = SMALLEST_RUNG

        def fn(valid, name, custkey, orderkey, orderdate, totalprice, x):
            return hashagg.batch_aggregate(
                valid, [(k, valid) for k in (name, custkey, orderkey,
                                             orderdate, totalprice)],
                [x], [valid], aggs, n)
        args = (_sds(n, jnp.bool_), _sds(n, jnp.int32),
                _sds(n, jnp.int64), _sds(n, jnp.int64),
                _sds(n, jnp.int32), _sds(n, jnp.float64),
                _sds(n, jnp.float64))
        sorts = None                    # the grouping sorts: not counted
    t0 = time.perf_counter()
    compiled = _compile(fn, args, one_chip)
    seconds = time.perf_counter() - t0
    record_property("compile_seconds", round(seconds, 1))
    print(f"{program}: compiled for a described v5e in {seconds:.1f} s")
    text = compiled.as_text()
    if sorts is not None:
        assert text.count(" sort(") == sorts
    if program.startswith("presorted_step") \
            or program == "five_key_agg_step":
        # segment ends come from the boundary mask: no search loop
        assert " while(" not in text
    if program.endswith("back_full_width"):
        # K = capacity: the probe's own columns stay where they are
        # and only the build's are gathered, at the batch's width
        # (a 64-bit column is two 32-bit gathers on this chip)
        moves = _moves(text)
        assert set(moves) == {("gather", WIDE)}, moves
        out = jax.eval_shape(fn, *args)
        assert {x.shape for x in jax.tree_util.tree_leaves(out)} \
            == {(WIDE,)}
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 2 << 30


#: TPC-H Q5 at SF10 (the cell sf10_q5_serial): the supplier build's
#: 100,000 rows sit on this rung, and so does each batch of the
#: customer join's output (about 160,000 live lines of 1994's orders a
#: lineitem batch) that probes it
Q5_LANES = 1 << 18


def _two_key_table(lanes):
    """An abstract sorted-hash BuildTable over the unique composite key
    (suppkey, nationkey)."""
    from presto_tpu.ops import join
    from presto_tpu.types import BIGINT
    batch = join.abstract_batch(lanes, [("bk1", BIGINT), ("bk2", BIGINT)])[0]
    k = join.choose_radix_bits(lanes)
    return join.BuildTable(
        _sds(lanes, jnp.int64), _sds(lanes, jnp.int64),
        _sds((1 << k) + 1, jnp.int64), _sds(lanes, jnp.int64),
        jax.ShapeDtypeStruct((), jnp.int64), batch, radix_bits=k,
        search_depth=8, unique_runs=True)


@pytest.mark.parametrize("program, lanes", [
    ("sorted_build", Q5_LANES), ("sorted_front", Q5_LANES),
    ("sorted_front", BATCH), ("sorted_front", WIDE)])
def test_q5_two_key_sorted_join_programs(one_chip, tpu_forks,
                                         record_property, program, lanes):
    """The sorted layout's programs on two keys, which no other cell
    runs (compile seconds for a described v5e beside each): the build hashes both keys, orders the rows by hash
    (one sort) and derives the radix metadata (34 s at 262,144 lanes);
    the probe's front hashes the probe's two keys and searches its
    bucket with gathers alone, at Q5's 262,144 lanes and at the 1M and
    4M lanes a lineitem batch has (2-3 s each)."""
    from presto_tpu.ops import join
    from presto_tpu.types import BIGINT, DOUBLE
    keys = ("bk1", "bk2")
    if program == "sorted_build":
        batch = join.abstract_batch(lanes, [
            ("bk1", BIGINT), ("bk2", BIGINT)])[0]
        fn = lambda b: join._build_sorted(  # noqa: E731
            b, keys, join.choose_radix_bits(lanes))
        args = (batch,)
    else:
        probe = join.abstract_batch(lanes, [
            ("pk1", BIGINT), ("pk2", BIGINT), ("extendedprice", DOUBLE),
            ("discount", DOUBLE)])[0]

        def fn(table, p):
            pk = ("pk1", "pk2")
            return join.aligned_front(
                table, p, pk, join._candidates_enc(table, p, pk), None,
                "inner", tuple(p.names), keys, "hash")
        args = (_two_key_table(Q5_LANES), probe)
    t0 = time.perf_counter()
    compiled = _compile(fn, args, one_chip)
    seconds = time.perf_counter() - t0
    record_property("compile_seconds", round(seconds, 1))
    print(f"{program} at {lanes}: compiled for a described v5e in "
          f"{seconds:.1f} s")
    text = compiled.as_text()
    if program == "sorted_build":
        assert text.count(" sort(") == 1
    else:
        # the bounded search is unrolled gathers at the batch's width
        assert " sort(" not in text and " while(" not in text
        assert set(_moves(text)) == {("gather", lanes)}
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 2 << 30
