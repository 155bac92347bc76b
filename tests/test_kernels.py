"""Kernel tests against pandas/numpy oracles (reference analog:
presto-main operator tests asserting output pages, OperatorAssertion.java:53)."""

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from presto_tpu.batch import Batch, bucket_capacity
from presto_tpu.execution.dynamic_filters import DF_SET_MAX
from presto_tpu.ops import common, hashagg, join, sort
from presto_tpu.types import BIGINT, DOUBLE, VARCHAR


def rows_of(batch):
    """Set-of-tuples for order-insensitive comparison."""
    return sorted(batch.to_pylist(), key=lambda t: tuple(
        (v is None, v) for v in t))


def test_groupby_sum_count_vs_pandas():
    rng = np.random.default_rng(0)
    n = 1000
    g = rng.integers(0, 7, n)
    v = rng.integers(-100, 100, n).astype(float)
    vals = [None if i % 13 == 0 else float(v[i]) for i in range(n)]
    b = Batch.from_pydict({"g": (g.tolist(), BIGINT), "v": (vals, DOUBLE)})

    aggs = [hashagg.make_sum(DOUBLE, DOUBLE), hashagg.make_count(DOUBLE),
            hashagg.make_avg(DOUBLE), hashagg.make_min(DOUBLE),
            hashagg.make_max(DOUBLE)]
    st = hashagg.init_state([BIGINT], aggs, max_groups=16)
    gcol = b.columns["g"].astuple()
    vcol = b.columns["v"].astuple()
    w_v = b.row_valid & vcol[1]
    st = hashagg.agg_step(
        st, b.row_valid, [gcol],
        [vcol[0], None, vcol[0], vcol[0], vcol[0]],
        [w_v, b.row_valid, w_v, w_v, w_v], aggs)
    out = hashagg.finalize(st, ["g"], [BIGINT], [None],
                           ["s", "c", "a", "mn", "mx"], aggs)

    df = pd.DataFrame({"g": g, "v": vals}).astype({"v": float})
    exp = df.groupby("g").agg(
        s=("v", "sum"), c=("v", "size"), a=("v", "mean"),
        mn=("v", "min"), mx=("v", "max")).reset_index()
    got = out.to_pandas().sort_values("g").reset_index(drop=True)
    assert got["g"].tolist() == exp["g"].tolist()
    np.testing.assert_allclose(got["s"], exp["s"], rtol=1e-12)
    assert got["c"].tolist() == exp["c"].tolist()
    np.testing.assert_allclose(got["a"], exp["a"], rtol=1e-12)
    np.testing.assert_allclose(got["mn"], exp["mn"])
    np.testing.assert_allclose(got["mx"], exp["mx"])


def test_groupby_multibatch_accumulation():
    aggs = [hashagg.make_sum(BIGINT, BIGINT)]
    st = hashagg.init_state([BIGINT], aggs, max_groups=16)
    for chunk in ([1, 2, 1], [2, 2, 3], [1, 3, 3]):
        b = Batch.from_pydict({"g": (chunk, BIGINT),
                               "v": ([10] * len(chunk), BIGINT)})
        g = b.columns["g"].astuple()
        v = b.columns["v"].astuple()
        w = b.row_valid & v[1]
        st = hashagg.agg_step(st, b.row_valid, [g], [v[0]], [w], aggs)
    out = hashagg.finalize(st, ["g"], [BIGINT], [None], ["s"], aggs)
    assert rows_of(out) == [(1, 30), (2, 30), (3, 30)]
    assert not bool(np.asarray(st.overflow))


def test_groupby_overflow_flag():
    aggs = [hashagg.make_count(None)]
    st = hashagg.init_state([BIGINT], aggs, max_groups=16)
    b = Batch.from_pydict({"g": (list(range(40)), BIGINT)})
    g = b.columns["g"].astuple()
    st = hashagg.agg_step(st, b.row_valid, [g], [None], [b.row_valid], aggs)
    assert bool(np.asarray(st.overflow))


def test_global_aggregation():
    aggs = [hashagg.make_sum(BIGINT, BIGINT), hashagg.make_count(None)]
    st = hashagg.init_state([], aggs, max_groups=16)
    b = Batch.from_pydict({"v": ([5, None, 7], BIGINT)})
    v = b.columns["v"].astuple()
    st = hashagg.agg_step(st, b.row_valid, [], [v[0], None],
                          [b.row_valid & v[1], b.row_valid], aggs)
    out = hashagg.finalize(st, [], [], [], ["s", "c"], aggs)
    assert out.to_pylist()[:1] == [(12, 3)]
    assert out.num_valid() == 1


def _boundary_mask(valid, keys):
    """Boundary mask and monotone ids of rows already in key order, as
    presorted_reduce derives them: a live row starts a group when its
    key differs from the previous LIVE row's."""
    bnd = np.zeros(len(valid), bool)
    prev = None
    for i, (v, k) in enumerate(zip(valid, keys)):
        if v:
            bnd[i] = prev is None or k != prev
            prev = k
    return bnd, np.cumsum(bnd).astype(np.int32) - 1


#: name -> (row_valid, keys in order, out_cap)
_SEGMENT_CASES = {
    "leading_dead_rows": ([0, 0, 0, 1, 1, 1, 1, 1],
                          [9, 9, 9, 1, 1, 2, 3, 3], 8),
    "dead_rows_inside_groups": ([1, 0, 1, 1, 0, 0, 1, 0],
                                [1, 5, 1, 2, 7, 2, 2, 2], 8),
    "no_live_row": ([0] * 8, [1, 2, 3, 4, 5, 6, 7, 8], 8),
    "one_group_spans_the_batch": ([1] * 8, [4] * 8, 8),
    "every_row_its_own_group": ([1] * 8, list(range(8)), 8),
    "out_cap_under_the_group_count": ([0, 1, 1, 1, 0, 1, 1, 1],
                                      [0, 1, 2, 2, 2, 3, 4, 5], 3),
}


@pytest.mark.parametrize("case", sorted(_SEGMENT_CASES))
def test_segment_ends_from_the_boundary_mask(case):
    """_first_rows against a NumPy searchsorted over the monotone ids
    (what the kernel did per slot before PR 38), and the integer sum
    _sorted_reduce derives from it against a per-group loop."""
    valid, keys, out_cap = _SEGMENT_CASES[case]
    valid = np.asarray(valid, bool)
    n = len(valid)
    bnd, gid_m = _boundary_mask(valid, keys)
    first = np.asarray(hashagg._first_rows(
        jnp.asarray(bnd), jnp.asarray(gid_m), out_cap))
    slots = np.arange(out_cap + 1)
    assert first.tolist() == np.searchsorted(
        gid_m, slots, side="left").tolist()
    groups = int(bnd.sum())
    starts, ends = first[:-1], first[1:]
    assert (ends >= starts).all()
    for g in range(out_cap):
        if g < groups:
            assert bnd[starts[g]] and gid_m[starts[g]] == g
        else:
            assert starts[g] == ends[g] == n
    # an integer contribution, identity on every dead row, near the
    # wrap-around of int64: the prefix-sum difference is exact
    big = np.iinfo(np.int64).max // 3
    contrib = np.where(valid, big + np.arange(n), 0).astype(np.int64)
    gid = np.clip(gid_m, 0, out_cap)
    got = np.asarray(hashagg._sorted_reduce(
        jnp.asarray(contrib), jnp.asarray(gid), jnp.asarray(first),
        out_cap, "sum"))
    with np.errstate(over="ignore"):
        want = [contrib[gid == g].sum(dtype=np.int64) if g < groups
                else 0 for g in range(out_cap)]
    assert got.tolist() == [int(w) for w in want]


#: density -> share of flagged lanes (none and all exactly)
_FLAG_DENSITIES = {"none": 0.0, "sparse": 0.01, "half": 0.5, "all": 1.0}
_FLAG_FORMS = {"search": common._first_true_search,
               "scatter": common._first_true_scatter}


@pytest.mark.parametrize("form", sorted(_FLAG_FORMS))
@pytest.mark.parametrize("fill", ["zero", "last_lane"])
@pytest.mark.parametrize("density", sorted(_FLAG_DENSITIES))
@pytest.mark.parametrize("n", [1024, 65536, 262144])
def test_first_true_indices_forms_equal_nonzero(n, density, fill, form):
    """Both forms of first_true_indices, and the one the rule picks,
    against jnp.nonzero(flags, size=, fill_value=) bit for bit, with
    `size` below, at and above the number of flagged lanes."""
    rng = np.random.default_rng(n + len(density))
    share = _FLAG_DENSITIES[density]
    flags = rng.random(n) < share if 0 < share < 1 \
        else np.full(n, share == 1.0)
    k = int(flags.sum())
    fill_value = 0 if fill == "zero" else n - 1
    flags = jnp.asarray(flags)
    # nonzero at a smaller size is the prefix of nonzero at size n
    every = np.asarray(jnp.nonzero(flags, size=n,
                                   fill_value=fill_value)[0])
    for size in sorted({max(k // 2, 1), max(k, 1), min(2 * k + 16, n)}):
        @jax.jit
        def both(f):
            return (_FLAG_FORMS[form](f, size, fill_value),
                    common.first_true_indices(f, size, fill_value))
        got, picked = both(flags)
        assert got.dtype == jnp.int32 and got.shape == (size,)
        assert np.asarray(got).tolist() == every[:size].tolist(), \
            (size, k)
        assert np.asarray(picked).tolist() == every[:size].tolist()


@pytest.mark.parametrize("n, size, form", [
    (1 << 20, 1 << 16, "scatter"),   # Q3's lineitem-orders back
    (1 << 18, 1 << 14, "scatter"),   # its split end's, Q5's supplier
    (1 << 20, 1 << 18, "scatter"),   # Q5's orders back, 1M rung
    (1 << 22, 1 << 18, "scatter"),   # and 4M rung
    (1 << 18, 1 << 13, "search"),    # a narrow pack of 262,144 lanes
    (1 << 20, 1 << 12, "search"),    # a shrink to the ladder's floor
    (1 << 20, DF_SET_MAX, "search"),  # a dynamic filter's distinct set
])
def test_flag_pack_form_at_the_cells_shapes(n, size, form):
    """The rule picks from the two shapes alone: the scatter where a
    search of every output slot would gather more lanes than the
    scatter writes."""
    assert common.flag_pack_form(n, size) == form


def _run_core(core, valid, keys, inputs, weights, aggs, out_cap):
    """One batch through the presorted core (rows in key order) or the
    hash-sorted one; -> {key: tuple of finalized values}, overflow."""
    valid = jnp.asarray(np.asarray(valid, bool))
    kcol = (jnp.asarray(np.asarray(keys, np.int64)),
            jnp.ones(len(keys), bool))
    fn = hashagg.presorted_aggregate if core == "presorted" \
        else hashagg.batch_aggregate
    st = fn(valid, [kcol],
            [None if x is None else jnp.asarray(x) for x in inputs],
            [valid & jnp.asarray(np.asarray(w, bool)) for w in weights],
            aggs, out_cap)
    out = hashagg.finalize(st, ["k"], [BIGINT], [None],
                           [f"a{i}" for i in range(len(aggs))], aggs)
    live = np.asarray(out.row_valid)
    cols = [np.asarray(out.columns[n].data)[live]
            for n in out.columns]
    return ({int(k): tuple(c[i] for c in cols[1:])
             for i, k in enumerate(cols[0])},
            bool(np.asarray(st.overflow)))


@pytest.mark.parametrize("core", ["presorted", "hash_sorted"])
def test_int64_sums_near_wrap_around(core):
    """BIGINT sums (and the DOUBLE sum's int64 count) come from one
    prefix sum over the whole batch: it wraps, the differences do not
    notice."""
    top = np.iinfo(np.int64).max
    valid = [0, 1, 1, 1, 0, 1, 1, 1, 1, 0]
    keys = [7, 1, 1, 2, 2, 2, 3, 3, 3, 3]
    v = np.asarray([5, top - 1, -3, top, 9, top, -top, 4, top, 8],
                   np.int64)
    aggs = [hashagg.make_sum(BIGINT, BIGINT), hashagg.make_count(None)]
    got, overflow = _run_core(core, valid, keys, [v, None],
                              [[1] * 10, [1] * 10], aggs, 16)
    with np.errstate(over="ignore"):
        want = {1: (v[1] + v[2], 2), 2: (v[3] + v[5], 2),
                3: (v[6] + v[7] + v[8], 3)}
    assert got == {k: (int(s), c) for k, (s, c) in want.items()}
    assert not overflow


@pytest.mark.parametrize("core", ["presorted", "hash_sorted"])
def test_nan_stays_in_its_group(core):
    """A DOUBLE sum keeps segment_sum: a NaN (or an infinity) in one
    group reaches no other group's total."""
    valid = [1] * 8
    keys = [1, 1, 2, 2, 2, 3, 3, 4]
    x = np.asarray([1.5, 2.5, 1.0, np.nan, 2.0, np.inf, 1.0, 0.25])
    aggs = [hashagg.make_sum(DOUBLE, DOUBLE), hashagg.make_count(None)]
    got, _ = _run_core(core, valid, keys, [x, None],
                       [[1] * 8, [1] * 8], aggs, 8)
    assert got[1] == (4.0, 2) and got[4] == (0.25, 1)
    assert np.isnan(got[2][0]) and got[2][1] == 3
    assert got[3] == (np.inf, 2)


@pytest.mark.parametrize("core", ["presorted", "hash_sorted"])
def test_groups_past_out_cap_set_the_flag_and_spare_the_kept(core):
    valid = [0, 1, 1, 1, 0, 1, 1, 1, 1, 1]
    keys = [0, 1, 1, 2, 2, 3, 3, 4, 5, 5]
    v = np.arange(10, dtype=np.int64) * 10
    aggs = [hashagg.make_sum(BIGINT, BIGINT)]
    got, overflow = _run_core(core, valid, keys, [v], [[1] * 10],
                              aggs, 3)
    want = {1: 30, 2: 30, 3: 110, 4: 70, 5: 170}
    assert overflow and len(got) == 3
    assert all(got[k] == (want[k],) for k in got)
    if core == "presorted":            # packed in key order
        assert sorted(got) == [1, 2, 3]


def test_inner_join_vs_pandas():
    rng = np.random.default_rng(1)
    bn, pn = 200, 300
    bkeys = rng.integers(0, 50, bn)
    pkeys = rng.integers(0, 60, pn)
    bb = Batch.from_pydict({"k": (bkeys.tolist(), BIGINT),
                            "bv": (list(range(bn)), BIGINT)})
    pb = Batch.from_pydict({"k": (pkeys.tolist(), BIGINT),
                            "pv": (list(range(pn)), BIGINT)})
    table = join.build(bb, ("k",))
    lo, hi, counts, pkv = join.probe_counts(table, pb, ("k",))
    total = int(np.asarray(counts).sum())
    cap = bucket_capacity(total)
    out = join.expand(table, pb, ("k",), lo, hi, counts, pkv, cap,
                      "inner", probe_prefix="p_", build_prefix="b_",
                      probe_output=["k", "pv"], build_output=["bv"])
    exp = pd.merge(pd.DataFrame({"k": pkeys, "pv": range(pn)}),
                   pd.DataFrame({"k": bkeys, "bv": range(bn)}), on="k")
    got = out.to_pandas()
    assert len(got) == len(exp)
    assert sorted(zip(got["p_k"], got["p_pv"], got["b_bv"])) == \
        sorted(zip(exp["k"], exp["pv"], exp["bv"]))


def test_left_join_with_nulls():
    bb = Batch.from_pydict({"k": ([1, 2, 2], BIGINT),
                            "bv": ([10, 20, 21], BIGINT)})
    pb = Batch.from_pydict({"k": ([1, 2, 3, None], BIGINT),
                            "pv": ([100, 200, 300, 400], BIGINT)})
    table = join.build(bb, ("k",))
    lo, hi, counts, pkv = join.probe_counts(table, pb, ("k",))
    out = join.expand(table, pb, ("k",), lo, hi, counts, pkv, 16,
                      "left", probe_output=["pv"], build_output=["bv"],
                      build_prefix="b_")
    assert rows_of(out) == [(100, 10), (200, 20), (200, 21),
                            (300, None), (400, None)]


def test_semi_join():
    bb = Batch.from_pydict({"k": ([2, 3, 3, 5], BIGINT)})
    pb = Batch.from_pydict({"k": ([1, 2, 3, 5, None], BIGINT)})
    table = join.build(bb, ("k",))
    found, valid = join.semi_mark(table, pb, ("k",))
    f = np.asarray(found)[:5].tolist()
    assert f == [False, True, True, True, False]


_M64 = 1 << 64
_C1 = 0xBF58476D1CE4E5B9
_C2 = 0x94D049BB133111EB


def _hash64_py(v: int) -> int:
    """Pure-python mirror of common.hash64 (uint64 logical shifts)."""
    x = v % _M64
    x = (x ^ (x >> 30)) * _C1 % _M64
    x = (x ^ (x >> 27)) * _C2 % _M64
    return x ^ (x >> 31)


def _hash64_inv(h: int) -> int:
    """hash64 is a BIJECTION since the uint64 fix (logical xorshifts
    invert exactly; the multiplies are odd -> invertible mod 2^64).
    This walks it backwards."""
    def unshift(y, k):
        x = y
        for _ in range(0, 64, k):
            x = y ^ (x >> k)
        return x % _M64
    x = unshift(h % _M64, 31)
    x = x * pow(_C2, -1, _M64) % _M64
    x = unshift(x, 27)
    x = x * pow(_C1, -1, _M64) % _M64
    return unshift(x, 30)


def _row_hash_collisions(n: int):
    """Engineer n distinct TWO-COLUMN rows sharing one row_hash.
    row_hash(a, b) = hash64(a) * 31 + hash64(b) (mod 2^64); hash64 is
    now bijective (no single-column collisions exist at all), so we
    fix a target T, pick distinct a_i, and solve b_i =
    hash64^-1(T - 31 * hash64(a_i))."""
    T = 0xDEAD_BEEF_CAFE_F00D
    rows = []
    for i in range(n):
        a = i + 1
        hb = (T - 31 * _hash64_py(a)) % _M64
        b = _hash64_inv(hb)
        rows.append((a, b - _M64 if b >= 1 << 63 else b))
    return rows


def test_semi_join_exact_under_hash_collisions():
    """Adversarial: >4 distinct (two-column) build keys sharing ONE
    64-bit row hash, plus a colliding key pair NOT in the build. The
    old MAX_RUN=4 fallback marked any row of a long run as a member by
    hash equality alone — a silent wrong IN/NOT IN answer. semi_mark
    must be exact for every run length."""
    from presto_tpu.ops import common
    import jax.numpy as jnp

    rows = _row_hash_collisions(5)
    ones = jnp.ones(len(rows), bool)
    hs = np.asarray(common.row_hash(
        [(jnp.asarray([a for a, _ in rows], jnp.int64), ones),
         (jnp.asarray([b for _, b in rows], jnp.int64), ones)]))
    assert len(set(hs.tolist())) == 1, "engineered rows must collide"

    # duplicates stretch the hash run to 6 (> the unrolled prefix of
    # 4) while keeping a distinct colliding pair OUT of the build
    build = [rows[0], rows[0], rows[0], rows[1], rows[1], rows[2]]
    outsider = rows[3]            # collides, but NOT a member
    member_deep = build[5]        # member sitting past offset 4
    bb = Batch.from_pydict({
        "a": ([a for a, _ in build], BIGINT),
        "b": ([b for _, b in build], BIGINT)})
    probe_rows = [outsider, member_deep, build[0], (42, 43)]
    pb = Batch.from_pydict({
        "a": ([a for a, _ in probe_rows], BIGINT),
        "b": ([b for _, b in probe_rows], BIGINT)})
    table = join.build(bb, ("a", "b"))
    found, valid = join.semi_mark(table, pb, ("a", "b"))
    f = np.asarray(found)[:4].tolist()
    assert f == [False, True, True, False]
    assert np.asarray(valid)[:4].tolist() == [True] * 4


def test_multi_key_join():
    bb = Batch.from_pydict({"a": ([1, 1, 2], BIGINT),
                            "b": ([1, 2, 1], BIGINT),
                            "v": ([11, 12, 21], BIGINT)})
    pb = Batch.from_pydict({"a": ([1, 2, 2], BIGINT),
                            "b": ([2, 1, 9], BIGINT)})
    table = join.build(bb, ("a", "b"))
    lo, hi, counts, pkv = join.probe_counts(table, pb, ("a", "b"))
    out = join.expand(table, pb, ("a", "b"), lo, hi, counts, pkv, 16,
                      "inner", probe_output=["a", "b"], build_output=["v"],
                      build_prefix="b_")
    assert rows_of(out) == [(1, 2, 12), (2, 1, 21)]


def test_sort_and_topn():
    b = Batch.from_pydict({
        "x": ([3, 1, None, 2, 1], BIGINT),
        "y": ([30.0, 10.0, 99.0, 20.0, 11.0], DOUBLE),
    })
    s = sort.sort_batch(b, ("x", "y"), (False, True), (False, False))
    assert s.to_pylist()[:5] == [
        (1, 11.0), (1, 10.0), (2, 20.0), (3, 30.0), (None, 99.0)]
    # TopN: 2 smallest x (nulls last)
    state = sort.distinct_state(
        [("x", BIGINT, None), ("y", DOUBLE, None)], 16)
    st = sort.topn_step(state, b, 2, ("x",), (False,), (False,))
    got = st.to_pylist()
    assert sorted(got) == [(1, 10.0), (1, 11.0)]


def test_limit():
    import jax.numpy as jnp
    b = Batch.from_pydict({"x": (list(range(10)), BIGINT)})
    out = sort.limit_batch(b, 4, jnp.asarray(2))
    assert out.to_pydict()["x"] == [0, 1]


def test_distinct():
    b = Batch.from_pydict({"x": ([1, 2, 1, None, None, 3], BIGINT)})
    state = sort.distinct_state([("x", BIGINT, None)], 16)
    st = sort.distinct_step(state, b)
    b2 = Batch.from_pydict({"x": ([3, 4, 1], BIGINT)})
    st = sort.distinct_step(st, b2)
    assert rows_of(st) == [(1,), (2,), (3,), (4,), (None,)]


def test_distinct_duplicates_beyond_capacity():
    # regression: duplicate runs must not push later groups past cap
    b = Batch.from_pydict({"x": ([1] * 20 + [2, 3, 4], BIGINT)})
    state = sort.distinct_state([("x", BIGINT, None)], 16)
    st = sort.distinct_step(state, b)
    assert rows_of(st) == [(1,), (2,), (3,), (4,)]
