"""Grouped aggregation kernel (reference: HashAggregationOperator.java:47
+ InMemoryHashAggregationBuilder + MultiChannelGroupByHash.java:54).

TPU-native design: instead of an open-addressing hash table (random
scatter is hostile to the VPU), grouping is *sort-based*: rows are
lex-sorted by key, group boundaries detected by adjacent comparison, and
states reduced with `jax.ops.segment_*` over sorted segment ids — all
static shapes, all fusible.

Cross-batch accumulation keeps a running state batch of at most
`max_groups` rows (keys + partial states). Each step re-groups
[state ++ new-batch] in one jitted call, so the accumulator is a
functional fold: state' = agg_step(state, batch). The same kernel
implements partial and final aggregation (final consumes partial states
as its input contributions), which is what makes the
partial -> shuffle -> final plan shape work unchanged.

Overflow: if distinct groups exceed max_groups the overflow flag
accumulates ON DEVICE and surfaces as GroupLimitExceeded when the
operator drains (AggregationOperator.get_output) — no per-batch host
sync. The retry is QUERY-level: LocalRunner._run_plan catches
GroupLimitExceeded and re-executes with a larger max_groups (the analog
of MultiChannelGroupByHash rehash :87). Any OTHER driver of
AggregationOperator (e.g. a distributed stage runner) must handle
GroupLimitExceeded itself or pre-size max_groups.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.batch import Batch, Column, bucket_capacity
from presto_tpu.ops import common
from presto_tpu.types import BIGINT, DOUBLE, Type

CVal = Tuple[jnp.ndarray, jnp.ndarray]


@dataclasses.dataclass(frozen=True)
class AggFunction:
    """One aggregate: state layout + per-row contribution + merge + final.

    state arrays are parallel to group slots. `init(value, weight)` maps a
    row's input (already masked) to state contributions; contributions and
    existing states merge with segment reductions described by `reduce`
    (one of sum/min/max per state array).

    A state component may be a VECTOR per group: declare it as
    (np.dtype, K) in `state_dtypes` and have init() return [rows, K]
    contributions (e.g. approx_percentile's bucket histogram). Vector
    components flow through the sort path (2-D segment reductions) and
    the direct path (one-hot matmul), but are not exposed as
    intermediate columns — the planner keeps such aggregations on a
    SINGLE step with co-located groups.
    """

    name: str
    state_dtypes: Tuple  # np.dtype | (np.dtype, K) per component
    reduces: Tuple[str, ...]  # per state array: "sum" | "min" | "max"
    # (value_data, contribute_weight_bool) -> tuple of state arrays
    init: Callable[[Optional[jnp.ndarray], jnp.ndarray], Tuple[jnp.ndarray, ...]]
    # tuple of state arrays -> (data, mask)
    final: Callable[[Tuple[jnp.ndarray, ...]], CVal]
    output_type: Type = BIGINT
    # partial-output: state arrays exposed as columns for shuffle
    intermediate_types: Tuple[Type, ...] = ()


def _comp_spec(comp) -> Tuple[np.dtype, Tuple[int, ...]]:
    """state_dtypes entry -> (dtype, extra per-group shape)."""
    if isinstance(comp, tuple):
        return np.dtype(comp[0]), (int(comp[1]),)
    return np.dtype(comp), ()


def _ident_for(reduce: str, comp) -> jnp.ndarray:
    dtype, _ = _comp_spec(comp)
    if reduce == "sum":
        return jnp.zeros((), dtype)
    info = jnp.iinfo(dtype) if jnp.issubdtype(dtype, jnp.integer) \
        else jnp.finfo(dtype)
    return jnp.asarray(info.max if reduce == "min" else info.min, dtype)


@functools.lru_cache(maxsize=None)
def make_sum(input_type: Type, output_type: Type) -> AggFunction:
    dt = output_type.np_dtype

    def init(value, w):
        v = jnp.where(w, value, 0).astype(dt)
        return (v, w.astype(np.int64))

    def final(state):
        total, cnt = state
        return total, cnt > 0  # SUM of empty/all-null group is NULL
    return AggFunction("sum", (dt, np.dtype(np.int64)), ("sum", "sum"),
                       init, final, output_type,
                       (output_type, BIGINT))


@functools.lru_cache(maxsize=None)
def make_count(input_type: Optional[Type]) -> AggFunction:
    def init(value, w):
        return (w.astype(np.int64),)

    def final(state):
        return state[0], jnp.ones_like(state[0], bool)
    return AggFunction("count", (np.dtype(np.int64),), ("sum",),
                       init, final, BIGINT, (BIGINT,))


@functools.lru_cache(maxsize=None)
def make_avg(input_type: Type) -> AggFunction:
    # avg computes in float64 (Presto: avg(decimal) keeps decimal — we
    # finalize back to the decimal scale in the operator's projection).
    def init(value, w):
        v = jnp.where(w, value, 0).astype(np.float64)
        return (v, w.astype(np.int64))

    def final(state):
        total, cnt = state
        return total / jnp.maximum(cnt, 1), cnt > 0
    return AggFunction("avg", (np.dtype(np.float64), np.dtype(np.int64)),
                       ("sum", "sum"), init, final, DOUBLE,
                       (DOUBLE, BIGINT))


@functools.lru_cache(maxsize=None)
def make_min(input_type: Type) -> AggFunction:
    dt = input_type.np_dtype
    ident = _ident_for("min", dt)

    def init(value, w):
        return (jnp.where(w, value, ident).astype(dt), w.astype(np.int64))

    def final(state):
        return state[0], state[1] > 0
    return AggFunction("min", (dt, np.dtype(np.int64)), ("min", "sum"),
                       init, final, input_type, (input_type, BIGINT))


@functools.lru_cache(maxsize=None)
def make_max(input_type: Type) -> AggFunction:
    dt = input_type.np_dtype
    ident = _ident_for("max", dt)

    def init(value, w):
        return (jnp.where(w, value, ident).astype(dt), w.astype(np.int64))

    def final(state):
        return state[0], state[1] > 0
    return AggFunction("max", (dt, np.dtype(np.int64)), ("max", "sum"),
                       init, final, input_type, (input_type, BIGINT))


@functools.lru_cache(maxsize=None)
def make_variance(kind: str) -> AggFunction:
    """var_samp/var_pop/stddev/stddev_pop via the mergeable
    (n, sum, sum of squares) state (reference:
    operator/aggregation/VarianceAggregation + CentralMomentsState —
    we use the sum-of-squares form: states stay sum-mergeable across
    partial/final without Welford's order dependence)."""
    pop = kind.endswith("_pop")
    sqrt = kind.startswith("stddev")

    def init(value, w):
        v = jnp.where(w, value, 0).astype(np.float64)
        return (w.astype(np.int64), v, v * v)

    def final(state):
        n, s, ss = state
        nf = jnp.maximum(n, 1).astype(np.float64)
        m2 = ss - (s * s) / nf
        denom = nf if pop else jnp.maximum(nf - 1, 1)
        v = jnp.maximum(m2, 0.0) / denom
        if sqrt:
            v = jnp.sqrt(v)
        mask = (n > 0) if pop else (n > 1)
        return v, mask
    return AggFunction(kind, (np.dtype(np.int64), np.dtype(np.float64),
                              np.dtype(np.float64)),
                       ("sum", "sum", "sum"), init, final, DOUBLE,
                       (BIGINT, DOUBLE, DOUBLE))


@functools.lru_cache(maxsize=None)
def make_count_if() -> AggFunction:
    def init(value, w):
        return ((w & value.astype(bool)).astype(np.int64),)

    def final(state):
        return state[0], jnp.ones_like(state[0], bool)
    return AggFunction("count_if", (np.dtype(np.int64),), ("sum",),
                       init, final, BIGINT, (BIGINT,))


@functools.lru_cache(maxsize=None)
def make_bool_and(is_or: bool) -> AggFunction:
    def init(value, w):
        b = value.astype(bool)
        if is_or:
            v = (w & b).astype(np.int64)
        else:
            v = jnp.where(w, b, True).astype(np.int64)
        return (v, w.astype(np.int64))

    def final(state):
        v, cnt = state
        return v > 0, cnt > 0  # empty/all-null group -> NULL
    from presto_tpu.types import BOOLEAN
    return AggFunction("bool_or" if is_or else "bool_and",
                       (np.dtype(np.int64), np.dtype(np.int64)),
                       ("max" if is_or else "min", "sum"),
                       init, final, BOOLEAN, (BOOLEAN, BIGINT))


@functools.lru_cache(maxsize=None)
def make_geometric_mean() -> AggFunction:
    def init(value, w):
        v = jnp.where(w, value, 1).astype(np.float64)
        return (jnp.log(v), w.astype(np.int64))

    def final(state):
        slog, cnt = state
        return jnp.exp(slog / jnp.maximum(cnt, 1)), cnt > 0
    return AggFunction("geometric_mean",
                       (np.dtype(np.float64), np.dtype(np.int64)),
                       ("sum", "sum"), init, final, DOUBLE,
                       (DOUBLE, BIGINT))


@functools.lru_cache(maxsize=None)
def make_checksum(input_type: Type) -> AggFunction:
    """Order-independent content hash (reference:
    aggregation/ChecksumAggregationFunction — XOR of row hashes; we sum
    wrapping int64, equally order-independent). Deviation from the
    reference: NULL arguments contribute nothing (the operator's
    contribute-weight protocol cannot distinguish a NULL value in the
    group from a row outside it), so checksum([1]) == checksum([1,
    NULL]); pair with count(*) when null-sensitivity matters."""
    def init(value, w):
        h = common.hash64(value, w)
        return (jnp.where(w, h, 0),)

    def final(state):
        return state[0], jnp.ones_like(state[0], bool)
    return AggFunction("checksum", (np.dtype(np.int64),), ("sum",),
                       init, final, BIGINT, (BIGINT,))


#: approx_percentile sketch geometry: log-spaced buckets with
#: per-bucket relative error (GAMMA-1)/(GAMMA+1) ~ 2.9% (the DDSketch
#: construction; reference: operator/aggregation/
#: ApproximateDoublePercentileAggregations' qdigest plays this role).
#: Layout: [0, HALF-2] negatives (most negative first), HALF-1 zero,
#: [HALF, K-1] positives. Magnitudes cover GAMMA^-(HALF/2) ..
#: GAMMA^(HALF/2) ~ 3e-6 .. 3e6; values outside clamp to the end
#: buckets.
PCTL_BUCKETS = 1024
_PCTL_GAMMA = 1.06
_PCTL_HALF = PCTL_BUCKETS // 2
_PCTL_EXP0 = _PCTL_HALF // 2  # exponent offset: magnitudes cover
#                               gamma^-256..gamma^+254 ~ 3e-7..2.7e6


def _pctl_bucket(value: jnp.ndarray) -> jnp.ndarray:
    lng = float(np.log(_PCTL_GAMMA))
    mag = jnp.abs(value.astype(jnp.float64))
    tiny = mag < 1e-12
    li = jnp.clip(jnp.round(jnp.log(jnp.maximum(mag, 1e-12)) / lng)
                  .astype(jnp.int32) + _PCTL_EXP0, 0, _PCTL_HALF - 2)
    pos = _PCTL_HALF + li
    neg = _PCTL_HALF - 2 - li
    b = jnp.where(value >= 0, pos, neg)
    return jnp.where(tiny, _PCTL_HALF - 1, b).astype(jnp.int32)


def _pctl_values() -> np.ndarray:
    """Representative value per bucket (geometric midpoint)."""
    # round()-based bucket indexing covers gamma^(i-1/2)..gamma^(i+1/2)
    # per bucket, whose geometric midpoint is gamma^i itself (no
    # DDSketch 2g/(g+1) factor — that is for ceil-based indexing)
    li = np.arange(_PCTL_HALF - 1)          # exponent slots
    mags = _PCTL_GAMMA ** (li.astype(np.float64) - _PCTL_EXP0)
    out = np.zeros(PCTL_BUCKETS)
    # positives [HALF, 2*HALF-2] ascending; zero at HALF-1;
    # negatives [0, HALF-2] with the most negative first
    out[_PCTL_HALF:2 * _PCTL_HALF - 1] = mags
    out[_PCTL_HALF - 2::-1] = -mags
    return out


@functools.lru_cache(maxsize=None)
def make_approx_percentile(fraction: float) -> AggFunction:
    """Mergeable log-histogram percentile sketch. State: one int32
    count vector of PCTL_BUCKETS per group. The per-row contribution
    is a one-hot bucket row — XLA reduces it without a scatter (sorted
    path: 2-D segment sum; direct path: one-hot matmul on the MXU)."""
    K = PCTL_BUCKETS

    def init(value, w):
        b = _pctl_bucket(value)
        oh = (b[:, None] == jnp.arange(K, dtype=jnp.int32)[None, :])
        return ((oh & w[:, None]).astype(np.int32),)

    def final(state):
        counts = state[0].astype(jnp.float64)   # [G, K]
        total = counts.sum(axis=1)
        cdf = jnp.cumsum(counts, axis=1)
        target = jnp.ceil(fraction * total)
        target = jnp.maximum(target, 1.0)
        # first bucket where cdf >= target
        hit = cdf >= target[:, None]
        idx = jnp.argmax(hit, axis=1)
        vals = jnp.asarray(_pctl_values())[idx]
        return vals, total > 0
    return AggFunction(f"approx_percentile[{fraction}]",
                       ((np.int32, K),), ("sum",), init, final,
                       DOUBLE, ())


#: approx_distinct default standard error — matches the reference's
#: ApproximateCountDistinctAggregation.DEFAULT_STANDARD_ERROR.
HLL_DEFAULT_ERROR = 0.023
#: Presto's accepted range for the explicit error argument.
HLL_MIN_ERROR, HLL_MAX_ERROR = 0.0040625, 0.26
#: Tightest error this engine actually delivers (2^14 registers:
#: 1.04/sqrt(16384)); the analyzer REJECTS tighter requests instead of
#: silently clamping (advisor r4).
HLL_HONORED_MIN_ERROR = 1.04 / (1 << 7)  # = 1.04/sqrt(2^14) = 0.008125


def hll_registers_for_error(e: float) -> int:
    """Register count m (power of two) with 1.04/sqrt(m) <= e, capped
    at 2^14. Deviation from the reference: errors tighter than ~0.81%
    clamp to 16384 registers — the per-row one-hot contribution is
    [rows, m], and 2^16 registers (Presto's floor of 0.0040625) would
    put a multi-GB intermediate in every batch step."""
    m = 16
    while 1.04 / np.sqrt(m) > e and m < (1 << 14):
        m *= 2
    return m


@functools.lru_cache(maxsize=None)
def make_approx_distinct(input_type: Type,
                         max_error: float = HLL_DEFAULT_ERROR
                         ) -> AggFunction:
    """Dense HyperLogLog (reference: operator/aggregation/
    ApproximateCountDistinctAggregation + HyperLogLog's dense mode).

    State: one int8 register vector of m slots per group, merged with
    elementwise MAX — it rides the same vector-state machinery as
    approx_percentile's histogram ((dtype, K) component). Per row: the
    low log2(m) hash bits pick the register, the leading-zero count of
    the remaining bits (+1) is the candidate value, emitted as a
    masked one-hot row. Registers use 0 = "empty"; rho <= 54 fits int8.
    Memory is O(groups x m) regardless of input cardinality — the
    whole point vs the exact-DISTINCT rewrite this replaces."""
    m = hll_registers_for_error(max_error)
    b = int(np.log2(m))

    def init(value, w):
        h = common.hash64(value, w).astype(jnp.uint64)
        reg = (h & jnp.uint64(m - 1)).astype(jnp.int32)
        wbits = h >> b  # top b bits now zero -> clz >= b
        rho = (jax.lax.clz(wbits).astype(jnp.int32) - (b - 1))
        oh = reg[:, None] == jnp.arange(m, dtype=jnp.int32)[None, :]
        contrib = jnp.where(oh & w[:, None], rho[:, None], 0)
        return (contrib.astype(np.int8),)

    def final(state):
        regs = jnp.maximum(state[0], 0).astype(jnp.float64)  # [G, m]
        est = (_HLL_ALPHA[b] * m * m
               / jnp.sum(jnp.exp2(-regs), axis=1))
        zeros = jnp.sum(state[0] <= 0, axis=1).astype(jnp.float64)
        # linear-counting correction for the small range
        small = m * jnp.log(m / jnp.maximum(zeros, 1.0))
        est = jnp.where((est <= 2.5 * m) & (zeros > 0), small, est)
        # empty group (all registers 0) -> 0, like the reference
        return jnp.round(est).astype(np.int64), \
            jnp.ones(est.shape[0], bool)
    return AggFunction(f"approx_distinct[{m}]", ((np.int8, m),),
                       ("max",), init, final, BIGINT, ())


#: alpha_m bias constant per b = log2(m) (Flajolet et al. 2007).
_HLL_ALPHA = {
    4: 0.673, 5: 0.697, 6: 0.709,
    **{bb: 0.7213 / (1 + 1.079 / (1 << bb)) for bb in range(7, 17)},
}


@functools.lru_cache(maxsize=None)
def make_moments(kind: str) -> AggFunction:
    """skewness / kurtosis via sum-mergeable raw moments
    (n, s1, s2, s3, s4) — reference:
    operator/aggregation/CentralMomentsAggregation (Presto returns
    sample skewness and EXCESS sample kurtosis)."""
    def init(value, w):
        v = jnp.where(w, value, 0).astype(np.float64)
        return (w.astype(np.int64), v, v * v, v ** 3, v ** 4)

    def final(state):
        n_i, s1, s2, s3, s4 = state
        n = jnp.maximum(n_i, 1).astype(np.float64)
        m = s1 / n
        m2 = s2 / n - m * m                       # population variance
        m3 = s3 / n - 3 * m * s2 / n + 2 * m ** 3
        m4 = s4 / n - 4 * m * s3 / n + 6 * m * m * s2 / n - 3 * m ** 4
        if kind == "skewness":
            # Presto CentralMomentsAggregation: g1 = m3 / m2^1.5,
            # UNcorrected (kurtosis below IS sample-corrected)
            denom = jnp.maximum(m2, 1e-300) ** 1.5
            v = m3 / denom
            mask = n_i > 2
        else:  # kurtosis (excess, sample-corrected)
            denom = jnp.maximum(m2 * m2, 1e-300)
            g2 = m4 / denom - 3.0
            v = ((n - 1) / jnp.maximum((n - 2) * (n - 3), 1)
                 * ((n + 1) * g2 + 6))
            mask = n_i > 3
        return v, mask
    return AggFunction(kind, (np.dtype(np.int64),) + (np.dtype(
        np.float64),) * 4, ("sum",) * 5, init, final, DOUBLE,
        (BIGINT,) + (DOUBLE,) * 4)


@functools.lru_cache(maxsize=None)
def make_entropy() -> AggFunction:
    """entropy(c): Shannon entropy (log2) of the count distribution —
    states (sum_c, sum_c_log_c) are sum-mergeable (reference:
    aggregation/EntropyAggregation)."""
    def init(value, w):
        v = jnp.where(w, jnp.maximum(value, 0), 0).astype(np.float64)
        clogc = jnp.where(v > 0, v * jnp.log(v), 0.0)
        return (v, clogc)

    def final(state):
        total, sclogc = state
        t = jnp.maximum(total, 1e-300)
        ent = (jnp.log(t) - sclogc / t) / np.log(2.0)
        return jnp.maximum(ent, 0.0), total > 0
    return AggFunction("entropy", (np.dtype(np.float64),) * 2,
                       ("sum", "sum"), init, final, DOUBLE,
                       (DOUBLE, DOUBLE))


AGG_FACTORIES = {
    "sum": make_sum,
    "count": make_count,
    "avg": make_avg,
    "min": make_min,
    "max": make_max,
}


@dataclasses.dataclass
class GroupByState:
    """Running accumulator: key columns + per-agg state arrays, with
    `valid[g]` marking live group slots. A pytree (flows through jit)."""
    keys: List[CVal]
    states: List[Tuple[jnp.ndarray, ...]]
    valid: jnp.ndarray
    overflow: jnp.ndarray  # bool scalar


jax.tree_util.register_pytree_node(
    GroupByState,
    lambda s: ((s.keys, s.states, s.valid, s.overflow), None),
    lambda _, c: GroupByState(*c),
)


def _full_state(n: int, comp, reduce: str) -> jnp.ndarray:
    dtype, extra = _comp_spec(comp)
    return jnp.full((n,) + extra, _ident_for(reduce, comp), dtype)


def _gate(w: jnp.ndarray, contrib: jnp.ndarray, ident) -> jnp.ndarray:
    """where(w, contrib, ident) broadcast over vector components."""
    if contrib.ndim == 2:
        return jnp.where(w[:, None], contrib, ident)
    return jnp.where(w, contrib, ident)


def init_state(key_types: Sequence[Type], aggs: Sequence[AggFunction],
               max_groups: int) -> GroupByState:
    keys = [(jnp.zeros(max_groups, t.np_dtype), jnp.zeros(max_groups, bool))
            for t in key_types]
    states = []
    for a in aggs:
        states.append(tuple(
            _full_state(max_groups, dt, r)
            for dt, r in zip(a.state_dtypes, a.reduces)))
    return GroupByState(keys, states, jnp.zeros(max_groups, bool),
                        jnp.asarray(False))


def _first_rows(bnd: jnp.ndarray, gid_m: jnp.ndarray, out_cap: int
                ) -> jnp.ndarray:
    """first[g], g in 0..out_cap: the row where packed group g starts,
    n where the batch holds no such group (int32, non-decreasing).
    `gid_m` is `prefix_sum(bnd) - 1`, so it is unique over the boundary
    rows: ONE scatter of their indices by their own id, every other
    row dropped past the end. `out_cap + 1` entries, so that the last
    kept group has an end: group g is rows first[g] .. first[g + 1]
    (dead rows inside it and after the last group included; they carry
    the reduce identity). One form on every backend. On a v5e the
    scatter is 24 ms at 4M lanes (the sort of the indices XLA:TPU puts
    before it included; 5.7 at 1M) where a binary search per slot was
    691 ms, three times a presorted step; the sorted segment_min of
    the same indices is 38 ms (PERF.md, PR 38)."""
    n = bnd.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    at = jnp.where(bnd, gid_m, out_cap + 1).astype(jnp.int32)
    return jnp.full(out_cap + 1, n, jnp.int32).at[at].set(
        rows, mode="drop")


def _sorted_reduce(sarr: jnp.ndarray, gid: jnp.ndarray,
                   first: jnp.ndarray, out_cap: int,
                   reduce: str) -> jnp.ndarray:
    """Reduce a contribution array ALREADY SORTED by ascending group id
    into `out_cap` packed slots (dead rows carry the reduce identity,
    and gid == out_cap where they belong to no kept group).

    Integer sums are a prefix-sum difference at the segment ends
    `first` (_first_rows) in place of the scatter-lowered segment_sum,
    exact under wrapping arithmetic: below[g] is the running sum of
    every row before group g, a group's sum is below[g + 1] - below[g],
    and a slot with no group reads first[g] == first[g + 1] == n, so 0.
    Floats keep segment_sum: a cumsum-difference would leak one
    group's NaN into every later group's total, and re-associate the
    additions. min/max stay segment ops (sorted hint)."""
    if reduce == "sum" and sarr.ndim == 1 \
            and jnp.issubdtype(sarr.dtype, jnp.integer):
        cs = common.prefix_sum(sarr, sarr.dtype)
        below = jnp.where(first > 0, cs[jnp.maximum(first - 1, 0)],
                          jnp.zeros((), sarr.dtype))
        return below[1:] - below[:-1]
    if reduce == "sum":
        red = jax.ops.segment_sum(sarr, gid, num_segments=out_cap + 1,
                                  indices_are_sorted=True)
    elif reduce == "min":
        red = jax.ops.segment_min(sarr, gid, num_segments=out_cap + 1,
                                  indices_are_sorted=True)
    else:
        red = jax.ops.segment_max(sarr, gid, num_segments=out_cap + 1,
                                  indices_are_sorted=True)
    return red[:out_cap]


def _group_reduce(keys: Sequence[CVal], valid: jnp.ndarray,
                  contribs: Sequence[Tuple[jnp.ndarray, ...]],
                  aggs: Sequence[AggFunction],
                  out_cap: int) -> GroupByState:
    """The sort-based grouping core. RADIX grouping (the join
    kernel's trick applied to the sort fold): grouping needs equal
    keys ADJACENT, not a total key order, so ONE (h1, h2) hash sort
    replaces the (1 + 2k)-operand lexicographic sort — Q18's five-key
    1.5M-group aggregation sorts two int64 columns instead of eleven
    operands, and each hash run is a small bucket the boundary scan
    resolves with adjacent compares. Keys and 1-D contributions follow
    the permutation by gather, boundary detection assigns PACKED group
    ids and each contribution is segment-reduced into `out_cap` slots.
    Vector (2-D) contributions gather through the same permutation.

    Groups beyond out_cap are dropped and the overflow flag set (the
    caller's retry protocol). Output groups land packed in (h1, h2)
    hash order — callers must not rely on it (the final ORDER BY /
    merge regroups by key)."""
    if not keys:
        # global aggregation: ONE group, no sort at all — a straight
        # axis-0 reduction per state component. Contributions of
        # non-contributing rows are already the reduce identity (init/
        # _gate emit identity for w=False), and dead state slots hold
        # identity by construction, so reducing the whole array is
        # exact. This matters for vector states (HLL registers, pctl
        # histograms): the sort path would drag an [n, K] payload
        # through a variadic sort the compiler chews minutes on.
        slots = jnp.arange(out_cap)
        new_states = []
        for st, agg in zip(contribs, aggs):
            reduced = []
            for arr, r, comp in zip(st, agg.reduces, agg.state_dtypes):
                if r == "sum":
                    v = jnp.sum(arr, axis=0)
                elif r == "min":
                    v = jnp.min(arr, axis=0)
                else:
                    v = jnp.max(arr, axis=0)
                full = _full_state(out_cap, comp, r)
                reduced.append(full.at[0].set(v.astype(full.dtype)))
            new_states.append(tuple(reduced))
        return GroupByState([], new_states, slots == 0,
                            jnp.asarray(False))
    # Boundaries compare the actual keys as well as the hashes, so a
    # (h1, h2) double collision between distinct keys can only SPLIT a
    # group (handled by the next merge level), never merge two keys.
    h1 = jnp.where(valid, common.row_hash(keys),
                   jnp.iinfo(jnp.int64).max)
    h2 = common.row_hash2(keys)
    # 96 of the 128 hash bits order the rows (one key lane fewer for
    # the TPU compiler); the boundary compare below still reads all
    # 128 and the keys themselves
    perm = common.lex_perm([h1, (h2 >> 32).astype(jnp.int32)])
    skeys = [(d[perm], m[perm]) for d, m in keys]
    svalid = valid[perm]
    bnd = common.boundaries(skeys, svalid,
                            hashes=(h1[perm], h2[perm]))
    gid_m = common.prefix_sum(bnd) - 1
    num_groups = jnp.sum(bnd)
    # segment ends once for every state and the keys; the invalid rows
    # sort last, past the last group's end, and carry the identity
    first = _first_rows(bnd, gid_m, out_cap)
    # invalid rows -> overflow segment out_cap (sliced away)
    gid = jnp.where(svalid, jnp.minimum(gid_m, out_cap), out_cap)

    new_states: List[Tuple[jnp.ndarray, ...]] = []
    for st, agg in zip(contribs, aggs):
        new_states.append(tuple(
            _sorted_reduce(arr[perm], gid, first, out_cap, r)
            for arr, r in zip(st, agg.reduces)))

    # representative key row per packed group
    slots = jnp.arange(out_cap)
    first_row = jnp.clip(first[:out_cap], 0, gid.shape[0] - 1)
    new_valid = slots < num_groups
    new_keys = [(d[first_row], m[first_row] & new_valid)
                for d, m in skeys]
    return GroupByState(new_keys, new_states, new_valid,
                        num_groups > out_cap)


def _make_contribs(aggs, agg_inputs, agg_weights, merge):
    contribs: List[Tuple[jnp.ndarray, ...]] = []
    for agg, inp, w, is_merge in zip(aggs, agg_inputs, agg_weights,
                                     merge):
        if is_merge:
            # inp is a tuple of partial state arrays; weight gates
            # validity
            parts = tuple(
                _gate(w, p, _ident_for(r, dt)).astype(_comp_spec(dt)[0])
                for p, dt, r in zip(inp, agg.state_dtypes, agg.reduces))
            contribs.append(parts)
        else:
            contribs.append(agg.init(inp, w))
    return contribs


def agg_step(state: GroupByState,
             row_valid: jnp.ndarray,
             key_cols: Sequence[CVal],
             agg_inputs: Sequence[Optional[jnp.ndarray]],
             agg_weights: Sequence[jnp.ndarray],
             aggs: Sequence[AggFunction],
             merge: Sequence[bool] | None = None) -> GroupByState:
    """One functional fold step: regroup [state ++ batch rows].

    `row_valid` is the incoming batch's selection vector (live rows form
    groups even when every agg input is NULL). `agg_inputs[i]` is the
    evaluated input column (or None for count(*)), `agg_weights[i]` is the
    per-row contribute mask (row_valid & not-null). When `merge[i]` is
    True the i-th "input" is a tuple of partial state arrays to merge
    instead of raw values (final aggregation after a shuffle).

    NOTE: folding a LARGE state through every batch re-sorts it each
    step; the operator uses batch_aggregate + merge_partials instead
    (per-batch compaction, log-depth merges). agg_step remains the
    semantic reference and the path for small accumulators."""
    max_groups = state.valid.shape[0]
    merge = merge or [False] * len(aggs)
    contribs = _make_contribs(aggs, agg_inputs, agg_weights, merge)

    # concat state rows + input rows, then one grouped reduction
    all_keys = [
        (jnp.concatenate([sk[0], kc[0].astype(sk[0].dtype)]),
         jnp.concatenate([sk[1], kc[1]]))
        for sk, kc in zip(state.keys, key_cols)
    ]
    all_valid = jnp.concatenate([state.valid, row_valid])
    all_states = []
    for st, cb, agg in zip(state.states, contribs, aggs):
        all_states.append(tuple(
            jnp.concatenate([s, c.astype(s.dtype)])
            for s, c in zip(st, cb)))
    out = _group_reduce(all_keys, all_valid, all_states, aggs,
                        max_groups)
    return GroupByState(out.keys, out.states, out.valid,
                        state.overflow | out.overflow)


def batch_aggregate(row_valid: jnp.ndarray,
                    key_cols: Sequence[CVal],
                    agg_inputs: Sequence[Optional[jnp.ndarray]],
                    agg_weights: Sequence[jnp.ndarray],
                    aggs: Sequence[AggFunction],
                    out_cap: int,
                    merge: Sequence[bool] | None = None) -> GroupByState:
    """Compact ONE batch to its distinct groups (<= out_cap slots) —
    no running state in the hot loop. The operator buffers these
    per-batch partials and tree-merges them with merge_partials, so a
    million-group aggregation never re-sorts a million-row state per
    batch (the old fold's failure mode on Q3/Q18-class queries)."""
    merge = merge or [False] * len(aggs)
    contribs = _make_contribs(aggs, agg_inputs, agg_weights, merge)
    return _group_reduce(key_cols, row_valid, contribs, aggs, out_cap)


def presorted_aggregate(row_valid: jnp.ndarray,
                        key_cols: Sequence[CVal],
                        agg_inputs: Sequence[Optional[jnp.ndarray]],
                        agg_weights: Sequence[jnp.ndarray],
                        aggs: Sequence[AggFunction],
                        out_cap: int,
                        merge: Sequence[bool] | None = None
                        ) -> GroupByState:
    """Group ONE batch whose rows are ALREADY sorted by the group keys
    (ascending, nulls last) — the streaming-aggregation input contract
    (reference: operator/StreamingAggregationOperator.java). No sort at
    all: group boundaries come from comparing each valid row with the
    PREVIOUS VALID row (a cummax of valid row indices bridges filtered-
    out rows), group ids from a cumsum, and states from the same
    segment reductions as the sort path. This is the whole point of
    choosing the streaming operator — the generic path would re-sort
    data the connector already delivered in key order (measured ~25x
    slower per batch at 1M rows).

    Dead rows inherit the enclosing group's id: their contributions are
    the reduce identity by construction (init/_gate emit identity for
    w=False), so they perturb no state, and they never start a group.
    Output groups land packed in input (= key) order."""
    merge = merge or [False] * len(aggs)
    contribs = _make_contribs(aggs, agg_inputs, agg_weights, merge)
    return presorted_reduce(row_valid, key_cols, contribs, aggs,
                            out_cap)


def presorted_reduce(row_valid: jnp.ndarray,
                     key_cols: Sequence[CVal],
                     contribs: Sequence[Tuple[jnp.ndarray, ...]],
                     aggs: Sequence[AggFunction],
                     out_cap: int) -> GroupByState:
    """The sort-free grouping core over rows already in key order:
    contributions are state-shaped (post _make_contribs / existing
    partial states). Shared by presorted_aggregate and the CPU
    host-lexsort splits (operators sort on the host, then reduce
    here)."""
    if not key_cols:
        return _group_reduce([], row_valid, contribs, aggs, out_cap)
    n = row_valid.shape[0]
    idx = jnp.arange(n)
    # index of the last valid row at-or-before each row, then shifted:
    # prev[i] = last valid index STRICTLY before i (-1 if none)
    lastv = jax.lax.cummax(jnp.where(row_valid, idx, -1))
    prev = jnp.roll(lastv, 1).at[0].set(-1)
    pidx = jnp.maximum(prev, 0)
    differs = prev < 0  # the first valid row always starts a group
    for data, mask in key_cols:
        pd, pm = data[pidx], mask[pidx]
        d = (data != pd) | (mask != pm)
        # both-NULL rows group together (SQL GROUP BY semantics)
        differs = differs | (d & (mask | pm))
    bnd = row_valid & differs
    # monotone group ids; leading dead rows sit at -1, later dead rows
    # inherit the current group
    gid_m = common.prefix_sum(bnd) - 1
    num_groups = jnp.sum(bnd)
    gid = jnp.clip(gid_m, 0, out_cap)
    # segment ends once for every state and the keys: group 0 starts
    # at its first boundary, the leading dead rows before it carry the
    # identity
    first = _first_rows(bnd, gid_m, out_cap)
    new_states: List[Tuple[jnp.ndarray, ...]] = []
    for st, agg in zip(contribs, aggs):
        new_states.append(tuple(
            _sorted_reduce(arr, gid, first, out_cap, r)
            for arr, r in zip(st, agg.reduces)))
    slots = jnp.arange(out_cap)
    first_row = jnp.clip(first[:out_cap], 0, n - 1)
    new_valid = slots < num_groups
    new_keys = [(d[first_row], m[first_row] & new_valid)
                for d, m in key_cols]
    return GroupByState(new_keys, new_states, new_valid,
                        num_groups > out_cap)


def merge_partials(states: Sequence[GroupByState],
                   aggs: Sequence[AggFunction],
                   out_cap: int) -> GroupByState:
    """Regroup several compacted partial states into one (log-depth
    tree merge; the reference analog is merging InMemoryHashAggregation
    builders across spill generations). Output capacity `out_cap`;
    overflow flags OR through."""
    keys = [
        (jnp.concatenate([s.keys[i][0] for s in states]),
         jnp.concatenate([s.keys[i][1] for s in states]))
        for i in range(len(states[0].keys))
    ]
    valid = jnp.concatenate([s.valid for s in states])
    contribs = []
    for ai in range(len(aggs)):
        contribs.append(tuple(
            jnp.concatenate([s.states[ai][ci] for s in states])
            for ci in range(len(states[0].states[ai]))))
    out = _group_reduce(keys, valid, contribs, aggs, out_cap)
    ovf = out.overflow
    for s in states:
        ovf = ovf | s.overflow
    return GroupByState(out.keys, out.states, out.valid, ovf)


# ---------------------------------------------------------------------------
# Direct-indexing aggregation for small key domains (the analog of the
# reference's BigintGroupByHash specialization, operator/BigintGroupByHash
# — and of low-cardinality group-by optimizations generally). When every
# group key is dictionary-encoded or boolean, the combined code domain is
# known statically; the group id IS the table slot, so grouping needs no
# sort at all: one segment-reduce per state array over a fixed [G] table.
# This is the TPU-happy path: pure streaming VPU work, no argsort.


@dataclasses.dataclass
class DirectState:
    """Slot-indexed accumulator: slot = mixed-radix key code."""
    states: List[Tuple[jnp.ndarray, ...]]
    present: jnp.ndarray  # bool [G] — slot has seen a live row


jax.tree_util.register_pytree_node(
    DirectState,
    lambda s: ((s.states, s.present), None),
    lambda _, c: DirectState(*c),
)


def direct_init(aggs: Sequence[AggFunction], num_slots: int) -> DirectState:
    states = []
    for a in aggs:
        states.append(tuple(
            _full_state(num_slots, dt, r)
            for dt, r in zip(a.state_dtypes, a.reduces)))
    return DirectState(states, jnp.zeros(num_slots, bool))


# Below this slot count, reduce into the slot table with a masked
# one-hot reduction instead of segment_*: segment ops lower to scatter,
# which XLA serializes on TPU; the [rows, slots] masked reduce fuses
# into a single streaming VPU pass (the ratio between the two on the
# chip is not measured).
_ONEHOT_SLOT_LIMIT = 256


def _slot_reduce(contrib: jnp.ndarray, gid: jnp.ndarray, num_slots: int,
                 reduce: str, dtype) -> jnp.ndarray:
    """Reduce per-row contributions into `num_slots` slots (drop slot
    `num_slots` discarded). gid is int32 in [0, num_slots]. contrib may
    be [rows] or [rows, K] (vector state component).

    Platform fork (trace-time): the masked one-hot reduce streams on
    the TPU VPU where scatter serializes, but on XLA:CPU it multiplies
    memory traffic by `num_slots` while the scatter-lowered segment
    ops run a fast linear pass — Q1's 12-slot direct aggregation paid
    ~5s/6M rows through the one-hot form on CPU."""
    c = contrib.astype(dtype)
    # 2-D non-sum one-hot would materialize [rows, slots, K]; the
    # segment path below keeps it at [rows, K] (HLL's max-merge)
    if num_slots <= _ONEHOT_SLOT_LIMIT and not common.cpu_backend() \
            and (c.ndim == 1 or reduce == "sum"):
        oh = gid[:, None] == jnp.arange(num_slots, dtype=gid.dtype)[None, :]
        if c.ndim == 2:
            if reduce == "sum":
                # [slots, rows] x [rows, K] matmul — MXU-friendly;
                # per-batch counts stay exact in f32 (rows < 2^24)
                return jax.lax.dot_general(
                    oh.astype(jnp.float32).T, c.astype(jnp.float32),
                    (((1,), (0,)), ((), ()))).astype(dtype)
        masked = jnp.where(oh, c[:, None], _ident_for(reduce, dtype))
        if reduce == "sum":
            return jnp.sum(masked, axis=0)
        if reduce == "min":
            return jnp.min(masked, axis=0)
        return jnp.max(masked, axis=0)
    if reduce == "sum":
        red = jax.ops.segment_sum(c, gid, num_segments=num_slots + 1)
    elif reduce == "min":
        red = jax.ops.segment_min(c, gid, num_segments=num_slots + 1)
    else:
        red = jax.ops.segment_max(c, gid, num_segments=num_slots + 1)
    return red[:num_slots]


def direct_step(state: DirectState,
                row_valid: jnp.ndarray,
                key_codes: Sequence[CVal],
                domains: Tuple[int, ...],
                agg_inputs: Sequence,
                agg_weights: Sequence[jnp.ndarray],
                aggs: Sequence[AggFunction],
                merge: Sequence[bool] | None = None) -> DirectState:
    """Accumulate one batch into the slot table. NULL keys get their own
    slot (code == domain), mirroring SQL's NULL-is-a-group semantics."""
    merge = merge or [False] * len(aggs)
    num_slots = state.present.shape[0]
    gid = jnp.zeros(row_valid.shape[0], jnp.int32)
    for (code, mask), dom in zip(key_codes, domains):
        c = jnp.where(mask, code.astype(jnp.int32), dom)
        gid = gid * (dom + 1) + c
    gid = jnp.where(row_valid, gid, num_slots)  # dead rows -> drop slot

    new_states = []
    for agg, st, inp, w, is_merge in zip(aggs, state.states, agg_inputs,
                                         agg_weights, merge):
        if is_merge:
            contrib = tuple(
                _gate(w, p, _ident_for(r, dt)).astype(
                    _comp_spec(dt)[0])
                for p, dt, r in zip(inp, agg.state_dtypes, agg.reduces))
        else:
            contrib = agg.init(inp, w)
        merged = []
        for arr, c, r in zip(st, contrib, agg.reduces):
            red = _slot_reduce(c, gid, num_slots, r, arr.dtype)
            if r == "sum":
                merged.append(arr + red)
            elif r == "min":
                merged.append(jnp.minimum(arr, red))
            else:
                merged.append(jnp.maximum(arr, red))
        new_states.append(tuple(merged))

    seen = _slot_reduce(row_valid.astype(jnp.int32), gid, num_slots,
                        "max", jnp.int32)
    return DirectState(new_states, state.present | (seen > 0))


def _decode_slots(state: DirectState, key_names: Sequence[str],
                  key_types: Sequence[Type],
                  key_dicts: Sequence[Optional[tuple]],
                  domains: Tuple[int, ...]
                  ) -> Tuple[Dict[str, Column], jnp.ndarray]:
    """Key columns decoded from the slot index (mixed radix, most-
    significant key first) plus the output row_valid. A global
    aggregation (no keys) emits exactly one row even over zero input
    rows (count(*) = 0)."""
    num_slots = state.present.shape[0]
    slot = jnp.arange(num_slots)
    cols: Dict[str, Column] = {}
    stride = num_slots
    for name, typ, dic, dom in zip(key_names, key_types, key_dicts,
                                   domains):
        stride //= (dom + 1)
        code = (slot // stride) % (dom + 1)
        mask = (code < dom) & state.present
        cols[name] = Column(code.astype(typ.np_dtype), mask, typ, dic)
    rv = state.present if key_names else jnp.ones_like(state.present)
    return cols, rv


def _pad_to_bucket(cols: Dict[str, Column], rv: jnp.ndarray) -> Batch:
    """Pad a slot-table batch up to the power-of-two capacity bucket so
    downstream jitted kernels keep the small bucketed shape set."""
    cap = bucket_capacity(rv.shape[0])
    pad = cap - rv.shape[0]
    if pad:
        cols = {
            n: Column(jnp.pad(c.data, (0, pad)), jnp.pad(c.mask, (0, pad)),
                      c.type, c.dictionary)
            for n, c in cols.items()
        }
        rv = jnp.pad(rv, (0, pad))
    return Batch(cols, rv)


def direct_finalize(state: DirectState, key_names: Sequence[str],
                    key_types: Sequence[Type],
                    key_dicts: Sequence[Optional[tuple]],
                    domains: Tuple[int, ...],
                    out_names: Sequence[str],
                    aggs: Sequence[AggFunction]) -> Batch:
    """One output row per present slot."""
    cols, rv = _decode_slots(state, key_names, key_types, key_dicts,
                             domains)
    for name, agg, st in zip(out_names, aggs, state.states):
        d, m = agg.final(st)
        cols[name] = Column(d.astype(agg.output_type.np_dtype),
                            m & rv, agg.output_type, None)
    return _pad_to_bucket(cols, rv)


def direct_intermediate(state: DirectState, key_names: Sequence[str],
                        key_types: Sequence[Type],
                        key_dicts: Sequence[Optional[tuple]],
                        domains: Tuple[int, ...],
                        out_names: Sequence[str],
                        aggs: Sequence[AggFunction]) -> Batch:
    """Partial states as columns for the shuffle (keys decoded as in
    direct_finalize; state arrays exposed as <out>__s{i})."""
    cols, rv = _decode_slots(state, key_names, key_types, key_dicts,
                             domains)
    for name, agg, st in zip(out_names, aggs, state.states):
        for i, (arr, it) in enumerate(zip(st, agg.intermediate_types)):
            cols[f"{name}__s{i}"] = Column(arr.astype(it.np_dtype),
                                           rv, it, None)
    return _pad_to_bucket(cols, rv)


def finalize(state: GroupByState, key_names: Sequence[str],
             key_types: Sequence[Type],
             key_dicts: Sequence[Optional[tuple]],
             out_names: Sequence[str],
             aggs: Sequence[AggFunction]) -> Batch:
    """Produce the output batch of one group per row."""
    cols: Dict[str, Column] = {}
    for name, typ, dic, (d, m) in zip(key_names, key_types, key_dicts,
                                      state.keys):
        cols[name] = Column(d.astype(typ.np_dtype), m, typ, dic)
    for name, agg, st in zip(out_names, aggs, state.states):
        d, m = agg.final(st)
        cols[name] = Column(d.astype(agg.output_type.np_dtype),
                            m & state.valid, agg.output_type, None)
    return Batch(cols, state.valid)


def intermediate_batch(state: GroupByState, key_names: Sequence[str],
                       key_types: Sequence[Type],
                       key_dicts: Sequence[Optional[tuple]],
                       out_names: Sequence[str],
                       aggs: Sequence[AggFunction]) -> Batch:
    """Expose partial states as columns (<out>__s0, <out>__s1, ...) for
    the shuffle between partial and final aggregation (reference analog:
    the INTERMEDIATE step of AccumulatorCompiler accumulators)."""
    cols: Dict[str, Column] = {}
    for name, typ, dic, (d, m) in zip(key_names, key_types, key_dicts,
                                      state.keys):
        cols[name] = Column(d.astype(typ.np_dtype), m, typ, dic)
    for name, agg, st in zip(out_names, aggs, state.states):
        for i, (arr, it) in enumerate(zip(st, agg.intermediate_types)):
            cols[f"{name}__s{i}"] = Column(arr.astype(it.np_dtype),
                                           state.valid, it, None)
    return Batch(cols, state.valid)
