"""Shared kernel utilities: multi-key lexicographic ordering, row hashing,
and null-aware sort keys.

Replaces the reference's generated PagesHashStrategy / OrderingCompiler
(sql/gen/JoinCompiler.java:92, OrderingCompiler) with argsort-based
primitives that XLA maps onto the TPU's sort HLO.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

CVal = Tuple[jnp.ndarray, jnp.ndarray]


# ---------------------------------------------------------------------------
# Platform-specialized primitives. Kernels compile per backend, so a
# fork is decided at trace time and each backend sees only its side.
# What is known of the two sides: XLA:CPU's sort lowering runs
# ~600ns/element, variadic payloads multiply that, and searchsorted
# lowers to a per-slot scan loop, while cumsum/scatter/gather are fast
# there. On XLA:TPU sorts and whole-batch 1-D scans are the COMPILE
# wall and widths over 32 bits multiply it, a sort runs in
# milliseconds, and a gather or scatter of 1M rows costs tens of
# milliseconds (PERF.md, PR 22); most TPU sides have no run time on
# record yet. Tier-1 runs on the CPU, so tests/test_tpu_compile.py
# steers each fork to its TPU side and compiles it for a described
# v5e.
#
# NOTE on host callbacks: routing these through jax.pure_callback to
# numpy (np.argsort is ~4x XLA:CPU's sort) DEADLOCKS under the
# engine's driver — XLA:CPU services the callback while another
# thread is parked in a blocking device read (the deferred-count
# protocol), and the two waits are circular (observed live in round
# 5). Everything here must stay traceable; host sorts are only legal
# at the OPERATOR layer, between jitted kernels (ops/host.py).


def cpu_backend() -> bool:
    return jax.default_backend() == "cpu"


def fast_searchsorted(a: jnp.ndarray, v: jnp.ndarray,
                      side: str = "left") -> jnp.ndarray:
    """jnp.searchsorted on TPU; on CPU a hand-unrolled vectorized
    binary search (gather + compare per level) — XLA:CPU lowers
    jnp.searchsorted to a slow per-slot scan (~160ms per 1M queries
    into 262k slots; this runs the same search in ~half)."""
    if not cpu_backend():
        return jnp.searchsorted(a, v, side=side)
    import math
    n = a.shape[0]
    dt = jnp.int64
    lo = jnp.zeros(v.shape, dt)
    hi = jnp.full(v.shape, n, dt)
    for _ in range(int(math.ceil(math.log2(max(n, 2)))) + 1):
        # freeze converged lanes: an extra iteration at lo == hi == n
        # would compare against a[n-1] and push lo to n + 1
        active = lo < hi
        mid = (lo + hi) >> 1
        mv = a[jnp.clip(mid, 0, n - 1)]
        go_left = (mv >= v) if side == "left" else (mv > v)
        hi = jnp.where(active & go_left, mid, hi)
        lo = jnp.where(active & ~go_left, mid + 1, lo)
    return lo


def bounded_searchsorted(a: jnp.ndarray, v: jnp.ndarray,
                         lo: jnp.ndarray, hi: jnp.ndarray,
                         iters: int, side: str = "left") -> jnp.ndarray:
    """Vectorized binary search with PER-QUERY initial bounds
    [lo, hi) — the radix-partitioned probe's workhorse: each query
    searches only its hash partition, so `iters` is log2(max partition
    size) instead of log2(n). `iters` must cover the largest bound
    span or the result is undefined (the build chooses it from the
    measured max partition, see ops/join.py). Works identically on
    CPU and TPU: the level-by-level gather+compare form vectorizes on
    both, and the partition bounds make jnp.searchsorted's whole-table
    log depth unnecessary."""
    n = a.shape[0]
    lo = lo.astype(jnp.int64)
    hi = hi.astype(jnp.int64)
    for _ in range(iters):
        active = lo < hi
        mid = (lo + hi) >> 1
        mv = a[jnp.clip(mid, 0, n - 1)]
        go_left = (mv >= v) if side == "left" else (mv > v)
        hi = jnp.where(active & go_left, mid, hi)
        lo = jnp.where(active & ~go_left, mid + 1, lo)
    return lo


def search_iters(max_span: int) -> int:
    """Iterations bounded_searchsorted needs to converge over spans of
    at most `max_span` (mirrors fast_searchsorted's count)."""
    import math
    return int(math.ceil(math.log2(max(int(max_span), 2)))) + 1


#: block width of the two-level prefix sum: XLA:TPU lowers a 1-D
#: cumsum over a whole batch to a deep reduce-window tree that takes
#: the compiler tens of seconds (minutes for 64-bit); a scan along the
#: minor axis of a [n / 1024, 1024] view compiles in about a second.
_SCAN_BLOCK = 1024


def prefix_sum(x: jnp.ndarray, dtype=jnp.int32) -> jnp.ndarray:
    """Inclusive prefix sum of a 1-D integer or boolean array as a
    blocked two-level scan: scan inside blocks, scan the block totals,
    add. Exact — integer addition is associative, wrapping included —
    so it equals `jnp.cumsum(x, dtype=dtype)` bit for bit. Row counts,
    ranks, group ids and offsets fit int32 at any batch size (the
    default); pass int64 for running sums of 64-bit values. Floats
    must not come here: re-associating a float sum changes it."""
    assert not jnp.issubdtype(x.dtype, jnp.floating), x.dtype
    x = x.astype(dtype)
    n = x.shape[0]
    if n <= _SCAN_BLOCK:
        return jnp.cumsum(x)
    pad = -n % _SCAN_BLOCK
    inner = jnp.cumsum(
        jnp.pad(x, (0, pad)).reshape(-1, _SCAN_BLOCK), axis=1)
    totals = inner[:, -1]
    offsets = prefix_sum(totals, dtype) - totals
    return (inner + offsets[:, None]).reshape(-1)[:n]


def first_true_indices(flags: jnp.ndarray, size: int,
                       fill_value: int) -> jnp.ndarray:
    """Indices of the first `size` True entries, ascending, padded
    with `fill_value` — `jnp.nonzero(flags, size=, fill_value=)[0]`
    as a prefix sum plus a binary search for each output slot (the
    stock lowering scans and scatters over the whole input)."""
    rank = prefix_sum(flags)
    slots = jnp.arange(size, dtype=jnp.int32)
    idx = fast_searchsorted(rank, slots + 1, side="left")
    return jnp.where(slots < rank[-1], idx.astype(jnp.int32),
                     jnp.int32(fill_value))


def _narrow_sort_key(a: jnp.ndarray) -> List[jnp.ndarray]:
    """Order-preserving 32-bit operands for one sort key. The TPU has
    no 64-bit lanes: a 64-bit operand inside a sort comparator costs
    the compiler 2.5-3x and an emulated f64 8-15x (rehearsal compiles,
    CHANGES.md PR 22). An int64 splits into (signed high word,
    unsigned low word); an f64 goes through its totalOrder bit pattern
    (float64_order_key). (Booleans are packed by lex_perm.)"""
    if a.dtype == jnp.float64:
        a = float64_order_key(a)
    if a.dtype in (jnp.int64, jnp.uint64):
        flip = jnp.uint32(1 << 31)
        hi = (a >> 32).astype(jnp.uint32)
        if a.dtype == jnp.uint64:
            hi = hi ^ flip
        lo = a.astype(jnp.uint32) ^ flip
        return [jax.lax.bitcast_convert_type(hi, jnp.int32),
                jax.lax.bitcast_convert_type(lo, jnp.int32)]
    return [a]


def lex_perm(sort_ops: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Stable permutation (int32) ordering rows by `sort_ops`
    (most-significant first): ONE lax.sort over 32-bit operands that
    carries only an int32 iota. The iota is the last key, which makes
    the order total, so the sort itself need not be stable. Payloads
    then move by gather. The TPU compiler's time grows faster than
    linearly in the number of KEY lanes (15 / 28 / 51 / 75 s for 1-4
    int32 keys at 64k rows, rehearsal compiles in CHANGES.md PR 22),
    so runs of adjacent boolean operands (~valid, null rank) share
    one lane."""
    n = sort_ops[0].shape[0]
    ops: List[jnp.ndarray] = []
    flags = None  # packed run of adjacent bool operands
    for a in sort_ops:
        if a.dtype == jnp.bool_:
            b = a.astype(jnp.int32)
            flags = b if flags is None else flags * 2 + b
            continue
        if flags is not None:
            ops.append(flags)
            flags = None
        ops.extend(_narrow_sort_key(a))
    if flags is not None:
        ops.append(flags)
    ops.append(jnp.arange(n, dtype=jnp.int32))
    return jax.lax.sort(tuple(ops), num_keys=len(ops),
                        is_stable=False)[-1]


def stable_argsort(a: jnp.ndarray) -> jnp.ndarray:
    """Single-key stable argsort (traceable; see NOTE above)."""
    return lex_perm([a])


def partition_perm(valid: jnp.ndarray) -> jnp.ndarray:
    """Stable valid-rows-first permutation (int32): equivalent to
    argsort(~valid), built from two prefix sums + one scatter of
    unique, nearly sorted positions. On CPU the bool argsort costs
    ~600ms per 1M rows, this form ~5ms; on a v5e the sort form ran
    1.9 ms but took the compiler 13 s where this takes 6.0 ms and 1 s
    (my chip run, PR 22) — and the per-column gathers that follow
    either form cost 17 ms each there."""
    n = valid.shape[0]
    nv = jnp.sum(valid, dtype=jnp.int32)
    pos = jnp.where(valid, prefix_sum(valid) - 1,
                    nv + prefix_sum(~valid) - 1)
    return jnp.zeros(n, jnp.int32).at[pos].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)


_F64_EXP_STEPS = (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)


def float64_bits(x: jnp.ndarray) -> jnp.ndarray:
    """IEEE-754 binary64 bit pattern of `x` as int64, by ARITHMETIC.
    XLA:TPU refuses every bitcast *from* f64 (its X64 rewriter keeps a
    double as two f32 halves), so the pattern is rebuilt from exact
    power-of-two scalings: normalize |x| into [1, 2) while counting
    the exponent, read the 53-bit significand with a convert. Equal to
    `bitcast_convert_type(x, int64)` for every normal value, both
    zeros and both infinities; every NaN maps to the one canonical
    quiet pattern, and a subnormal reads as the backend's arithmetic
    reads it (XLA:CPU flushes it to a signed zero, exactly as its
    `==` does). This is the ONE f64 -> integer key
    function: hash64/hash64b, expr's $hash and the merge kernel's
    total order all call it, so both sides of any hash compare agree."""
    x = x.astype(jnp.float64)
    a = jnp.abs(x)
    e = jnp.zeros(x.shape, jnp.int32)
    for k in _F64_EXP_STEPS:                 # a >= 2: scale down
        big = a >= 2.0 ** k
        a = jnp.where(big, a * 2.0 ** -k, a)
        e = jnp.where(big, e + k, e)
    for k in (512,) + _F64_EXP_STEPS:        # a < 1: scale up
        small = a < 2.0 ** (1 - k)
        a = jnp.where(small, a * 2.0 ** k, a)
        e = jnp.where(small, e - k, e)
    mant = (a * 2.0 ** 52).astype(jnp.int64)         # [2^52, 2^53) or 0
    normal = ((e + 1023).astype(jnp.int64) << 52) | (mant - (1 << 52))
    subnormal = mant >> jnp.clip(-1022 - e, 0, 63).astype(jnp.int64)
    bits = jnp.where(e >= -1022, normal, subnormal)
    # a backend whose doubles have a narrower exponent range than
    # binary64 (the TPU's) stops scaling a zero early
    bits = jnp.where(a == 0, jnp.int64(0), bits)
    bits = jnp.where(jnp.isinf(x), jnp.int64(0x7FF0 << 48), bits)
    # the sign survives the narrowing convert (also for -0.0 and
    # underflow), and f32 bitcasts are native on every backend
    sign = jax.lax.bitcast_convert_type(
        x.astype(jnp.float32), jnp.uint32) >> 31
    bits = bits | (sign.astype(jnp.int64) << 63)
    return jnp.where(jnp.isnan(x), jnp.int64(0x7FF8 << 48), bits)


def float64_order_key(x: jnp.ndarray) -> jnp.ndarray:
    """int64 whose signed order is lax.sort's order of the f64 `x`:
    IEEE totalOrder with lax.sort's own canonicalization (-0.0 equals
    0.0; every NaN is the one positive NaN, last)."""
    b = float64_bits(jnp.where(x == 0, 0.0, x))
    # negative floats order by descending magnitude
    return jnp.where(b < 0, ~b ^ jnp.int64(-1 << 63), b)


def _hash_lanes(data: jnp.ndarray, mask: jnp.ndarray,
                null_lane: int) -> jnp.ndarray:
    """Key column -> uint64 lanes for the avalanche mixers; NULL takes
    a fixed lane."""
    if data.dtype in (jnp.float32, jnp.float64):
        x = float64_bits(data)
    else:
        x = data.astype(jnp.int64)
    return jnp.where(mask, x, jnp.int64(null_lane)).astype(jnp.uint64)


def hash64(data: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Splitmix64-style avalanche hash; NULL hashes to a fixed lane.
    Mixing runs in uint64 so the xor-shifts are LOGICAL: an arithmetic
    shift sign-extends and biases every high bit toward the sign —
    harmless for low-bit bucketing, fatal for anything reading the top
    bits (HLL rho, spill partitioning's h >> 32)."""
    x = _hash_lanes(data, mask, -0x61C8864680B583EB)
    x = (x ^ (x >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> 27)) * jnp.uint64(0x94D049BB133111EB)
    x = x ^ (x >> 31)
    return jax.lax.bitcast_convert_type(x, jnp.int64)


def hash64b(data: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """SECOND avalanche hash, independent of hash64: murmur3's fmix64
    constants instead of splitmix's, and a different NULL lane. Used
    by the join probe's verify-elision — a candidate whose 64-bit
    search hash already matches is confirmed by comparing this hash
    instead of gathering every key column (see docs/JOIN_KERNEL.md
    for the collision argument)."""
    x = _hash_lanes(data, mask, 0x2545F4914F6CDD1D)
    x = (x ^ (x >> 33)) * jnp.uint64(0xFF51AFD7ED558CCD)
    x = (x ^ (x >> 33)) * jnp.uint64(0xC4CEB9FE1A85EC53)
    x = x ^ (x >> 33)
    return jax.lax.bitcast_convert_type(x, jnp.int64)


def row_hash(cols: Sequence[CVal]) -> jnp.ndarray:
    """Combined hash of several key columns (for shuffle + group-by)."""
    h = None
    for data, mask in cols:
        hi = hash64(data, mask)
        h = hi if h is None else h * jnp.int64(31) + hi
    assert h is not None
    return h


def row_hash2(cols: Sequence[CVal]) -> jnp.ndarray:
    """Combined SECOND hash (hash64b-based, different combine
    multiplier) — independent of row_hash, so the pair behaves as a
    128-bit fingerprint."""
    h = None
    for data, mask in cols:
        hi = hash64b(data, mask)
        h = hi if h is None else h * jnp.int64(37) + hi
    assert h is not None
    return h


def sort_rows(keys: Sequence[CVal],
              descending: Optional[Sequence[bool]] = None,
              nulls_first: Optional[Sequence[bool]] = None,
              valid: Optional[jnp.ndarray] = None,
              payloads: Sequence[jnp.ndarray] = ()):
    """Lexicographic sort of rows: one `lex_perm` over the keys, then
    one gather per carried array. (Riding keys and payloads through a
    single variadic lax.sort instead costs the TPU compiler minutes:
    on a v5e a 5-payload int64 sort of 1M rows compiled in 96 s and
    ran in 6 ms, this form compiled in 25 s and ran in 82 ms — my chip
    run, PR 22, PERF.md. The gathers are the price of a cold start
    that fits a client's timeout.)

    Sort operands per key are (null_rank, canonical_value) so SQL
    null ordering and NULL==NULL grouping hold; `valid=False` rows sort
    to the end. Returns (sorted_keys, sorted_valid, sorted_payloads).
    """
    desc = descending or [False] * len(keys)
    nf = nulls_first or [False] * len(keys)
    sort_ops: List[jnp.ndarray] = []
    if valid is not None:
        sort_ops.append(~valid)
    for (data, mask), d, nfirst in zip(keys, desc, nf):
        sort_ops.append(mask if nfirst else ~mask)
        sv = _negate_for_desc(data) if d else data
        sort_ops.append(jnp.where(mask, sv, jnp.zeros((), sv.dtype)))
    if not sort_ops:
        return list(keys), valid, list(payloads)
    perm = lex_perm(sort_ops)
    skeys = [(d[perm], m[perm]) for d, m in keys]
    svalid = None if valid is None else valid[perm]
    return skeys, svalid, [p[perm] for p in payloads]


def _negate_for_desc(key: jnp.ndarray) -> jnp.ndarray:
    if key.dtype == jnp.bool_:
        return ~key
    return -key.astype(jnp.float64) if key.dtype in (jnp.float32,) \
        else -key


def boundaries(sorted_keys: Sequence[CVal],
               sorted_valid: jnp.ndarray,
               hashes: Optional[Sequence[jnp.ndarray]] = None
               ) -> jnp.ndarray:
    """True where a new group starts (first valid row or key change),
    over rows already in group order. NULLs compare equal for grouping
    (SQL GROUP BY treats NULLs as one group).

    `hashes` (already in the same sorted order) extends the adjacent
    compare for HASH-ordered grouping: rows are grouped by (hashes,
    keys), so equal-key adjacency only needs the hash sort, not a full
    lexicographic key sort (see hashagg._group_reduce)."""
    n = sorted_valid.shape[0]
    first = jnp.zeros(n, bool).at[0].set(True)
    change = first
    for h in (hashes or ()):
        change = change | (h != jnp.roll(h, 1))
    for data, mask in sorted_keys:
        prev_d = jnp.roll(data, 1)
        prev_m = jnp.roll(mask, 1)
        differs = (data != prev_d) | (mask != prev_m)
        # both-null rows compare equal
        differs = differs & ~(~mask & ~prev_m)
        change = change | differs
    prev_valid = jnp.roll(sorted_valid, 1).at[0].set(False)
    return sorted_valid & (change | ~prev_valid)


def take(cols: Sequence[CVal], idx: jnp.ndarray) -> List[CVal]:
    return [(d[idx], m[idx]) for d, m in cols]
