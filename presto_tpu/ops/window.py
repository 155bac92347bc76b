"""Window function kernel (reference: WindowOperator.java:62 +
operator/window/ — FramedWindowFunction, RankingFunction etc.).

TPU-native design: one whole-relation kernel, not a per-row loop. Rows
are lex-sorted by (partition keys, order keys); partition and peer
boundaries come from adjacent comparison; ranking functions are
position arithmetic over boundary prefix sums; framed aggregates are
(segmented) prefix scans; full-partition aggregates are segment
reductions gathered back to rows. Results scatter back to the original
row order, so the operator preserves input order (like the reference).

General frames: any ROWS/RANGE BETWEEN with UNBOUNDED / CURRENT ROW /
k PRECEDING / k FOLLOWING bounds. Per-row frame positions [flo, fhi]
come from position arithmetic (ROWS) or a vectorized partition-local
binary search over the canonical sort value (RANGE offsets); sums and
counts are prefix-sum differences, min/max are O(n log n) sparse-table
range queries (no sequential sliding window), and positional values
gather at frame endpoints. The frame of every row in a query computes
simultaneously — there is no per-row loop anywhere.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.batch import Batch, Column
from presto_tpu.ops import common
from presto_tpu.telemetry import kernels as _kernels
from presto_tpu.types import Type

#: legacy frame modes (still accepted; normalized in the kernel)
FULL = "full"              # whole partition
ROWS_RUNNING = "rows"      # rows unbounded preceding..current row
RANGE_RUNNING = "range"    # + peers share their group's last value

#: frame bound encoding: "u" = UNBOUNDED, "c" = CURRENT ROW, a signed
#: number = offset (negative = PRECEDING, positive = FOLLOWING)
Bound = Union[str, int, float]


@dataclasses.dataclass(frozen=True)
class WindowCallSpec:
    """Static description of one window function call (hashable: part
    of the jit cache key)."""
    out_name: str
    function: str              # rank|ntile|sum|first_value|...
    arg: Optional[str]         # input column name (None for count(*))
    frame: str                 # "rows" | "range" | legacy mode consts
    out_type: Type = None
    out_dict: Optional[Tuple[str, ...]] = None
    offset: int = 1            # lag/lead distance; ntile/nth_value N
    fstart: Bound = "u"        # frame start bound
    fend: Bound = "c"          # frame end bound
    filter_arg: Optional[str] = None   # FILTER (WHERE ...) column
    default: Any = None        # lag/lead constant default value

    def norm_frame(self) -> Tuple[str, Bound, Bound]:
        """Normalize legacy mode constants to (mode, fstart, fend)."""
        if self.frame == FULL:
            return "rows", "u", "u"
        if self.frame == ROWS_RUNNING and self.fstart == "u" \
                and self.fend == "c":
            return "rows", "u", "c"
        if self.frame == RANGE_RUNNING:
            return "range", self.fstart, self.fend
        return self.frame, self.fstart, self.fend


RANKING = ("rank", "dense_rank", "row_number", "ntile", "percent_rank",
           "cume_dist")
POSITIONAL = ("lag", "lead", "first_value", "last_value", "nth_value")


def _rmq(contrib: jnp.ndarray, flo, fhi, op, ident) -> jnp.ndarray:
    """Range min/max over [flo, fhi] per row via a sparse table:
    log n doubling levels, then each query combines two overlapping
    power-of-two blocks — O(n log n) build, O(1) per query, fully
    vectorized (the TPU answer to the sequential sliding-window
    deque)."""
    n = contrib.shape[0]
    levels = max(1, math.ceil(math.log2(max(n, 2))) + 1)
    tabs = [contrib]
    for lv in range(1, levels):
        shift = 1 << (lv - 1)
        prev = tabs[-1]
        if shift < n:
            shifted = jnp.concatenate(
                [prev[shift:], jnp.full((shift,), ident, prev.dtype)])
        else:
            shifted = jnp.full((n,), ident, prev.dtype)
        tabs.append(op(prev, shifted))
    T = jnp.stack(tabs).reshape(-1)          # [levels * n]
    w = fhi - flo + 1
    k = jnp.where(w > 0,
                  jnp.floor(jnp.log2(jnp.maximum(w, 1))), 0
                  ).astype(jnp.int32)
    lo = jnp.clip(flo, 0, n - 1)
    hi2 = jnp.clip(fhi - (1 << k) + 1, 0, n - 1)
    a = T[k * n + lo]
    b = T[k * n + hi2]
    return jnp.where(w > 0, op(a, b), ident)


def _part_searchsorted(sv: jnp.ndarray, target: jnp.ndarray,
                       pstart: jnp.ndarray, pend: jnp.ndarray,
                       side_left: bool) -> jnp.ndarray:
    """Per-row binary search WITHIN [pstart[i], pend[i]]: first index j
    with sv[j] >= target[i] (side_left) or > target[i] (not side_left).
    sv is nondecreasing inside each partition. ~log2(n) vectorized
    gather steps."""
    n = sv.shape[0]
    lo = pstart
    hi = pend + 1
    for _ in range(max(1, math.ceil(math.log2(max(n, 2))) + 1)):
        active = lo < hi
        mid = (lo + hi) // 2
        midv = sv[jnp.clip(mid, 0, n - 1)]
        go_left = (midv >= target) if side_left else (midv > target)
        hi = jnp.where(active & go_left, mid, hi)
        lo = jnp.where(active & ~go_left, mid + 1, lo)
    return lo


def _seg_scan(op_name: str, x: jnp.ndarray, restart: jnp.ndarray):
    """Segmented inclusive scan: `op` over runs delimited by `restart`
    (True at each segment's first row)."""
    if op_name == "sum":
        # global prefix sum minus the prefix just before the current
        # segment's first row
        cum = jnp.cumsum(x)
        start_pos = _segment_positions(restart)
        base = cum[start_pos] - x[start_pos]
        return cum - base

    def comb(a, b):
        af, av = a
        bf, bv = b
        if op_name == "min":
            v = jnp.minimum(av, bv)
        else:
            v = jnp.maximum(av, bv)
        return (af | bf, jnp.where(bf, bv, v))

    _, vals = jax.lax.associative_scan(comb, (restart, x), axis=0)
    return vals


def _segment_positions(bnd: jnp.ndarray) -> jnp.ndarray:
    """Index of the current segment's first row, per row."""
    pos = jnp.arange(bnd.shape[0])
    return jax.lax.cummax(jnp.where(bnd, pos, 0), axis=0)


@functools.partial(
    _kernels.jit, family="window",
    static_argnames=("part_names", "order_names", "descending",
                     "nulls_first", "calls"))
def _window_kernel_jit(batch: Batch,
                  part_names: Tuple[str, ...],
                  order_names: Tuple[str, ...],
                  descending: Tuple[bool, ...],
                  nulls_first: Tuple[bool, ...],
                  calls: Tuple[WindowCallSpec, ...]) -> Batch:
    cap = batch.capacity
    valid = batch.row_valid
    part_cols = [batch.columns[n].astuple() for n in part_names]
    order_cols = [batch.columns[n].astuple() for n in order_names]

    # ONE sort_rows call carries the referenced argument columns and a
    # row-index iota; results return to input order with a second sort
    # keyed on that iota (a sort, not the scatter-lowered inverse
    # permutation — scatters serialize on TPU)
    ref_args = tuple(sorted(
        {c.arg for c in calls if c.arg is not None}
        | {c.filter_arg for c in calls if c.filter_arg is not None}))
    payloads: list = []
    for a in ref_args:
        payloads.extend(batch.columns[a].astuple())
    payloads.append(jnp.arange(cap, dtype=jnp.int32))
    skeys, svalid, spay = common.sort_rows(
        part_cols + order_cols,
        descending=(False,) * len(part_cols) + tuple(descending),
        nulls_first=(False,) * len(part_cols) + tuple(nulls_first),
        valid=valid, payloads=payloads)
    spart = skeys[:len(part_cols)]
    sorder = skeys[len(part_cols):]
    sargs = {a: (spay[2 * i], spay[2 * i + 1])
             for i, a in enumerate(ref_args)}
    iota_sorted = spay[-1]
    pos = jnp.arange(cap)

    if part_cols:
        pbnd = common.boundaries(spart, svalid)
    else:
        pbnd = jnp.where(pos == 0, svalid, False)
    pid = jnp.maximum(jnp.cumsum(pbnd) - 1, 0)  # partition index
    pstart = _segment_positions(pbnd)

    if order_cols:
        peer_bnd = common.boundaries(spart + sorder, svalid)
    else:
        peer_bnd = pbnd
    peer_id = jnp.maximum(jnp.cumsum(peer_bnd) - 1, 0)
    peer_start = _segment_positions(peer_bnd)
    # last VALID row position of each peer group / partition, gathered
    # per row (padding rows sort to the end and inherit the final
    # group's ids — they must not win the max)
    peer_end = jax.ops.segment_max(
        jnp.where(svalid, pos, -1), peer_id, num_segments=cap + 1,
        indices_are_sorted=True)[peer_id]
    peer_end = jnp.maximum(peer_end, 0)
    part_end = jnp.maximum(jax.ops.segment_max(
        jnp.where(svalid, pos, -1), pid, num_segments=cap + 1,
        indices_are_sorted=True)[pid], 0)
    psize = part_end - pstart + 1

    # canonical nondecreasing-within-partition value of the first order
    # key (RANGE offset frames); NULLs pinned to the end they sort to
    if order_cols:
        od, om = sorder[0]
        if jnp.issubdtype(od.dtype, jnp.integer):
            sv_val = -od.astype(jnp.int64) if descending[0] \
                else od.astype(jnp.int64)
            info = jnp.iinfo(jnp.int64)
            null_sv = info.min if nulls_first[0] else info.max
        else:
            sv_val = -od.astype(jnp.float64) if descending[0] \
                else od.astype(jnp.float64)
            null_sv = -jnp.inf if nulls_first[0] else jnp.inf
        sv0 = jnp.where(om, sv_val, jnp.asarray(null_sv, sv_val.dtype))
        ok_mask0 = om
    else:
        sv0 = jnp.zeros(cap, jnp.int64)
        ok_mask0 = jnp.ones(cap, bool)

    frame_cache = {}

    def frame_of(mode, fs, fe):
        """Per-row inclusive frame positions [flo, fhi]."""
        key = (mode, fs, fe)
        if key in frame_cache:
            return frame_cache[key]
        if mode == "rows":
            if fs == "u":
                flo = pstart
            elif fs == "c":
                flo = pos
            else:
                flo = jnp.maximum(pstart, pos + int(fs))
            if fe == "u":
                fhi = part_end
            elif fe == "c":
                fhi = pos
            else:
                fhi = jnp.minimum(part_end, pos + int(fe))
        else:  # range (value-based, first order key)
            if fs == "u":
                flo = pstart
            elif fs == "c":
                flo = peer_start
            else:
                # k PRECEDING on the canonical scale is always -k
                off = jnp.asarray(fs, sv0.dtype)
                flo = _part_searchsorted(sv0, sv0 + off, pstart,
                                         part_end, True)
                flo = jnp.where(ok_mask0, flo, peer_start)
            if fe == "u":
                fhi = part_end
            elif fe == "c":
                fhi = peer_end
            else:
                off = jnp.asarray(fe, sv0.dtype)
                fhi = _part_searchsorted(sv0, sv0 + off, pstart,
                                         part_end, False) - 1
                fhi = jnp.where(ok_mask0, fhi, peer_end)
        frame_cache[key] = (flo, fhi)
        return flo, fhi

    def range_sum(arr, flo, fhi):
        pre = jnp.cumsum(arr, axis=0)
        hi_v = pre[jnp.clip(fhi, 0, cap - 1)]
        lo_v = jnp.where(flo > 0,
                         pre[jnp.clip(flo - 1, 0, cap - 1)],
                         jnp.zeros((), pre.dtype))
        return jnp.where(fhi >= flo, hi_v - lo_v,
                         jnp.zeros((), pre.dtype))

    def range_sum_dd(arr, flo, fhi):
        """Compensated framed float sum: prefix sums kept as
        DOUBLE-DOUBLE (hi, lo) pairs via a two-sum associative scan,
        so the prefix-difference trick keeps ~107 bits through the
        cancellation that kills a plain f64 cumsum difference (one
        large early value would otherwise poison every later frame —
        the reference's per-frame accumulation never differences)."""
        def two_sum(a, b):
            s = a + b
            bp = s - a
            return s, (a - (s - bp)) + (b - bp)

        def combine(l, r):
            s, e = two_sum(l[0], r[0])
            return s, e + l[1] + r[1]

        hi, lo = jax.lax.associative_scan(
            combine, (arr, jnp.zeros_like(arr)))
        hi_h = hi[jnp.clip(fhi, 0, cap - 1)]
        lo_h = lo[jnp.clip(fhi, 0, cap - 1)]
        zero = jnp.zeros((), arr.dtype)
        at_lo = jnp.clip(flo - 1, 0, cap - 1)
        hi_l = jnp.where(flo > 0, hi[at_lo], zero)
        lo_l = jnp.where(flo > 0, lo[at_lo], zero)
        v = (hi_h - hi_l) + (lo_h - lo_l)
        return jnp.where(fhi >= flo, v, zero)

    def float_range_sum(arr, w, flo, fhi):
        """Float framed sum with EXACT IEEE special-value semantics: a
        plain cumsum difference would leak one row's NaN/Inf into every
        LATER frame (x - NaN = NaN). The finite part flows through the
        compensated scan; NaN/+Inf/-Inf presence is counted with
        integer prefix sums (exact) and re-applied only to frames that
        contain them."""
        finite = jnp.isfinite(arr)
        base = range_sum_dd(jnp.where(finite, arr, 0.0), flo, fhi)
        n_nan = range_sum((w & jnp.isnan(arr)).astype(jnp.int32),
                          flo, fhi)
        n_pinf = range_sum((w & (arr == jnp.inf)).astype(jnp.int32),
                           flo, fhi)
        n_ninf = range_sum((w & (arr == -jnp.inf)).astype(jnp.int32),
                           flo, fhi)
        out = jnp.where(n_pinf > 0, jnp.inf, base)
        out = jnp.where(n_ninf > 0, -jnp.inf, out)
        out = jnp.where((n_pinf > 0) & (n_ninf > 0), jnp.nan, out)
        return jnp.where(n_nan > 0, jnp.nan, out)

    out_sorted = {}  # name -> (data, mask) in SORTED row order
    for c in calls:
        if c.function in RANKING:
            if c.function == "row_number":
                v = pos - pstart + 1
            elif c.function == "rank":
                v = peer_start - pstart + 1
            elif c.function == "dense_rank":
                dc = jnp.cumsum(peer_bnd)
                v = dc - dc[pstart] + 1
            elif c.function == "ntile":
                # larger buckets first (reference: NTileFunction):
                # r = psize % n buckets get q+1 rows
                nt = max(int(c.offset), 1)
                q = psize // nt
                r = psize % nt
                idx = pos - pstart
                cutoff = r * (q + 1)
                v = jnp.where(
                    idx < cutoff,
                    idx // jnp.maximum(q + 1, 1) + 1,
                    r + (idx - cutoff) // jnp.maximum(q, 1) + 1)
            elif c.function == "percent_rank":
                rk = (peer_start - pstart).astype(jnp.float64)
                v = jnp.where(psize > 1,
                              rk / jnp.maximum(psize - 1, 1), 0.0)
            else:  # cume_dist
                v = (peer_end - pstart + 1).astype(jnp.float64) \
                    / jnp.maximum(psize, 1)
            out_sorted[c.out_name] = (
                v.astype(c.out_type.np_dtype), svalid)
            continue

        if c.function in POSITIONAL:
            sd, sm = sargs[c.arg]
            if c.function in ("lag", "lead"):
                k = c.offset if c.function == "lag" else -c.offset
                idx = jnp.clip(pos - k, 0, cap - 1)
                in_part = (pid[idx] == pid) & svalid[idx] \
                    & (pos - k >= 0) & (pos - k <= cap - 1)
                d = sd[idx]
                m = jnp.where(in_part, sm[idx], False)
                if c.default is not None:
                    d = jnp.where(in_part, d,
                                  jnp.asarray(c.default, d.dtype))
                    m = m | ~in_part
            else:
                flo, fhi = frame_of(*c.norm_frame())
                if c.function == "first_value":
                    idx = flo
                elif c.function == "last_value":
                    idx = fhi
                else:  # nth_value: N-th row of the frame
                    idx = flo + (max(int(c.offset), 1) - 1)
                nonempty = (fhi >= flo) & (idx >= flo) & (idx <= fhi)
                idx = jnp.clip(idx, 0, cap - 1)
                d = sd[idx]
                m = sm[idx] & nonempty
            out_sorted[c.out_name] = (d, m & svalid)
            continue

        # aggregates over a frame
        if c.arg is None:  # count(*)
            w = svalid
            vals = w.astype(jnp.int64)
        else:
            sd, sm = sargs[c.arg]
            w = svalid & sm
            vals = sd
        if c.filter_arg is not None:
            fd, fm = sargs[c.filter_arg]
            w = w & fd.astype(bool) & fm

        fn = c.function
        dt = c.out_type.np_dtype
        flo, fhi = frame_of(*c.norm_frame())
        cnt_contrib = w.astype(np.int64)
        runc = range_sum(cnt_contrib, flo, fhi)
        if fn == "count":
            run = runc
        elif fn in ("sum", "avg"):
            contrib = jnp.where(w, vals, 0).astype(
                np.float64 if fn == "avg" else dt)
            if jnp.issubdtype(contrib.dtype, jnp.floating):
                run = float_range_sum(contrib, w, flo, fhi)
            else:
                run = range_sum(contrib, flo, fhi)
        elif fn in ("min", "max"):
            ident = _minmax_ident(fn, vals.dtype)
            contrib = jnp.where(w, vals, ident)
            op = jnp.minimum if fn == "min" else jnp.maximum
            run = _rmq(contrib, flo, fhi, op, ident)
        else:
            raise ValueError(f"unknown window function {fn}")

        if fn == "count":
            data, mask = run.astype(jnp.int64), svalid
        elif fn == "avg":
            data = run / jnp.maximum(runc, 1)
            mask = runc > 0
        else:
            data, mask = run.astype(dt), runc > 0
        out_sorted[c.out_name] = (data, mask & svalid)

    # back to input order: one sort keyed on the carried iota (the
    # sorted iota is a permutation, so this is an exact inverse)
    names = list(out_sorted)
    flat: list = []
    for n in names:
        flat.extend(out_sorted[n])
    unsorted = jax.lax.sort((iota_sorted,) + tuple(flat), num_keys=1,
                            is_stable=True)[1:]
    cols = dict(batch.columns)
    spec_of = {c.out_name: c for c in calls}
    for i, n in enumerate(names):
        c = spec_of[n]
        dic = None if c.function in RANKING else c.out_dict
        cols[n] = Column(unsorted[2 * i], unsorted[2 * i + 1],
                         c.out_type, dic)
    return Batch(cols, valid)


# compile-vs-execute attribution for the window family (previously an
# uninstrumented module-level jit whose compile time landed in busy)
_instr = _kernels.instrument_kernel

window_kernel = _instr(_window_kernel_jit, "window")


# -- kernel contract (tools/kernelcheck.py) ----------------------------
from presto_tpu.analysis.contracts import (
    KernelContract, TracePoint, abstract_batch, register_contract,
)


def _window_point(cap, variant):
    from presto_tpu.types import BIGINT, DOUBLE
    b, rb = abstract_batch(
        cap, [("p", BIGINT), ("o", BIGINT), ("v", DOUBLE)])
    calls = (
        WindowCallSpec("rnk", "rank", None, FULL, BIGINT),
        WindowCallSpec("s", "sum", "v", ROWS_RUNNING, DOUBLE),
        WindowCallSpec("lg", "lag", "v", FULL, DOUBLE),
    )
    return TracePoint(
        lambda batch: _window_kernel_jit(
            batch, part_names=("p",), order_names=("o",),
            descending=(False,), nulls_first=(False,), calls=calls),
        (b,), (rb,))


register_contract(KernelContract(
    family="window", module=__name__, build=_window_point,
    structure_varies=True,
    structure_reason="the _rmq sparse table builds ceil(log2(n))+1 "
                     "doubling levels in Python — eqn count depends "
                     "on the bucket by construction"))


def _minmax_ident(fn: str, dtype):
    info = jnp.iinfo(dtype) if jnp.issubdtype(dtype, jnp.integer) \
        else jnp.finfo(dtype)
    return jnp.asarray(info.max if fn == "min" else info.min, dtype)
