"""Sorted-run merge kernels (reference: operator/MergeOperator.java:44
merging pre-sorted remote shards via MergeSortedPages).

TPU-native design: no heap, no comparison loop over rows. Two sorted
runs A and B merge by *rank arithmetic*: every A-row's output slot is
its own index plus the count of B-rows strictly below it, and every
B-row's slot is its index plus the count of A-rows at-or-below it
(ties resolve A-first — stability across runs). The counts come from
one vectorized lexicographic binary search (fixed log2(n) rounds of
gathers — no data-dependent control flow), then a single scatter
places both runs. k runs fold pairwise in a log-depth tree.

The lex compare uses exactly `common.sort_rows`'s canonical operand
encoding ((null_rank, canonical_value) per key, ~valid leading), so a
merge of sorted runs is bit-identical to re-sorting their union."""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from presto_tpu.batch import Batch, Column
from presto_tpu.ops.common import _negate_for_desc, float64_order_key
from presto_tpu.telemetry import kernels as _kernels

CVal = Tuple[jnp.ndarray, jnp.ndarray]


def _total_order(v: jnp.ndarray) -> jnp.ndarray:
    """Map a sort operand to an integer with the SAME order lax.sort
    uses. Floats get the sign-flip of their bit pattern that realizes
    IEEE totalOrder (-inf < ... < +inf < NaN) as integer order — a
    plain IEEE `<`/`==` would treat NaN keys as unordered, collapsing
    the merge's rank arithmetic into colliding scatter slots (dropped
    + duplicated rows). lax.sort compares -0.0 equal to 0.0 and every
    NaN as the one positive NaN; so does this key."""
    if v.dtype == jnp.float64:
        # arithmetic, not a bitcast: XLA:TPU refuses bitcasts from f64
        return float64_order_key(v)
    if v.dtype == jnp.float32:
        v = jnp.where(v == 0, jnp.float32(0), v)
        v = jnp.where(jnp.isnan(v), jnp.float32(jnp.nan), v)
        u = jax.lax.bitcast_convert_type(v, jnp.uint32)
        top = jnp.uint32(1) << 31
        return jnp.where(u & top != 0, ~u, u | top)
    return v


def _canonical_ops(batch: Batch, key_names, descending, nulls_first
                   ) -> List[jnp.ndarray]:
    """Sort operands in lex significance order: ~valid first, then
    (null_rank, canonical_value) per key — mirrors common.sort_rows,
    with float values mapped through the totalOrder bitcast so binary
    comparisons agree with the lax.sort order of the input runs."""
    ops = [~batch.row_valid]
    for name, d, nfirst in zip(key_names, descending, nulls_first):
        c = batch.columns[name]
        ops.append(c.mask if nfirst else ~c.mask)
        sv = _negate_for_desc(c.data) if d else c.data
        sv = jnp.where(c.mask, sv, jnp.zeros((), sv.dtype))
        ops.append(_total_order(sv))
    return ops


def _lex_count_below(b_ops: List[jnp.ndarray],
                     a_ops: List[jnp.ndarray],
                     strict: bool) -> jnp.ndarray:
    """For every row r of A (queries `a_ops`), how many rows of the
    lex-sorted run B (`b_ops`) order before it — strictly (<) or
    non-strictly (<=). One vectorized binary search: ceil(log2(nB))+1
    rounds, each one gather per operand."""
    n_b = b_ops[0].shape[0]
    n_a = a_ops[0].shape[0]
    lo = jnp.zeros(n_a, jnp.int32)
    hi = jnp.full(n_a, n_b, jnp.int32)
    import math
    rounds = max(1, int(math.ceil(math.log2(max(n_b, 2)))) + 1)
    for _ in range(rounds):
        mid = (lo + hi) // 2
        midc = jnp.minimum(mid, n_b - 1)
        # lexicographic b[mid] < a  /  b[mid] <= a
        lt = jnp.zeros(n_a, bool)
        eq = jnp.ones(n_a, bool)
        for bo, ao in zip(b_ops, a_ops):
            bv = bo[midc]
            lt = lt | (eq & (bv < ao))
            eq = eq & (bv == ao)
        advance = (lt | eq) if not strict else lt
        lo = jnp.where(advance, mid + 1, lo)
        hi = jnp.where(advance, hi, mid)
        # keep the completed searches stable
        lo = jnp.minimum(lo, n_b)
    return lo


@functools.partial(_kernels.jit, family="merge",
                   static_argnums=(2, 3, 4))
def _merge_pair_jit(a: Batch, b: Batch, key_names: Tuple[str, ...],
                    descending: Tuple[bool, ...],
                    nulls_first: Tuple[bool, ...]) -> Batch:
    """Merge two lex-sorted batches into one sorted batch of capacity
    |A|+|B| (invalid rows sort to the end in both, so they land at the
    end of the output too)."""
    a_ops = _canonical_ops(a, key_names, descending, nulls_first)
    b_ops = _canonical_ops(b, key_names, descending, nulls_first)
    n_a, n_b = a.capacity, b.capacity
    pos_a = jnp.arange(n_a, dtype=jnp.int32) \
        + _lex_count_below(b_ops, a_ops, strict=True)
    pos_b = jnp.arange(n_b, dtype=jnp.int32) \
        + _lex_count_below(a_ops, b_ops, strict=False)
    out_cap = n_a + n_b
    cols = {}
    for name in a.names:
        ca, cb = a.columns[name], b.columns[name]
        data = jnp.zeros((out_cap,), ca.data.dtype)
        data = data.at[pos_a].set(ca.data).at[pos_b].set(cb.data)
        mask = jnp.zeros((out_cap,), bool)
        mask = mask.at[pos_a].set(ca.mask).at[pos_b].set(cb.mask)
        cols[name] = Column(data, mask, ca.type, ca.dictionary)
    rv = jnp.zeros((out_cap,), bool)
    rv = rv.at[pos_a].set(a.row_valid).at[pos_b].set(b.row_valid)
    return Batch(cols, rv)


# compile-vs-execute attribution for the sorted-run merge family
_instr = _kernels.instrument_kernel

merge_pair = _instr(_merge_pair_jit, "merge")


# -- kernel contract (tools/kernelcheck.py) ----------------------------
from presto_tpu.analysis.contracts import (
    KernelContract, TracePoint, abstract_batch, register_contract,
)


def _merge_point(cap, variant):
    from presto_tpu.types import BIGINT, DOUBLE
    schema = [("k", BIGINT), ("v", DOUBLE)]
    a, ra = abstract_batch(cap, schema)
    b, rb = abstract_batch(cap, schema)
    keys, desc, nf = ("k",), (False,), (False,)
    return TracePoint(
        lambda x, y: _merge_pair_jit(x, y, keys, desc, nf),
        (a, b), (ra, rb))


register_contract(KernelContract(
    family="merge", module=__name__, build=_merge_point,
    structure_varies=True,
    structure_reason="_lex_count_below unrolls ceil(log2(n))+1 "
                     "binary-search rounds in Python — eqn count is "
                     "a function of the bucket by construction"))


def merge_runs(runs: Sequence[Batch], key_names: Sequence[str],
               descending: Sequence[bool],
               nulls_first: Sequence[bool]) -> Batch:
    """Pairwise log-depth tree fold of k sorted runs (host-side loop —
    each level is one jitted merge per pair)."""
    key_names = tuple(key_names)
    descending = tuple(descending)
    nulls_first = tuple(nulls_first)
    level = list(runs)
    while len(level) > 1:
        nxt = []
        for i in range(0, len(level) - 1, 2):
            nxt.append(merge_pair(level[i], level[i + 1], key_names,
                                  descending, nulls_first))
        if len(level) % 2:
            nxt.append(level[-1])
        level = nxt
    return level[0]
