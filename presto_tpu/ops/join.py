"""Equi-join kernels with a RADIX-PARTITIONED probe (reference:
HashBuilderOperator.java:51, LookupJoinOperator.java:53 probing a
generated PagesHashStrategy over PagesIndex.java:75; partitioning
design after Balkesen et al., "Main-Memory Hash Joins on Multi-Core
CPUs", ICDE 2013).

TPU-native design: no pointer-chasing hash table. Two build layouts
share the expand stage and nothing else. A build with ONE unique
integer key whose range is the order of its row count is addressed
directly (`build_direct`: `slot_of[key - min]`, one gather a probe
row, nothing hashed or sorted; chosen by HashBuildOperator.finish
from what the build side shows). Every other build side is
*sorted by key hash* once; `build_for_backend` then records, per
top-`radix_bits` hash prefix, where that bucket starts in the sorted
order (`part_starts`, one bucket per ~build row), the length of every
equal-hash run (`run_len`), and a SECOND independent 64-bit hash
(`hash2`). A probe row:

1. computes its 64-bit key hash; the top `radix_bits` bits name its
   bucket, whose [start, end) bounds are two O(1) gathers;
2. binary-searches ONLY that bucket (`bounded_searchsorted`, depth =
   log2(max bucket) measured at build — ~5 levels for a 256k-row
   build instead of 2 x 19 whole-table levels, and ONE search: the
   run length read from `run_len[lo]` replaces the side="right"
   search);
3. verifies the candidate by comparing `hash2` instead of gathering
   every key column — with the search hash that is a 128-bit
   fingerprint, and a false match needs a simultaneous collision in
   two independent avalanche functions (see docs/JOIN_KERNEL.md).
   The full-key compare survives behind `verify="full"` as the
   collision fallback and the oracle the radix tests compare against.

Expansion is layout-specialized (all switches STATIC — they ride the
BuildTable pytree aux data or the call signature, so each shape
compiles once):

- ALIGNED: when every build hash run has length 1 (`unique_runs` —
  any unique-key/FK->PK build) and the output capacity equals the
  probe capacity, output slot i IS probe row i: inner misses just
  mask their slot dead. No prefix sum, no scatter, no
  expand-by-counts. It is two bodies, `aligned_front` (search, count)
  and `aligned_back` (gather both sides' columns at a static width):
  under one program at the batch's width the back moves nothing; an
  operator that reads the count between them gathers at the live
  rows' bucket only (LATE materialization, docs/JOIN_KERNEL.md), and
  no deferred shrink follows.
- GENERAL: duplicate-key builds (or caller-grown capacities) take the
  prefix-sum + expand-by-counts path with a host-chosen capacity and
  the on-device overflow flag.

On XLA:CPU the probe runs as TWO dispatches (search, then expand):
its fusion emitter re-materializes a fused producer chain once per
consumer, so feeding the bounded search into a multi-output expand
re-runs the whole search per output column (measured ~2x on the
round-6 host). The dispatch boundary materializes `lo` exactly once;
TPU keeps the single fused dispatch.

Join types: inner, left, full, semi (IN/EXISTS), anti (NOT IN/NOT
EXISTS); right joins are planned as flipped left joins. FULL OUTER
(reference: LookupJoinOperator + LookupOuterOperator.java:42) probes
like a left join while scatter-accumulating a per-build-row matched
flag on device; after the probe side is exhausted the operator emits
the never-matched build rows with a NULL probe side.

The bucket-contiguous layout is exactly what the ICI all_to_all
shuffle wants on a real TPU mesh: each device owns a contiguous span
of hash buckets, and per-bucket probes are small vectorized searches
instead of whole-table binary search.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.batch import Batch, Column
from presto_tpu.native import pages
from presto_tpu.ops import common
from presto_tpu.telemetry import kernels as _kernels

CVal = Tuple[jnp.ndarray, jnp.ndarray]

#: bucket-per-row radix: k ~ log2(build size), so buckets average ~1
#: row and the bounded search runs ~log2(max bucket) ~ 5 levels.
#: part_starts costs 8 bytes per bucket — at most 2^MAX_RADIX_BITS+1
#: entries (2 MB), the same order as the build itself.
MAX_RADIX_BITS = 18
#: builds at or below this size skip partitioning entirely (the
#: whole-table search is already that shallow)
MIN_RADIX_ROWS = 1024

#: the DIRECT layout (one int32 slot per key value in [min, max]) is
#: taken only while the table stays the order of the build itself:
#: 8 slots a build row is 32 bytes, what the sorted layout's two
#: hashes, run length and permutation index cost per row, and covers
#: a primary key thinned by a filter to an eighth of its range.
DIRECT_MAX_SPREAD_FACTOR = 8
#: ... and never past 2^27 entries (512 MB of int32, 3% of a 16 GB
#: chip), whatever the build's capacity. A sparser or wider key domain
#: keeps the sorted layout, whose cost follows rows and not range.
DIRECT_MAX_SPREAD = 1 << 27

#: build layouts a probe may be handed (BuildTable.layout)
LAYOUTS = ("sorted", "direct")

#: verify modes: "hash" elides the per-candidate full-key compare via
#: the second independent hash; "full" gathers and compares every key
#: column (the pre-radix behavior — collision fallback + test oracle).
VERIFY_MODES = ("hash", "full")


@dataclasses.dataclass
class BuildTable:
    """Build side, ready for probing. A pytree whose AUX DATA carries
    the static search/layout parameters.

    `layout == "sorted"`: `batch` rows are IN sorted-hash order (every
    column follows the build's hash permutation), so a probe candidate
    at sorted slot s reads batch row s directly — no index
    indirection. The direct fields are None.

    `layout == "direct"` (one unique integer key, see `build_direct`):
    `batch` stays in ARRIVAL order, `slot_of[key - key_min]` is the
    batch row holding that key (-1: no such key), and the four
    sorted-hash fields are None. `unique_runs` is True by
    construction."""
    sorted_hash: Optional[jnp.ndarray]  # [n] int64, invalid rows at +inf end
    hash2: Optional[jnp.ndarray]      # [n] int64 second hash (verify)
    part_starts: Optional[jnp.ndarray]  # [2^k + 1] int64 bucket offsets
    run_len: Optional[jnp.ndarray]    # [n] int64: run length AT run starts
    valid_count: jnp.ndarray          # scalar: live build rows
    batch: Batch                      # build rows (order: see layout)
    radix_bits: int = 0               # STATIC: k (0 = whole-table)
    search_depth: int = 64            # STATIC: bounded-search iterations
    unique_runs: bool = False         # STATIC: every valid run has len 1
    layout: str = "sorted"            # STATIC: one of LAYOUTS
    slot_of: Optional[jnp.ndarray] = None   # [R] int32 row, -1 = empty
    key_min: Optional[jnp.ndarray] = None   # scalar int64, a LEAF: a
    key_max: Optional[jnp.ndarray] = None   # new literal compiles nothing


jax.tree_util.register_pytree_node(
    BuildTable,
    lambda t: ((t.sorted_hash, t.hash2, t.part_starts, t.run_len,
                t.valid_count, t.batch, t.slot_of, t.key_min,
                t.key_max),
               (t.radix_bits, t.search_depth, t.unique_runs, t.layout)),
    lambda aux, c: BuildTable(*c[:6], radix_bits=aux[0],
                              search_depth=aux[1], unique_runs=aux[2],
                              layout=aux[3], slot_of=c[6],
                              key_min=c[7], key_max=c[8]),
)

#: int64 sentinel pushing NULL-key/invalid build rows to the sorted end
_H_INVALID = jnp.iinfo(jnp.int64).max
#: hash2 sentinel for those rows — can never equal a valid probe hash2
#: except by a 2^-64 accident (the old full-key path had the same
#: residual odds through an unmasked key column)
_H2_INVALID = jnp.iinfo(jnp.int64).min


def choose_radix_bits(capacity: int) -> int:
    """k from the build size, on HOST: one bucket per expected row,
    capped so part_starts stays bounded."""
    if capacity <= MIN_RADIX_ROWS:
        return 0
    return max(1, min(int(math.ceil(math.log2(capacity))),
                      MAX_RADIX_BITS))


def _bucket_depth(depth: int) -> int:
    """Round the measured bounded-search depth up to a power of two
    when kernel shape bucketing is on: the depth is a STATIC arg of
    every probe kernel, and the exact data-measured value would mint a
    fresh trace per build-side skew profile. A rounded depth costs at
    most 2x search levels (each a cheap gather round) and collapses
    the trace count to ~6 variants."""
    from presto_tpu.batch import shape_buckets_on
    if not shape_buckets_on():
        return depth
    p = 1
    while p < depth:
        p *= 2
    return p


@functools.lru_cache(maxsize=None)
def _partition_bounds_np(k: int) -> np.ndarray:
    """The 2^k signed-int64 bucket boundary values (bucket p = top-k
    bits of the SIGNED hash, offset to [0, 2^k)). Vectorized + cached:
    the signed value (p - half) << (64-k) has the two's-complement
    bit pattern ((p XOR half) << (64-k)), so the whole table is one
    uint64 shift reinterpreted as int64."""
    half = np.uint64(1 << (k - 1))
    p = np.arange(1 << k, dtype=np.uint64)
    return ((p ^ half) << np.uint64(64 - k)).view(np.int64)


def _partition_of(h: jnp.ndarray, k: int) -> jnp.ndarray:
    """Top-k-bit bucket id in [0, 2^k) — arithmetic shift keeps the
    signed sort order aligned with the bucket order."""
    return (h >> jnp.int64(64 - k)) + jnp.int64(1 << (k - 1))


def _hash_batch(batch: Batch, key_names: Tuple[str, ...]):
    keys = [batch.columns[k].astuple() for k in key_names]
    valid = batch.row_valid
    for _, m in keys:
        valid = valid & m
    h = common.row_hash(keys)
    h2 = common.row_hash2(keys)
    h = jnp.where(valid, h, _H_INVALID)
    h2 = jnp.where(valid, h2, _H2_INVALID)
    return h, h2, valid


@functools.partial(_kernels.jit, family="join_build", part="sorted",
                   static_argnums=(1, 2))
def _build_sorted(batch: Batch, key_names: Tuple[str, ...], k: int):
    """Device build: hash keys, order the rows by hash (one
    permutation, one gather per column), then derive the radix
    metadata from the sorted hashes. Returns the BuildTable fields
    plus (max bucket span, max valid run length) for the host's static
    search-depth/layout choice."""
    h, h2, valid = _hash_batch(batch, key_names)
    perm = common.lex_perm([h])
    sh, sh2, sbatch = _build_apply_perm(batch, h, h2, perm)
    n = sh.shape[0]
    first_inv = jnp.searchsorted(sh, _H_INVALID, side="left")
    if k > 0:
        bounds = jnp.asarray(_partition_bounds_np(k))
        starts = jnp.searchsorted(sh, bounds, side="left")
        part_starts = jnp.concatenate(
            [starts, jnp.asarray([n], starts.dtype)]).astype(jnp.int64)
    else:
        part_starts = jnp.asarray([0, n], jnp.int64)
    # invalid rows sit in one giant sentinel run at the end; they can
    # never match (hash2 sentinel), so clipping every bucket at the
    # first invalid row keeps them out of all search spans — without
    # this, a half-padded build would blow the measured max span (and
    # with it the static search depth) up to the padding size
    part_starts = jnp.minimum(part_starts, first_inv)
    max_span = jnp.max(jnp.diff(part_starts))
    idx = jnp.arange(n)
    run_end = jnp.searchsorted(sh, sh, side="right")
    run_len = (run_end - idx).astype(jnp.int64)
    max_run = jnp.max(jnp.where(idx < first_inv,
                                jnp.minimum(run_end, first_inv) - idx,
                                0))
    return sh, sh2, part_starts, run_len, jnp.sum(valid), sbatch, \
        jnp.stack([max_span.astype(jnp.int64),
                   max_run.astype(jnp.int64)])


@functools.partial(_kernels.jit, family="join_build", part="hash",
                   static_argnums=(1,))
def _build_hash(batch: Batch, key_names: Tuple[str, ...]):
    h, h2, _ = _hash_batch(batch, key_names)
    return h, h2


@functools.partial(_kernels.jit, family="join_build", part="apply_perm")
def _build_apply_perm(batch: Batch, h: jnp.ndarray, h2: jnp.ndarray,
                      perm: jnp.ndarray):
    cols = {
        n: Column(c.data[perm], c.mask[perm], c.type, c.dictionary)
        for n, c in batch.columns.items()
    }
    return h[perm], h2[perm], Batch(cols, batch.row_valid[perm])


def build_for_backend(batch: Batch, key_names: Tuple[str, ...],
                      radix_bits: Optional[int] = None) -> BuildTable:
    """Index the build side, with the sort done where it is cheapest
    and the radix metadata measured on the way out.

    On CPU the hash order comes from a HOST numpy argsort between two
    jitted kernels (XLA:CPU's sort runs ~600ns/element; numpy is ~4x
    faster and the build runs at operator level where an eager host
    step is legal — pure_callback inside jit deadlocks against the
    driver's blocking reads, see ops/common.py), and the bucket
    offsets/run lengths are linear numpy passes. On TPU: the
    one-dispatch device build plus one tiny fetch (max bucket span +
    max run length) — legal here for the same operator-level reason.

    `radix_bits` overrides the size-derived k (0 forces the
    whole-table search — the pre-radix shape)."""
    k = choose_radix_bits(batch.capacity) if radix_bits is None \
        else max(0, min(int(radix_bits), MAX_RADIX_BITS))
    if not common.cpu_backend():
        sh, h2, part_starts, run_len, vc, sbatch, spans = \
            _build_sorted(batch, key_names, k)
        max_span, max_run = (int(x) for x in pages.to_host(spans))
        return BuildTable(sh, h2, part_starts, run_len, vc, sbatch,
                          radix_bits=k,
                          search_depth=_bucket_depth(
                              common.search_iters(max_span)),
                          unique_runs=max_run <= 1)
    h, h2 = _build_hash(batch, key_names)
    hn = pages.to_host(h)
    perm = np.argsort(hn, kind="stable")
    sh_np = hn[perm]
    n = sh_np.shape[0]
    first_inv = int(np.searchsorted(sh_np, np.iinfo(np.int64).max,
                                    side="left"))
    # live rows = everything before the sentinel run (a valid row
    # hashing to exactly int64.max miscounts here at 2^-64 odds; the
    # count only feeds diagnostics)
    vc = jnp.asarray(first_inv, jnp.int64)
    if k > 0:
        # O(n) bucket histogram instead of 2^k binary searches
        bucket = (sh_np >> np.int64(64 - k)) + np.int64(1 << (k - 1))
        counts = np.bincount(bucket, minlength=1 << k)
        part_starts = np.empty((1 << k) + 1, np.int64)
        part_starts[0] = 0
        np.cumsum(counts, out=part_starts[1:])
    else:
        part_starts = np.asarray([0, n], np.int64)
    np.minimum(part_starts, first_inv, out=part_starts)
    max_span = int(np.max(np.diff(part_starts))) if n else 0
    # run lengths via run starts (linear passes, no n-wide search)
    run_len = np.zeros(n, np.int64)
    max_run = 0
    if n:
        head = np.empty(n, bool)
        head[0] = True
        np.not_equal(sh_np[1:], sh_np[:-1], out=head[1:])
        starts_idx = np.flatnonzero(head)
        lens = np.diff(np.append(starts_idx, n))
        run_len[starts_idx] = lens
        vstarts = starts_idx < first_inv
        if vstarts.any():
            vlens = np.minimum(starts_idx + lens, first_inv) - starts_idx
            max_run = int(vlens[vstarts].max())
    sh, sh2, sbatch = _build_apply_perm(batch, h, h2,
                                        jnp.asarray(perm))
    return BuildTable(sh, sh2, jnp.asarray(part_starts),
                      jnp.asarray(run_len), vc, sbatch,
                      radix_bits=k,
                      search_depth=_bucket_depth(
                          common.search_iters(max_span)),
                      unique_runs=max_run <= 1)


def build(batch: Batch, key_names: Tuple[str, ...],
          radix_bits: Optional[int] = None) -> BuildTable:
    """Operator-level build entry point (alias kept for tests/callers
    of the pre-radix API)."""
    return build_for_backend(batch, key_names, radix_bits)


# ---------------------------------------------------------------------------
# The DIRECT layout: one unique integer key, `key - min` is the address
# (the array join of DuckDB's perfect hash join / HyPer). Nothing is
# hashed, sorted or permuted; see docs/JOIN_KERNEL.md, "The direct
# layout". The choice is the operator's (operators/join_ops.py,
# HashBuildOperator.finish), from what the build side itself shows.


@functools.partial(_kernels.jit, family="join_build", part="stats")
def key_stats_step(stats: jnp.ndarray, data: jnp.ndarray,
                   mask: jnp.ndarray, row_valid: jnp.ndarray):
    """Fold one build batch into int64 [rows, min key, max key]: rows
    counts `row_valid` (NULL keys included: they occupy the table),
    min/max read live non-NULL keys only. One array, so the operator's
    one blocking fetch at finish brings all three."""
    live = row_valid & mask
    k = data.astype(jnp.int64)
    info = jnp.iinfo(jnp.int64)
    return jnp.stack([
        stats[0] + jnp.sum(row_valid, dtype=jnp.int64),
        jnp.minimum(stats[1], jnp.min(jnp.where(live, k, info.max))),
        jnp.maximum(stats[2], jnp.max(jnp.where(live, k, info.min)))])


def key_stats_init() -> np.ndarray:
    """No rows, and the empty range (min > max) that no key is in."""
    info = np.iinfo(np.int64)
    return np.asarray([0, info.max, info.min], np.int64)


def direct_table_len(key_min: int, key_max: int,
                     capacity: int) -> Optional[int]:
    """HOST: the direct table's length for live keys in [key_min,
    key_max] — their spread rounded up to a power of two, a static
    shape, so a build side compiles a handful of variants — or None
    when the spread is too wide for `capacity` rows (the sorted layout
    then). An empty range (min > max: no live key) takes length 1."""
    spread = max(int(key_max) - int(key_min) + 1, 1)
    if spread > min(DIRECT_MAX_SPREAD_FACTOR * capacity,
                    DIRECT_MAX_SPREAD):
        return None
    return 1 << (spread - 1).bit_length()


@functools.partial(_kernels.jit, family="join_build", part="direct",
                   static_argnums=(1, 3))
def _build_direct(batch: Batch, key_name: str, stats: jnp.ndarray,
                  table_len: int):
    """Device build: ONE scatter `slot_of[key - min] = row` over the
    live rows and one gather back. A slot two rows wrote keeps one of
    them, so the other reads back a row that is not itself: the
    all-reduce of `slot_of[key - min] == row` is the uniqueness flag.
    `stats` is key_stats_step's array: min and max stay device values
    (a different literal or seed compiles nothing)."""
    c = batch.columns[key_name]
    live = batch.row_valid & c.mask
    key_min, key_max = stats[1], stats[2]
    rows = jnp.arange(live.shape[0], dtype=jnp.int32)
    # dead rows address one past the end: dropped by the scatter
    at = jnp.where(live, c.data.astype(jnp.int64) - key_min,
                   table_len).astype(jnp.int32)
    slot_of = jnp.full(table_len, -1, jnp.int32).at[at].set(
        rows, mode="drop")
    back = slot_of[jnp.minimum(at, table_len - 1)]
    unique = jnp.all(~live | (back == rows))
    return slot_of, key_min, key_max, jnp.sum(live), unique


def build_direct(batch: Batch, key_name: str, stats: jnp.ndarray,
                 table_len: int) -> Optional[BuildTable]:
    """The direct table over `batch` (rows stay in arrival order), or
    None when two live rows share a key: the caller then builds the
    sorted layout. One tiny fetch (the uniqueness flag), where the
    sorted build fetches its span and run maxima."""
    slot_of, key_min, key_max, vc, unique = _build_direct(
        batch, key_name, stats, table_len)
    if not bool(pages.to_host(unique)):
        return None
    return BuildTable(None, None, None, None, vc, batch,
                      unique_runs=True, layout="direct",
                      slot_of=slot_of, key_min=key_min, key_max=key_max)


def _direct_enc(table: BuildTable, probe: Batch,
                probe_keys: Tuple[str, ...]) -> jnp.ndarray:
    """Per probe row: the build batch row holding its key, or -1.
    Exact by construction (a slot holds the one row with that key), so
    no verify mode applies. The range test comes BEFORE the
    subtraction, so an extreme int64 key cannot wrap into the table."""
    (name,) = probe_keys
    data, mask = probe.columns[name].astuple()
    if jnp.issubdtype(data.dtype, jnp.floating):
        # the sorted layout hashes a float's bit pattern, which no
        # integer build key shares: nothing matches there either
        return jnp.full(data.shape, -1, jnp.int32)
    k = data.astype(jnp.int64)
    in_range = probe.row_valid & mask \
        & (k >= table.key_min) & (k <= table.key_max)
    at = jnp.where(in_range, k - table.key_min, 0).astype(jnp.int32)
    brow = table.slot_of[at]
    return jnp.where(in_range & (brow >= 0), brow, jnp.int32(-1))


#: the CPU path's search dispatch for a direct table (XLA:TPU fuses it
#: into the probe program)
_direct_jit = _kernels.jit(_direct_enc, "join_probe", "direct",
                          static_argnums=(2,))


# ---------------------------------------------------------------------------
# Probe stage 1: candidate search. On CPU it runs as TWO dispatches
# (hash, then search) each with ONE expensive output, so XLA:CPU's
# fusion emitter cannot re-materialize the hash chain into every
# search level or the search chain into every expand output.


def _probe_hashes(probe: Batch, probe_keys: Tuple[str, ...]):
    """(h, h2) for the probe keys, with the INVALID sentinels folded
    in: a NULL-key/dead probe row carries (_H_INVALID, _H2_INVALID),
    which cannot match any build row — its hash-MAX candidates were
    clipped out of every search span at build time, so downstream
    stages need no separate validity mask."""
    keys = [probe.columns[k].astuple() for k in probe_keys]
    valid = probe.row_valid
    for _, m in keys:
        valid = valid & m
    h = jnp.where(valid, common.row_hash(keys), _H_INVALID)
    h2 = jnp.where(valid, common.row_hash2(keys), _H2_INVALID)
    return h, h2


#: shared with semi_join; named once, under the family that owns it
_hash_jit = _kernels.jit(_probe_hashes, "join_probe", "hash",
                        static_argnums=(1,))


def _search_enc(table: BuildTable, h: jnp.ndarray, h2: jnp.ndarray,
                verify: str) -> jnp.ndarray:
    """Per probe row: the build slot of its candidate run start, or -1
    when there is none. For unique-run builds the second-hash
    verification folds in here — the single candidate is confirmed or
    rejected on the spot, so the expand stage needs no per-slot
    verify at all (verify="full" defers to the expand stage, which
    owns the build-side key names)."""
    n = table.sorted_hash.shape[0]
    k = table.radix_bits
    if k > 0:
        pid = _partition_of(h, k)
        lo0 = table.part_starts[pid]
        hi0 = table.part_starts[pid + 1]
    else:
        # whole-table mode still honors the invalid-tail clip baked
        # into part_starts ([0, first_invalid)) — the measured search
        # depth covers exactly that span
        lo0 = jnp.zeros(h.shape, jnp.int64)
        hi0 = jnp.broadcast_to(table.part_starts[-1], h.shape)
    lo = common.bounded_searchsorted(table.sorted_hash, h, lo0, hi0,
                                     table.search_depth, side="left")
    loc = jnp.clip(lo, 0, n - 1)
    found = (lo < hi0) & (table.sorted_hash[loc] == h)
    if table.unique_runs and verify == "hash":
        found = found & (table.hash2[loc] == h2)
    return jnp.where(found, lo, jnp.int64(-1))


_search_jit = _kernels.jit(_search_enc, "join_probe", "search",
                          static_argnums=(3,))


def _candidates_enc(table: BuildTable, probe: Batch,
                    probe_keys: Tuple[str, ...],
                    verify: str = "hash") -> jnp.ndarray:
    """Traceable single-region composition (the TPU fused path).
    The table's layout is static: a direct table takes one gather and
    hashes nothing."""
    if table.layout == "direct":
        return _direct_enc(table, probe, probe_keys)
    h, h2 = _probe_hashes(probe, probe_keys)
    return _search_enc(table, h, h2, verify)


def _candidates_cpu(table: BuildTable, probe: Batch,
                    probe_keys: Tuple[str, ...],
                    verify: str = "hash") -> jnp.ndarray:
    """Two-dispatch composition (the CPU path) — still zero host
    syncs, the stages just materialize their one hot output each."""
    if table.layout == "direct":
        return _direct_jit(table, probe, probe_keys)
    h, h2 = _hash_jit(probe, probe_keys)
    return _search_jit(table, h, h2, verify)


def probe_counts(table: BuildTable, probe: Batch,
                 probe_keys: Tuple[str, ...]):
    """Per-probe-row candidate run [lo, hi) in the sorted build, plus
    the candidate count (collisions included; exact verification
    happens in expand — totals for capacity use hi-lo, an upper
    bound). `probe_keys` name the probe batch's key columns (build key
    names may differ — symbols are per-side in the planner).

    Compat surface for tests/operators that stage the probe manually;
    the fused probe_join path never materializes hi."""
    lo_enc = _candidates_cpu(table, probe, probe_keys, "full")
    return _counts_jit(table, probe, probe_keys, lo_enc)


@functools.partial(_kernels.jit, family="join_probe", part="counts",
                   static_argnums=(2,))
def _counts_jit(table, probe, probe_keys, lo_enc):
    keys = [probe.columns[k].astuple() for k in probe_keys]
    valid = probe.row_valid
    for _, m in keys:
        valid = valid & m
    found = lo_enc >= 0
    lo = jnp.maximum(lo_enc, 0)
    counts = jnp.where(found, table.run_len[lo], 0)
    lo = jnp.where(found, lo, 0)
    return lo, lo + counts, counts, valid


def expand(table: BuildTable, probe: Batch, key_names,
           lo, hi, counts, probe_key_valid,
           out_capacity: int, join_type: str = "inner",
           probe_prefix: str = "", build_prefix: str = "",
           build_output: Optional[Sequence[str]] = None,
           probe_output: Optional[Sequence[str]] = None,
           build_keys: Optional[Sequence[str]] = None,
           verify: str = "full") -> Batch:
    """Materialize join output rows with a static `out_capacity`
    (compat surface over the general expand path).

    Output slot j belongs to probe row p(j) = searchsorted(cum, j) where
    cum is the exclusive prefix sum of per-probe output counts; its build
    candidate is build_slot = lo[p] + (j - cum[p]). Collision candidates
    are masked out by the second-hash compare (or the full-key compare
    under verify="full")."""
    if build_keys is not None:
        assert len(build_keys) == len(key_names), \
            "probe/build key lists must have equal length"
    out, _ = _expand_general_jit(
        table, probe, tuple(key_names), lo, counts, probe_key_valid,
        out_capacity, join_type,
        tuple(probe_output if probe_output is not None
              else probe.names),
        tuple(build_output if build_output is not None
              else table.batch.names),
        probe_prefix, build_prefix,
        tuple(build_keys) if build_keys is not None
        else tuple(key_names), verify)
    return out


@functools.partial(_kernels.jit, family="join_probe",
                   part="expand_general",
                   static_argnums=(2, 6, 7, 8, 9, 10, 11, 12,
                                            13))
def _expand_general_jit(table, probe, key_names, lo, counts,
                        probe_key_valid, out_capacity, join_type,
                        probe_output, build_output, probe_prefix,
                        build_prefix, build_keys, verify):
    out, overflow, _, _ = _expand_general(
        table, probe, key_names, lo, counts, out_capacity, join_type,
        probe_output, build_output, probe_prefix, build_prefix,
        build_keys, verify)
    return out, overflow


def probe_join(table: BuildTable, probe: Batch,
               key_names: Tuple[str, ...], out_capacity: int,
               join_type: str, probe_output: Tuple[str, ...],
               build_output: Tuple[str, ...],
               build_keys: Tuple[str, ...], verify: str = "hash"
               ) -> Tuple[Batch, jnp.ndarray, jnp.ndarray]:
    """Fused probe with NO host sync — the output capacity is chosen
    by the CALLER (typically probe capacity x an expansion factor).
    One dispatch on TPU; two on CPU (see module docstring). Returns
    (output batch, overflow flag, live output rows), all on device:

    - `overflow` records whether the true output exceeded out_capacity;
      the operator accumulates it across batches and the runner checks
      ONCE per query, retrying with a larger factor (the same sync-free
      protocol as GroupLimitExceeded). The aligned layout cannot
      overflow — it returns a constant False.
    - the live-row count backs the operator's one-round-delayed
      output compaction (its d2h copy starts immediately, so the read
      a driver round later is normally a cache hit)."""
    if common.cpu_backend():
        if table.layout == "direct":
            lo_enc, h2 = _direct_jit(table, probe, key_names), None
        else:
            h, h2 = _hash_jit(probe, key_names)
            lo_enc = _search_jit(table, h, h2, verify)
        out, overflow, total, _ = _expand_dispatch(
            table, probe, key_names, lo_enc, h2, None, out_capacity,
            join_type, probe_output, build_output, build_keys, verify)
        return out, overflow, total
    out, overflow, total, _ = _probe_join_fused(
        table, probe, key_names, None, out_capacity, join_type,
        probe_output, build_output, build_keys, verify)
    return out, overflow, total


def probe_join_full(table: BuildTable, probe: Batch,
                    key_names: Tuple[str, ...], matched: jnp.ndarray,
                    out_capacity: int, probe_output: Tuple[str, ...],
                    build_output: Tuple[str, ...],
                    build_keys: Tuple[str, ...], verify: str = "hash"):
    """FULL OUTER probe step: identical to a left-join probe (unmatched
    probe rows emit one NULL-build row), plus a scatter-max that folds
    this batch's verified matches into the running per-build-row
    `matched` flags — no host syncs (reference:
    LookupJoinOperator.java:392 + the joinPositionsVisited bitmap
    behind LookupOuterOperator.java:42)."""
    if common.cpu_backend():
        h, h2 = _hash_jit(probe, key_names)
        lo_enc = _search_jit(table, h, h2, verify)
        out, overflow, total, matched = _expand_dispatch(
            table, probe, key_names, lo_enc, h2, matched, out_capacity,
            "full", probe_output, build_output, build_keys, verify)
        return out, overflow, total, matched
    return _probe_join_fused(table, probe, key_names, matched,
                             out_capacity, "full", probe_output,
                             build_output, build_keys, verify)


@functools.partial(_kernels.jit, family="join_probe", part="fused",
                   static_argnums=(2, 4, 5, 6, 7, 8, 9))
def _probe_join_fused(table, probe, key_names, matched, out_capacity,
                      join_type, probe_output, build_output, build_keys,
                      verify):
    lo_enc = _candidates_enc(table, probe, key_names, verify)
    return _expand_from_enc(table, probe, key_names, lo_enc, matched,
                            out_capacity, join_type, probe_output,
                            build_output, build_keys, verify)


@functools.partial(_kernels.jit, family="join_probe", part="expand",
                   static_argnums=(2, 6, 7, 8, 9, 10, 11))
def _expand_dispatch(table, probe, key_names, lo_enc, h2, matched,
                     out_capacity, join_type, probe_output,
                     build_output, build_keys, verify):
    return _expand_from_enc(table, probe, key_names, lo_enc, matched,
                            out_capacity, join_type, probe_output,
                            build_output, build_keys, verify, h2=h2)


def aligned_expansion(table: BuildTable, join_type: str,
                      out_capacity: int, probe_capacity: int) -> bool:
    """STATIC: does this probe take the aligned layout (output slot i
    is probe row i)? Every build hash run has length 1 and the output
    is as wide as the probe batch."""
    return (table.unique_runs
            and join_type in ("inner", "left", "full")
            and out_capacity == probe_capacity)


def _expand_from_enc(table, probe, key_names, lo_enc, matched,
                     out_capacity, join_type, probe_output,
                     build_output, build_keys, verify, h2=None):
    """Traceable expand stage: picks the aligned or general layout (a
    STATIC choice) and folds the FULL join's matched-flag update.
    `h2` carries stage 1's probe hash2 across the CPU dispatch
    boundary so the hash-verify doesn't rehash the key columns (None
    on the fused TPU path, where XLA CSEs the recompute away)."""
    aligned = aligned_expansion(table, join_type, out_capacity,
                                probe.row_valid.shape[0])
    # a direct table has no hash runs to expand: its builder promised
    # a consumer that reads it aligned (HashBuildOperator.finish)
    assert aligned or table.layout != "direct", \
        f"direct build table probed unaligned: {join_type} join, " \
        f"capacity {out_capacity} for {probe.row_valid.shape[0]} rows"
    if aligned:
        # both halves under the caller's one program, at the batch's
        # own width: every slot stays where its probe row is
        side, brow, verified, overflow, live, matched = aligned_front(
            table, probe, key_names, lo_enc, matched, join_type,
            probe_output, build_keys, verify)
        out = aligned_back(table.batch, side, brow, verified, live,
                           out_capacity, join_type, build_output)
        return out, overflow, live, matched
    found = lo_enc >= 0
    lo = jnp.maximum(lo_enc, 0)
    counts = jnp.where(found, table.run_len[lo], 0)
    out, overflow, brow, verified = _expand_general(
        table, probe, key_names, lo, counts, out_capacity,
        join_type, probe_output, build_output, "", "", build_keys,
        verify, h2=h2)
    if join_type == "full" and matched is not None:
        matched = matched.at[brow].max(verified, mode="drop")
    return out, overflow, jnp.sum(out.row_valid), matched


# -- the aligned layout, materialized late ------------------------------
#
# A unique-run (or direct) build answers each probe row with at most
# one build row, so output slot i IS probe row i and nothing expands.
# The work splits where the live rows become known: the FRONT searches
# at the batch's width and counts, the BACK gathers probe and build
# columns at the width the count allows. An operator that waits for
# the count between them (LookupJoinOperator) pays the payload gathers
# over the live rows' bucket only; under one program at the batch's
# width the back is the identity pack (docs/JOIN_KERNEL.md).


def aligned_front(table, probe, key_names, lo_enc, matched, join_type,
                  probe_output, build_keys, verify):
    """The search's half: per probe row the build row (`brow`, int32)
    and whether it matched (`verified`), the FULL join's matched-flag
    scatter, and the count of output rows. No build OUTPUT column is
    read; under verify="full" the build KEY columns are (the compare
    is the search's business). An inner miss is a dead slot; a
    left/full miss keeps its probe row. Returns (the probe side's
    output columns, brow, verified, overflow, live count, matched);
    the aligned layout emits at most one row a probe row, so the
    overflow flag is constant False."""
    verified = lo_enc >= 0
    brow = jnp.maximum(lo_enc, 0).astype(jnp.int32)
    if verify == "full":
        # collision-fallback oracle: one candidate per row, compare
        # the actual key columns (stage 1 verified nothing)
        for kn, bn in zip(key_names, build_keys):
            pd, pm = probe.columns[kn].astuple()
            bd, bm = table.batch.columns[bn].astuple()
            verified = verified & (pd == bd[brow]) & pm & bm[brow]
    if join_type == "full" and matched is not None:
        matched = matched.at[brow].max(verified, mode="drop")
    live = probe.row_valid if join_type in ("left", "full") \
        else verified
    return probe.select(probe_output), brow, verified, \
        jnp.asarray(False), jnp.sum(live), matched


def aligned_back(build: Batch, side: Batch, brow, verified, live,
                 width: int, join_type: str, build_output) -> Batch:
    """The payload's half, at STATIC `width` lanes: the first `live`
    output rows of the front, in probe order. `side`, `brow`,
    `verified` and `live` are aligned_front's; `build` is the table's
    batch. At the probe batch's own width nothing moves (slot i stays
    probe row i); below it the live rows pack to the front
    (`first_true_indices` is ascending), the caller having chosen a
    width that holds them. Probe columns are gathered at the live
    rows, build columns at their build rows; a left/full miss keeps a
    NULL build side."""
    cap = side.row_valid.shape[0]
    live_mask = side.row_valid if join_type in ("left", "full") \
        else verified
    if width == cap:
        idx, row_valid = slice(None), live_mask
    else:
        idx = common.first_true_indices(live_mask, width, cap - 1)
        row_valid = jnp.arange(width) < live
        brow, verified = brow[idx], verified[idx] & row_valid
    cols: Dict[str, Column] = {}
    for name, c in side.columns.items():
        cols[name] = Column(c.data[idx], c.mask[idx] & row_valid,
                            c.type, c.dictionary)
    for name in build_output:
        c = build.columns[name]
        cols[name] = Column(c.data[brow], c.mask[brow] & verified,
                            c.type, c.dictionary)
    return Batch(cols, row_valid)


def _expand_general(table, probe, key_names, lo, counts, out_capacity,
                    join_type, probe_output, build_output, probe_prefix,
                    build_prefix, build_keys, verify, h2=None):
    """Prefix-sum expansion for duplicate-key builds: output slot j
    belongs to probe row p(j), candidate build_slot = lo[p] + (j -
    cum[p]). Returns (batch, overflow, brow, verified) — brow/verified
    feed the FULL join's matched-flag scatter."""
    assert verify in VERIFY_MODES, f"unknown verify mode {verify!r}"
    left_join = join_type in ("left", "full")
    # per-probe emitted rows: matches, or 1 unmatched row for LEFT
    emit = counts
    if left_join:
        emit = jnp.where(probe.row_valid & (counts == 0), 1, counts)
        emit = jnp.where(probe.row_valid, emit, 0)
    cum = common.prefix_sum(emit, emit.dtype) - emit  # exclusive prefix
    total = cum[-1] + emit[-1] if emit.shape[0] else jnp.asarray(0)

    slots = jnp.arange(out_capacity)
    # which probe row does output slot j come from? TPU: binary search
    # on the monotone prefix. CPU: expand-by-counts — scatter a 1 at
    # each probe's run start and prefix-sum (two linear passes instead
    # of log2(cap) full-width gather rounds)
    if common.cpu_backend():
        heads = jnp.zeros(out_capacity + 1, jnp.int64).at[
            jnp.clip(cum, 0, out_capacity)].add(1, mode="drop")
        pid = common.prefix_sum(heads[:out_capacity]) - 1
    else:
        pid = common.fast_searchsorted(cum, slots, side="right") - 1
    pid = jnp.clip(pid, 0, emit.shape[0] - 1)
    k = slots - cum[pid]                      # k-th emission of that row
    slot_live = slots < total
    is_match = slot_live & (k < counts[pid])
    # build rows are stored in sorted-hash order: the candidate slot IS
    # the row index (near-contiguous gathers within each hash run)
    brow = jnp.clip(lo[pid] + k, 0, table.sorted_hash.shape[0] - 1)

    # verify candidates. "hash": the search hash already matched
    # (candidates come from the probe hash's own run), so one compare
    # of the second independent hash confirms the key — 2 gathers
    # total instead of 4 per key column. "full": the pre-radix
    # per-key-column compare (collision fallback / test oracle).
    if verify == "hash":
        h2p = h2 if h2 is not None else common.row_hash2(
            [probe.columns[kn].astuple() for kn in key_names])
        verified = is_match & (h2p[pid] == table.hash2[brow])
    else:
        verified = is_match
        for kn, bn in zip(key_names, build_keys):
            pd, pm = probe.columns[kn].astuple()
            bd, bm = table.batch.columns[bn].astuple()
            same = (pd[pid] == bd[brow]) & pm[pid] & bm[brow]
            verified = verified & same

    if left_join:
        # a probe row with zero *verified* matches must still emit one
        # NULL-build row — including when all its hash-run candidates
        # failed key verification (collision). Reuse its k==0 slot.
        any_verified = jax.ops.segment_max(
            verified.astype(jnp.int32), pid,
            num_segments=emit.shape[0], indices_are_sorted=True) > 0
        unmatched = slot_live & (k == 0) & ~any_verified[pid] \
            & probe.row_valid[pid]
        live = verified | unmatched
    else:
        live = verified

    cols: Dict[str, Column] = {}
    for name in probe_output:
        c = probe.columns[name]
        cols[probe_prefix + name] = Column(
            c.data[pid], c.mask[pid] & live, c.type, c.dictionary)
    for name in build_output:
        c = table.batch.columns[name]
        bmask = c.mask[brow] & verified  # NULL build side on unmatched
        cols[build_prefix + name] = Column(c.data[brow], bmask, c.type,
                                           c.dictionary)
    return Batch(cols, live), total > out_capacity, brow, verified


@functools.partial(_kernels.jit, family="join_outer",
                   static_argnums=(2, 3))
def unmatched_build(table: BuildTable, matched: jnp.ndarray,
                    probe_schema: Tuple[Tuple, ...],
                    build_output: Tuple[str, ...]):
    """The FULL join's final batch: build rows no probe row ever
    matched, probe side all-NULL (reference: LookupOuterOperator's
    appendTo loop). `probe_schema` is ((name, type, dictionary), ...)
    for the NULL probe columns. Returns (batch, live_count)."""
    live = table.batch.row_valid & ~matched
    n = matched.shape[0]
    cols: Dict[str, Column] = {}
    for name, typ, dic in probe_schema:
        cols[name] = Column(jnp.zeros(n, dtype=typ.np_dtype),
                            jnp.zeros(n, dtype=bool), typ, dic)
    for name in build_output:
        c = table.batch.columns[name]
        cols[name] = Column(c.data, c.mask & live, c.type, c.dictionary)
    return Batch(cols, live), jnp.sum(live)


def semi_mark(table: BuildTable, probe: Batch,
              key_names: Tuple[str, ...],
              build_keys: Optional[Tuple[str, ...]] = None,
              verify: str = "hash"):
    """For each probe row: does any build row share its key? One
    bounded search into the row's radix bucket finds the candidate
    run. Unique-run builds are fully resolved by that search (the
    verification folded into stage 1); duplicate-run builds confirm
    the first UNROLL candidates with straight-line second-hash
    gathers and scan any longer runs with an on-device
    `lax.while_loop` — no host sync. Under verify="hash" a false
    IN/EXISTS match needs a SIMULTANEOUS collision in two independent
    64-bit hashes (see docs/JOIN_KERNEL.md); verify="full" keeps the
    exact per-key-column compare of the pre-radix kernel."""
    assert verify in VERIFY_MODES, f"unknown verify mode {verify!r}"
    build_keys = build_keys or key_names
    assert len(build_keys) == len(key_names), \
        "probe/build key lists must have equal length"
    if table.unique_runs and verify == "hash":
        if common.cpu_backend():
            lo_enc = _candidates_cpu(table, probe, key_names, verify)
            return _semi_from_enc(probe, key_names, lo_enc)
        return _semi_unique_fused(table, probe, key_names)
    if common.cpu_backend():
        lo_enc = _candidates_cpu(table, probe, key_names, "full")
        return _semi_scan_jit(table, probe, key_names, lo_enc,
                              tuple(build_keys), verify)
    return _semi_fused(table, probe, key_names, tuple(build_keys),
                       verify)


@functools.partial(_kernels.jit, family="semi_join", part="unique",
                   static_argnums=(2,))
def _semi_unique_fused(table: BuildTable, probe: Batch, key_names):
    """Unique-run membership in ONE dispatch (TPU): the search stage's
    folded second-hash verification fully resolves each probe row."""
    lo_enc = _candidates_enc(table, probe, key_names, "hash")
    return _semi_resolve(probe, key_names, lo_enc)


def _semi_resolve(probe: Batch, key_names, lo_enc):
    keys = [probe.columns[k].astuple() for k in key_names]
    valid = probe.row_valid
    for _, m in keys:
        valid = valid & m
    return (lo_enc >= 0) & valid, valid


_semi_from_enc = _kernels.jit(_semi_resolve, "semi_join", "resolve",
                             static_argnums=(1,))


@functools.partial(_kernels.jit, family="semi_join", part="fused",
                   static_argnums=(2, 3, 4))
def _semi_fused(table, probe, key_names, build_keys, verify):
    lo_enc = _candidates_enc(table, probe, key_names, verify)
    return _semi_scan(table, probe, key_names, lo_enc, build_keys,
                      verify)


@functools.partial(_kernels.jit, family="semi_join", part="scan",
                   static_argnums=(2, 4, 5))
def _semi_scan_jit(table, probe, key_names, lo_enc, build_keys,
                   verify):
    return _semi_scan(table, probe, key_names, lo_enc, build_keys,
                      verify)


def _semi_scan(table, probe, key_names, lo_enc, build_keys, verify):
    """Exact membership over duplicate-hash runs: scan each probe
    row's candidate run until a verified match or the run ends."""
    keys = [probe.columns[k].astuple() for k in key_names]
    valid = probe.row_valid
    for _, m in keys:
        valid = valid & m
    found0 = lo_enc >= 0
    lo = jnp.maximum(lo_enc, 0)
    counts = jnp.where(found0, table.run_len[lo], 0)
    hi = lo + counts
    nbuild = table.sorted_hash.shape[0]
    if verify == "hash":
        h2p = common.row_hash2(keys)
        bcols = None
    else:
        bcols = [table.batch.columns[bn].astuple() for bn in build_keys]

    def check_at(i, found):
        """found |= (probe key == build key at run offset i)."""
        brow = jnp.clip(lo + i, 0, nbuild - 1)
        in_run = (lo + i) < hi
        same = in_run & valid
        if verify == "hash":
            same = same & (table.hash2[brow] == h2p)
        else:
            for (pd, pm), (bd, bm) in zip(keys, bcols):
                same = same & (pd == bd[brow]) & pm & bm[brow]
        return found | same

    UNROLL = 4
    found = jnp.zeros_like(valid)
    for i in range(UNROLL):
        found = check_at(i, found)

    def cond(state):
        i, found = state
        # a row still needs scanning while its run extends past i and
        # no match has been confirmed yet
        return jnp.any(((lo + i) < hi) & valid & ~found)

    def body(state):
        i, found = state
        return i + 1, check_at(i, found)

    _, found = jax.lax.while_loop(
        cond, body, (jnp.asarray(UNROLL, jnp.int32), found))
    return found & valid, valid


# -- instrumented public entry points ---------------------------------
#
# Compile-vs-execute attribution for the join kernel families, same
# contract as ops/sort.py: the operator-facing host entry points wrap
# with instrument_kernel, and the `jits=[...]` lists name every
# module-level jit an entry point composes so all executable caches
# are polled for compile detection (the operator-layer probe kernels
# in operators/join_ops.py register their own per-plan jits the same
# way). The *_impl jits above stay unwrapped so they can compose into
# other traces without double accounting.
_instr = _kernels.instrument_kernel

build_for_backend = _instr(
    build_for_backend, "join_build",
    jits=[_build_sorted, _build_hash, _build_apply_perm])
build_direct = _instr(build_direct, "join_build", jits=[_build_direct])
key_stats_step = _instr(key_stats_step, "join_build")
probe_join = _instr(
    probe_join, "join_probe",
    jits=[_hash_jit, _search_jit, _direct_jit, _expand_dispatch,
          _probe_join_fused, _expand_general_jit])
probe_join_full = _instr(
    probe_join_full, "join_probe",
    jits=[_hash_jit, _search_jit, _expand_dispatch,
          _probe_join_fused, _expand_general_jit])
probe_counts = _instr(
    probe_counts, "join_probe",
    jits=[_hash_jit, _search_jit, _counts_jit])
semi_mark = _instr(
    semi_mark, "semi_join",
    jits=[_hash_jit, _search_jit, _semi_from_enc, _semi_scan_jit,
          _semi_fused, _semi_unique_fused])
unmatched_build = _instr(unmatched_build, "join_outer")


# -- kernel contracts (tools/kernelcheck.py) ---------------------------
#
# The probe families are checked against the PROBE batch's dead lanes;
# BuildTable metadata (sorted hashes, bucket offsets, run lengths) is
# role "clean" by the modular contract — join_build's OWN contract
# proves those arrays are sentinel-canonical for dead build rows, so
# the probe may assume it (the invalid-tail clip + _H_INVALID design).
# Build BATCH columns keep the "data" role: gathered build values must
# stay mask-guarded in the probe output.
from presto_tpu.analysis.contracts import (
    KernelContract, TracePoint, abstract_batch, register_contract,
)


def _abstract_table(n: int, k: int, unique: bool, depth: int = 8):
    from presto_tpu.analysis.contracts import sds
    from presto_tpu.types import BIGINT, DOUBLE
    import numpy as _np
    batch, rbatch = abstract_batch(n, [("bk", BIGINT), ("bv", DOUBLE)])
    t = BuildTable(sds((n,), _np.int64), sds((n,), _np.int64),
                   sds(((1 << k) + 1,), _np.int64),
                   sds((n,), _np.int64), sds((), _np.int64), batch,
                   radix_bits=k, search_depth=depth,
                   unique_runs=unique)
    rt = BuildTable("clean", "clean", "clean", "clean", "clean",
                    rbatch, radix_bits=k, search_depth=depth,
                    unique_runs=unique)
    return t, rt


def _abstract_direct_table(n: int, table_len: int):
    from presto_tpu.analysis.contracts import sds
    from presto_tpu.types import BIGINT, DOUBLE
    import numpy as _np
    batch, rbatch = abstract_batch(n, [("bk", BIGINT), ("bv", DOUBLE)])
    scalar = sds((), _np.int64)
    t = BuildTable(None, None, None, None, scalar, batch,
                   unique_runs=True, layout="direct",
                   slot_of=sds((table_len,), _np.int32),
                   key_min=scalar, key_max=scalar)
    # join_build's direct contract proves slot_of addresses live rows
    # only (dead rows scatter out of range), so the probe may assume it
    rt = BuildTable(None, None, None, None, "clean", rbatch,
                    unique_runs=True, layout="direct", slot_of="clean",
                    key_min="clean", key_max="clean")
    return t, rt


def _probe_schema():
    from presto_tpu.types import BIGINT, DOUBLE
    return [("pk", BIGINT), ("pv", DOUBLE)]


def _build_direct_point(cap, variant):
    from presto_tpu.analysis.contracts import sds
    import numpy as _np
    b, rb = abstract_batch(cap, _probe_schema())
    return TracePoint(
        lambda bb, st: _build_direct(bb, "pk", st, 8 * cap),
        (b, sds((3,), _np.int64)), (rb, "clean"))


def _key_stats_point(cap, variant):
    from presto_tpu.analysis.contracts import sds
    import numpy as _np
    b, rb = abstract_batch(cap, [("pk", _probe_schema()[0][1])])
    c, rc = b.columns["pk"], rb.columns["pk"]
    return TracePoint(
        lambda st, d, m, rv: key_stats_step.__wrapped__(st, d, m, rv),
        (sds((3,), _np.int64), c.data, c.mask, b.row_valid),
        ("clean", rc.data, rc.mask, rb.row_valid))


def _probe_direct_point(cap, variant):
    t, rt = _abstract_direct_table(4096, 32768)
    p, rp = abstract_batch(cap, _probe_schema())
    jt = variant.get("join_type", "inner")
    return TracePoint(
        lambda tt, pp: _probe_join_fused(
            tt, pp, ("pk",), None, cap, jt, ("pk", "pv"), ("bv",),
            ("bk",), "hash"),
        (t, p), (rt, rp))


def _materialize_point(cap, variant):
    from presto_tpu.analysis.contracts import sds
    import numpy as _np
    t, rt = _abstract_direct_table(4096, 32768)
    p, rp = abstract_batch(cap, _probe_schema())
    jt = variant.get("join_type", "inner")
    # the front's outputs: its own contracts prove brow addresses a
    # live build row and verified is False wherever the probe is dead
    return TracePoint(
        lambda bb, pp, brow, verified, live: aligned_back(
            bb, pp, brow, verified, live, cap // 4, jt, ("bv",)),
        (t.batch, p, sds((cap,), _np.int32), sds((cap,), _np.bool_),
         sds((), _np.int64)),
        (rt.batch, rp, "clean", "mask", "clean"))


def _build_point(cap, variant):
    b, rb = abstract_batch(cap, _probe_schema())
    which = variant.get("entry", "sorted")
    if which == "sorted":
        return TracePoint(lambda bb: _build_sorted(bb, ("pk",), 8),
                          (b,), (rb,))
    return TracePoint(lambda bb: _build_hash(bb, ("pk",)), (b,), (rb,))


def _build_perm_point(cap, variant):
    from presto_tpu.analysis.contracts import sds
    import numpy as _np
    b, rb = abstract_batch(cap, _probe_schema())
    h = sds((cap,), _np.int64)
    return TracePoint(lambda bb, hh, h2, perm: _build_apply_perm(
        bb, hh, h2, perm),
        (b, h, h, sds((cap,), _np.int64)),
        (rb, "clean", "clean", "clean"))


def _probe_point(cap, variant):
    t, rt = _abstract_table(4096, 8, variant.get("unique", False))
    p, rp = abstract_batch(cap, _probe_schema())
    jt = variant.get("join_type", "inner")
    if jt == "full":
        from presto_tpu.analysis.contracts import sds
        import numpy as _np
        m = sds((4096,), _np.bool_)
        return TracePoint(
            lambda tt, pp, mm: _probe_join_fused(
                tt, pp, ("pk",), mm, cap, "full", ("pk", "pv"),
                ("bv",), ("bk",), "hash"),
            (t, p, m), (rt, rp, "clean"))
    return TracePoint(
        lambda tt, pp: _probe_join_fused(
            tt, pp, ("pk",), None, cap, jt, ("pk", "pv"), ("bv",),
            ("bk",), "hash"),
        (t, p), (rt, rp))


def _semi_point(cap, variant):
    unique = variant.get("unique", False)
    t, rt = _abstract_table(4096, 8, unique)
    p, rp = abstract_batch(cap, _probe_schema())
    if unique:
        return TracePoint(
            lambda tt, pp: _semi_unique_fused(tt, pp, ("pk",)),
            (t, p), (rt, rp))
    return TracePoint(
        lambda tt, pp: _semi_fused(tt, pp, ("pk",), ("bk",), "hash"),
        (t, p), (rt, rp))


def _outer_point(cap, variant):
    from presto_tpu.analysis.contracts import sds
    from presto_tpu.types import BIGINT
    import numpy as _np
    t, rt = _abstract_table(cap, 8, False)
    m = sds((cap,), _np.bool_)
    return TracePoint(
        lambda tt, mm: unmatched_build.__wrapped__(
            tt, mm, (("pk", BIGINT, None),), ("bv",)),
        (t, m), (rt, "clean"))


register_contract(KernelContract(
    family="join_build", module=__name__, build=_build_point,
    notes="device build: order by hash + gathers (the TPU path; "
          "traceable on every backend)"))
register_contract(KernelContract(
    family="join_build", module=__name__,
    build=lambda cap, v: _build_point(cap, {"entry": "hash"}),
    notes="hash stage of the CPU host-argsort build"))
register_contract(KernelContract(
    family="join_build", module=__name__, build=_build_perm_point,
    notes="permutation-apply stage of the CPU host-argsort build"))
register_contract(KernelContract(
    family="join_build", module=__name__, build=_build_direct_point,
    notes="direct layout: one scatter by key - min, one gather back "
          "for the uniqueness flag; dead rows scatter out of range"))
register_contract(KernelContract(
    family="join_build", module=__name__, build=_key_stats_point,
    notes="per-batch fold of [rows, min key, max key], the one array "
          "the operator fetches at finish"))
register_contract(KernelContract(
    family="join_probe", module=__name__, build=_probe_point,
    notes="inner probe, general (duplicate-run) expand layout"))
register_contract(KernelContract(
    family="join_probe", module=__name__, build=_probe_direct_point,
    notes="inner probe of a direct table: range test, one int32 "
          "gather, aligned expand"))
register_contract(KernelContract(
    family="join_probe", module=__name__,
    build=lambda cap, v: _probe_direct_point(cap, {"join_type": "left"}),
    notes="left probe of a direct table"))
register_contract(KernelContract(
    family="join_probe", module=__name__,
    build=lambda cap, v: _probe_point(cap, {"join_type": "left"}),
    notes="left probe: adds the unmatched-row pass (a distinct "
          "program per plan shape — join_type is static by design)"))
register_contract(KernelContract(
    family="join_probe", module=__name__,
    build=lambda cap, v: _probe_point(cap, {"join_type": "full"}),
    notes="FULL probe: matched-flag scatter rides the trace"))
register_contract(KernelContract(
    family="join_probe", module=__name__, build=_materialize_point,
    structure_varies=True,
    structure_reason="first_true_indices binary-searches the rank "
                     "prefix: log2(capacity) unrolled rounds on the "
                     "CPU side of fast_searchsorted",
    notes="the aligned probe's back half at a quarter of the batch's "
          "width: live rows packed, both sides' columns gathered"))
register_contract(KernelContract(
    family="join_probe", module=__name__,
    build=lambda cap, v: _materialize_point(cap, {"join_type": "left"}),
    structure_varies=True,
    structure_reason="first_true_indices, as the inner back half",
    notes="left back half: the live rows are the probe's own"))
register_contract(KernelContract(
    family="semi_join", module=__name__, build=_semi_point,
    notes="duplicate-run scan path (bounded unroll + while_loop)"))
register_contract(KernelContract(
    family="semi_join", module=__name__,
    build=lambda cap, v: _semi_point(cap, {"unique": True}),
    notes="unique-run path: verification folded into the search"))
register_contract(KernelContract(
    family="join_outer", module=__name__, build=_outer_point))
