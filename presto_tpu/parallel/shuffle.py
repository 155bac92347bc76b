"""Hash repartitioning as an ICI all_to_all (the TPU-native rebuild of
the reference's shuffle: PartitionedOutputOperator.partitionPage
operator/PartitionedOutputOperator.java:360-417 producing per-consumer
buffers in PartitionedOutputBuffer.java:48, pulled over HTTP by
ExchangeClient.java:81).

A `ShardedBatch` is a Batch whose arrays carry a leading `workers` mesh
axis: global shape [W, rows] sharded so each chip holds one [rows] slice.
Every repartition entry point runs one shard_mapped program per chip
(`_wave_body`), in which rows move as CONTIGUOUS SEGMENTS addressed by
W counts, never lane by lane through an index array over W * rows lanes:

  1. dest[i] = abs(hash(key columns)[i]) mod W, invalid rows to bucket W
     (row -> consumer). One sort of dest gives `order`; the bucket
     sizes are W masked sums `sum(dest == d)`, `offsets` their exclusive
     prefix.
  2. per array ONE gather g = a[order]: the rows grouped by
     destination, source order kept inside a bucket. After it bucket d
     is the contiguous run g[offsets[d] : offsets[d] + counts[d]].
  3. the [W, rows] send buffer is W dynamic_slices of g of length
     `rows` starting at offsets[d], each masked to its count (dead
     lanes zeroed, so nothing a pad lane carried reaches the wire).
     XLA clamps a dynamic_slice whose window runs past the end of its
     operand, and a `rows`-lane window of a `rows`-lane operand can
     only start at 0 — every bucket would silently read bucket 0's
     rows — so the window is cut from g extended by `rows` dead lanes.
     The bucket capacity stays `rows`: a chip holds <= rows live rows,
     so no key skew can overflow a bucket (overflow-free by
     construction: no fallback path, nothing to count).
  4. jax.lax.all_to_all over the `workers` axis swaps buckets so chip d
     receives bucket d from every chip, each already packed to its
     front; the [W] counts cross the same way. row_valid does not
     cross (a segment's count says which lanes are live) and the
     validity masks cross as bits of shared 32-bit lanes.
  5. the pack: a [W * rows] output per array written by W
     dynamic_update_slices in ascending source order, segment s (all
     `rows` lanes of it) at the sum of the received counts before s.
     That start is <= s * rows, so no window clamps, and each write
     overwrites the dead tail of the one before. row_valid =
     iota < total, lanes from `total` on zeroed, count = total. The
     consumer receives source 0's rows, then source 1's, ...

Equal keys land on equal chips, which is the contract partial->final
aggregation, partitioned joins, and distinct rely on. Presto's LZ4
serde + token-acked HTTP long-poll collapses into one XLA collective.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from presto_tpu.batch import Batch, Column, bucket_capacity
from presto_tpu.ops import common
from presto_tpu.parallel.mesh import place, worker_axis
from presto_tpu.telemetry import kernels as _kernels
from presto_tpu.telemetry import ledger


class ShardedBatch:
    """A Batch distributed over the `workers` mesh axis.

    `batch.columns[*].data` has global shape [W * rows_per_worker] with a
    NamedSharding that gives each chip one contiguous [rows_per_worker]
    slice (the analog of one worker's task input queue).
    """

    def __init__(self, batch: Batch, mesh: Mesh,
                 axis: str = worker_axis):
        self.batch = batch
        self.mesh = mesh
        self.axis = axis

    @property
    def n_workers(self) -> int:
        return self.mesh.shape[self.axis]

    @property
    def rows_per_worker(self) -> int:
        return self.batch.capacity // self.n_workers


def _row_sharding(mesh: Mesh, axis: str) -> NamedSharding:
    return NamedSharding(mesh, P(axis))


def shard_batch(batch: Batch, mesh: Mesh,
                axis: str = worker_axis) -> ShardedBatch:
    """Distribute a host/single-device Batch row-wise over the mesh
    (round-robin free: rows are already position-agnostic). Pads the
    capacity up so it divides evenly."""
    w = mesh.shape[axis]
    cap = batch.capacity
    per = -(-cap // w)
    per = bucket_capacity(per)
    target = per * w
    if target != cap:
        batch = batch.compact(target)
    sh = _row_sharding(mesh, axis)
    cols = {
        n: Column(jax.device_put(c.data, sh), jax.device_put(c.mask, sh),
                  c.type, c.dictionary)
        for n, c in batch.columns.items()
    }
    rv = jax.device_put(batch.row_valid, sh)
    return ShardedBatch(Batch(cols, rv), mesh, axis)


def _replicate(batch: Batch, mesh: Mesh) -> Batch:
    """Copy a batch onto every device (replicated sharding)."""
    rep = NamedSharding(mesh, P())
    cols = {
        n: Column(jax.device_put(c.data, rep), jax.device_put(c.mask, rep),
                  c.type, c.dictionary)
        for n, c in batch.columns.items()
    }
    return Batch(cols, jax.device_put(batch.row_valid, rep))


def unshard_batch(sb: ShardedBatch) -> Batch:
    """Gather to one addressable batch (root-stage output)."""
    return _replicate(sb.batch, sb.mesh)


# ---------------------------------------------------------------------------
# The shuffle kernel (per-chip body run under shard_map)


def _bucket_sizes(dest: jnp.ndarray, n_parts: int) -> jnp.ndarray:
    """int32[n_parts]: how many rows go to each destination — one
    masked sum a bucket (rows routed nowhere carry dest == n_parts and
    are counted by none)."""
    return jnp.stack([jnp.sum(dest == d, dtype=jnp.int32)
                      for d in range(n_parts)])


def _send_segments(g: jnp.ndarray, offsets: jnp.ndarray,
                   counts: jnp.ndarray) -> jnp.ndarray:
    """The [n_parts, rows] send buffer of one array already grouped by
    destination: bucket d is the contiguous run g[offsets[d]:][:counts[d]],
    copied by one dynamic_slice of `rows` lanes and zeroed past its
    count. The window is cut from `g` extended by `rows` dead lanes,
    which no start in [0, rows] can run past: cut from `g` itself it
    would clamp to start 0 (module docstring, step 3)."""
    rows = g.shape[0]
    ext = jnp.concatenate([g, jnp.zeros(rows, g.dtype)])
    lane = jnp.arange(rows, dtype=jnp.int32)
    dead = jnp.zeros((), g.dtype)
    return jnp.stack([
        jnp.where(lane < counts[d],
                  jax.lax.dynamic_slice(ext, (offsets[d],), (rows,)),
                  dead)
        for d in range(counts.shape[0])])


def _pack_segments(recv: jnp.ndarray, starts: jnp.ndarray,
                   total: jnp.ndarray) -> jnp.ndarray:
    """[n_parts * rows] from the received [n_parts, rows] segments, each
    live at its front: segment s (all `rows` lanes of it) is written at
    starts[s] = the live rows of the segments before it, in ascending
    source order, so each write overwrites the dead tail of the one
    before. starts[s] <= s * rows, so no window runs past the end and
    none clamps. Lanes from `total` on are zeroed."""
    w, rows = recv.shape
    out = jnp.zeros(w * rows, recv.dtype)
    for s in range(w):
        out = jax.lax.dynamic_update_slice(out, recv[s], (starts[s],))
    lane = jnp.arange(w * rows, dtype=jnp.int32)
    return jnp.where(lane < total, out, jnp.zeros((), recv.dtype))


#: validity masks cross the wave packed 32 to an unsigned lane
_MASK_LANE_BITS = 32


def _pack_masks(masks: Sequence[jnp.ndarray]) -> List[jnp.ndarray]:
    lanes = []
    for i in range(0, len(masks), _MASK_LANE_BITS):
        lane = jnp.zeros(masks[0].shape, jnp.uint32)
        for bit, m in enumerate(masks[i:i + _MASK_LANE_BITS]):
            lane = lane | (m.astype(jnp.uint32) << bit)
        lanes.append(lane)
    return lanes


def _unpack_masks(lanes: Sequence[jnp.ndarray], n: int
                  ) -> Tuple[jnp.ndarray, ...]:
    return tuple(
        (lanes[i // _MASK_LANE_BITS] >> (i % _MASK_LANE_BITS)) & 1 != 0
        for i in range(n))


def _wave_body(n_parts: int, axis: str, row_valid, key_datas,
               key_masks, datas, masks):
    """The per-chip wave pipeline (module docstring, steps 1-5): rows
    cross as contiguous segments addressed by n_parts counts. Shared
    by the plain and the chained (fused-fragment) wave programs, by
    hash_repartition and by their KernelContract trace points.
    Returns (datas, masks, row_valid, count[1]) over n_parts * rows
    lanes, the live rows packed to the front in source-chip order,
    source order kept inside a chip's rows, dead lanes zeroed."""
    rows = row_valid.shape[0]
    h = common.row_hash(list(zip(key_datas, key_masks)))
    dest = (jnp.abs(h) % n_parts).astype(jnp.int32)
    dest = jnp.where(row_valid, dest, n_parts)  # invalid -> no bucket
    order = common.stable_argsort(dest)
    counts = _bucket_sizes(dest, n_parts)
    offsets = jnp.cumsum(counts) - counts
    # row_valid does not cross (the counts say which lanes are live)
    nd = len(datas)
    send = [_send_segments(a[order], offsets, counts)
            for a in list(datas) + _pack_masks(masks)]
    recv = [jax.lax.all_to_all(b, axis, 0, 0, tiled=True) for b in send]
    got = jax.lax.all_to_all(counts, axis, 0, 0, tiled=True)
    starts = jnp.cumsum(got) - got
    total = jnp.sum(got)
    flat = [_pack_segments(b, starts, total) for b in recv]
    out_valid = jnp.arange(n_parts * rows, dtype=jnp.int32) < total
    return (tuple(flat[:nd]), _unpack_masks(flat[nd:], len(masks)),
            out_valid, total.reshape(1))


def hash_repartition(sb: ShardedBatch, key_names: Sequence[str]
                     ) -> ShardedBatch:
    """Repartition so rows with equal keys land on the same chip.

    Output rows_per_worker = W * input rows_per_worker (each chip can in
    the worst case receive every other chip's full slice; no overflow is
    possible by construction), each chip's live rows packed to the front
    of its slice. Callers that need the batch small again compact after
    aggregation (`wave_repartition` slices by the received counts)."""
    mesh, axis = sb.mesh, sb.axis
    w = sb.n_workers
    b = sb.batch
    names = b.names
    key_idx = [names.index(k) for k in key_names]
    datas = tuple(b.columns[n].data for n in names)
    masks = tuple(b.columns[n].mask for n in names)
    key_datas = tuple(datas[i] for i in key_idx)
    key_masks = tuple(masks[i] for i in key_idx)

    body = functools.partial(_wave_body, w, axis)
    spec = P(axis)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec,) * 5,
        out_specs=(spec, spec, spec, spec))
    out_datas, out_masks, out_valid, _ = fn(
        b.row_valid, key_datas, key_masks, datas, masks)
    cols = {
        n: Column(d, m, b.columns[n].type, b.columns[n].dictionary)
        for n, d, m in zip(names, out_datas, out_masks)
    }
    return ShardedBatch(Batch(cols, out_valid), mesh, axis)


def broadcast_batch(batch: Batch, mesh: Mesh,
                    axis: str = worker_axis) -> Batch:
    """Replicate a batch to every chip (the analog of
    FIXED_BROADCAST_DISTRIBUTION + BroadcastOutputBuffer for small join
    build sides — SystemPartitioningHandle.java:63)."""
    return _replicate(batch, mesh)


# ---------------------------------------------------------------------------
# Wave shuffle: the engine's exchange-operator entry point.
#
# One "wave" = one batch per worker. The compiled SPMD program (cached
# per mesh/shape/signature so repeated waves never retrace) runs
# _wave_body: the received rows come out packed to the front of each
# shard with their count — the host reads the [W] counts once per wave and
# slices every consumer's shard down to its capacity bucket, which fixes
# the W× capacity blow-up of chained shuffles (each consumer batch ends
# up sized to its live rows, not to W * producer capacity).


@functools.lru_cache(maxsize=256)
def _wave_program(mesh: Mesh, axis: str, w: int, n_keys: int,
                  n_cols: int):
    spec = P(axis)
    body = functools.partial(_wave_body, w, axis)
    # the lru entry holds the instrumented wrapper, so the warm jit
    # cache (and with it the zero-new-kernels guarantee for the second
    # same-bucket wave) travels with the cache hit
    return _kernels.instrument_kernel(_kernels.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec,) * 5,
        out_specs=(spec, spec, spec, spec)), "spmd_shuffle"),
        "spmd_shuffle")


# -- chained wave: a fused-fragment chain traced INSIDE the wave -------
#
# planner/fusion.fuse_exchange_sinks absorbs a distributed fragment's
# tail chain (filter/project run) into its repartition exchange: the
# chain then traces inside the shard_map body, per shard, IN THE SAME
# program as the hash + all_to_all — one dispatch per wave instead of
# one per chain stage per producer, no per-batch deferred-compact host
# round (the wave's segments leave dead lanes behind, before the wire), and
# the output sharding is the consumer's input spec by construction.


@dataclasses.dataclass(frozen=True)
class WaveChain:
    """The absorbed chain: `stages` are operators/fused_fragment
    ChainStages, `key` their chain_fingerprint (hashable, never None —
    the planner declines uncacheable chains), `label` the EXPLAIN
    constituent label (fused[...+all_to_all])."""
    stages: tuple
    key: object
    label: str


_CHAINED_PROGRAMS: "collections.OrderedDict" = collections.OrderedDict()
_CHAINED_PROGRAMS_MAX = 128


def _chained_wave_program(mesh: Mesh, axis: str, w: int,
                          chain: WaveChain, template: Batch,
                          key_names: Tuple[str, ...],
                          remap_flags: Tuple[bool, ...]):
    """(instrumented jit, output column meta) for one chained wave
    shape family. Cached like _wave_program; the key adds the chain
    fingerprint + input schema so two plans sharing a chain share the
    compiled program (and its warm retrace state)."""
    in_sig = tuple((n, str(np.dtype(c.data.dtype)))
                   for n, c in template.columns.items())
    cache_key = (mesh, axis, w, chain.key, key_names, remap_flags,
                 in_sig)
    cached = _CHAINED_PROGRAMS.get(cache_key)
    if cached is not None:
        _CHAINED_PROGRAMS.move_to_end(cache_key)
        return cached

    from presto_tpu.operators.fused_fragment import make_chain_body
    chain_fn = make_chain_body(chain.stages)
    in_meta = tuple((n, c.type, c.dictionary)
                    for n, c in template.columns.items())
    # output schema by abstract evaluation — names/types/dictionaries
    # only, nothing executes (Batch aux data rides jax.eval_shape)
    out_t = jax.eval_shape(chain_fn, template)
    out_meta = tuple((n, c.type, c.dictionary)
                     for n, c in out_t.columns.items())
    out_names = tuple(n for n, _, _ in out_meta)

    def body(row_valid, datas, masks, remap_tables):
        cols = {n: Column(d, m, t, dic)
                for (n, t, dic), d, m in zip(in_meta, datas, masks)}
        out = chain_fn(Batch(cols, row_valid))
        key_datas, key_masks, ri = [], [], 0
        for i, k in enumerate(key_names):
            c = out.columns[k]
            d = c.data
            if remap_flags[i]:
                # routing only: the hash sees unified-dictionary
                # codes, the payload keeps the producer's codes —
                # exactly the eager-remap semantics of the plain wave
                d = remap_tables[ri][d]
                ri += 1
            key_datas.append(d)
            key_masks.append(c.mask)
        o_datas = tuple(out.columns[n].data for n in out_names)
        o_masks = tuple(out.columns[n].mask for n in out_names)
        return _wave_body(w, axis, out.row_valid, tuple(key_datas),
                          tuple(key_masks), o_datas, o_masks)

    spec = P(axis)
    fn = _kernels.instrument_kernel(_kernels.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec, P()),
        out_specs=(spec, spec, spec, spec)), "spmd_fragment"),
        "spmd_fragment")
    entry = (fn, out_meta)
    _CHAINED_PROGRAMS[cache_key] = entry
    while len(_CHAINED_PROGRAMS) > _CHAINED_PROGRAMS_MAX:
        _CHAINED_PROGRAMS.popitem(last=False)
    return entry


def batch_row_bytes(batch: Batch) -> int:
    """Wire bytes per row of a wave for this schema: column payloads
    + one mask byte per column + the row_valid byte (the exchange's
    bytes/row accounting; docs/SHARDING.md)."""
    return sum(np.dtype(c.data.dtype).itemsize + 1
               for c in batch.columns.values()) + 1


def _as_global(arrays, mesh: Mesh, axis: str, cap: int):
    """Assemble per-device shards into one sharded global array
    (zero-copy when each shard already lives on its mesh device; one
    that has to move is charged as the exchange's chip-to-chip copy:
    parallel/mesh.place)."""
    w = len(arrays)
    sh = NamedSharding(mesh, P(axis))
    placed = [place(a, d, counted_as="exchange_")
              for a, d in zip(arrays, mesh.devices.reshape(-1))]
    return jax.make_array_from_single_device_arrays(
        (w * cap,) + placed[0].shape[1:], sh, placed)


def wave_repartition(mesh: Mesh, batches, key_names,
                     key_remaps=None, axis: str = worker_axis,
                     chain: Optional[WaveChain] = None,
                     return_counts: bool = False):
    """Hash-repartition one wave (one Batch per worker) over ICI.

    `key_remaps[i]`, when set, is an int32 device array re-encoding that
    string key's dictionary codes onto the unified hash dictionary so
    equal strings hash equally on every producer.

    `chain`, when set, is the fused-fragment chain the planner absorbed
    into this exchange (fuse_exchange_sinks): it traces inside the
    shard_map body ahead of the hash, per shard, and the partition keys
    are read from the CHAIN OUTPUT (key remaps ride the trace as
    replicated operands). The producers then push raw chain INPUT
    batches and the whole tail runs as one SPMD program per wave.

    Returns the list of per-consumer Batches (consumer i's batch lives
    on mesh device i), each compacted and sliced to the capacity bucket
    of its live rows — with `return_counts`, `(batches, counts)` where
    `counts[i]` is consumer i's received live rows (the exchange's
    rows/bytes accounting reads it off the wave's one host sync).
    """
    w = len(batches)
    assert w == mesh.shape[axis]
    from presto_tpu.batch import quantized_capacity
    # quantized wave capacity: the whole shard_map program recompiles
    # per distinct shape, so waves ride a coarse capacity ladder
    cap = quantized_capacity(max(b.capacity for b in batches))
    batches = [b if b.capacity == cap else b.compact(cap)
               for b in batches]
    names = batches[0].names
    tmpl = batches[0]

    # the wave's host side in four frames of the caller's category
    # (MeshExchange._run_wave's exchange.all_to_all): assembling the
    # global arrays, the program's call, the sync on the counts, and
    # slicing each consumer's batch out of the outputs
    with ledger.span("exchange.all_to_all", detail="assemble"):
        g_datas = tuple(
            _as_global([b.columns[n].data for b in batches], mesh,
                       axis, cap) for n in names)
        g_masks = tuple(
            _as_global([b.columns[n].mask for b in batches], mesh,
                       axis, cap) for n in names)
        g_valid = _as_global([b.row_valid for b in batches], mesh,
                             axis, cap)

    if chain is not None:
        remap_flags = tuple(
            key_remaps is not None and key_remaps[i] is not None
            for i in range(len(key_names)))
        fn, out_meta = _chained_wave_program(
            mesh, axis, w, chain, tmpl, tuple(key_names), remap_flags)
        tables = tuple(key_remaps[i]
                       for i, f in enumerate(remap_flags) if f)
        with ledger.span("exchange.all_to_all", detail="dispatch"):
            out_datas, out_masks, out_valid, counts = fn(
                g_valid, g_datas, g_masks, tables)
    else:
        key_datas, key_masks = [], []
        with ledger.span("exchange.all_to_all", detail="assemble"):
            for i, k in enumerate(key_names):
                datas, masks = [], []
                for b in batches:
                    c = b.columns[k]
                    d = c.data
                    if key_remaps is not None \
                            and key_remaps[i] is not None:
                        d = key_remaps[i][d]
                    datas.append(d)
                    masks.append(c.mask)
                key_datas.append(_as_global(datas, mesh, axis, cap))
                key_masks.append(_as_global(masks, mesh, axis, cap))
        fn = _wave_program(mesh, axis, w, len(key_names), len(names))
        with ledger.span("exchange.all_to_all", detail="dispatch"):
            out_datas, out_masks, out_valid, counts = fn(
                g_valid, tuple(key_datas), tuple(key_masks), g_datas,
                g_masks)
        out_meta = tuple((n, tmpl.columns[n].type,
                          tmpl.columns[n].dictionary) for n in names)

    with ledger.span("exchange.all_to_all", detail="sync"):
        counts = np.asarray(counts)  # ONE host sync per wave
    out = []
    with ledger.span("exchange.all_to_all", detail="slice"):
        for c in range(w):
            shard_len = _shard(out_valid, c).shape[0]
            cap2 = min(quantized_capacity(int(counts[c])), shard_len)
            cols = {}
            for (n, typ, dic), gd, gm in zip(out_meta, out_datas,
                                             out_masks):
                cols[n] = Column(_shard(gd, c)[:cap2],
                                 _shard(gm, c)[:cap2], typ, dic)
            out.append(Batch(cols, _shard(out_valid, c)[:cap2]))
    if return_counts:
        return out, counts
    return out


def _shard(garr, index: int):
    """The `index`-th row-shard of a sharded global array (on-device)."""
    shards = sorted(garr.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    return shards[index].data


# -- kernel contracts (tools/kernelcheck.py) ---------------------------
#
# The sharded families: KC001/KC002 hold THROUGH shard_map — the taint
# walk recurses into the shard_map jaxpr (analysis/taint.py) and
# all_to_all is lane-moving structural, so the same pad-invariance
# proof covers the collective. Contract meshes use a power-of-two
# width up to 8 so the ladder buckets (4096/16384/65536) always divide
# evenly; tier-1 traces at the test suite's full 8-virtual-device
# width, a bare CLI without the XLA flag degrades to w=1 (all_to_all
# over a singleton axis — still the same program structure).
from presto_tpu.analysis.contracts import (
    KernelContract, TracePoint, abstract_batch, register_contract,
)


def _contract_mesh() -> Mesh:
    from presto_tpu.parallel.mesh import make_mesh
    n = len(jax.devices())
    w = 1
    while w * 2 <= min(8, n):
        w *= 2
    return make_mesh(w)


def _spmd_shuffle_point(cap, variant):
    from presto_tpu.types import BIGINT, DOUBLE
    mesh = _contract_mesh()
    w = int(mesh.shape[worker_axis])
    spec = P(worker_axis)

    def fn(batch):
        names = list(batch.columns)
        datas = tuple(batch.columns[n].data for n in names)
        masks = tuple(batch.columns[n].mask for n in names)
        body = functools.partial(_wave_body, w, worker_axis)
        sm = jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 5,
                        out_specs=(spec,) * 4)
        out_datas, out_masks, out_valid, count = sm(
            batch.row_valid, (datas[0],), (masks[0],), datas, masks)
        cols = {n: Column(d, m, batch.columns[n].type,
                          batch.columns[n].dictionary)
                for n, d, m in zip(names, out_datas, out_masks)}
        return Batch(cols, out_valid), count

    b, rb = abstract_batch(cap, [("k", BIGINT), ("v", DOUBLE)])
    return TracePoint(fn, (b,), (rb,))


register_contract(KernelContract(
    family="spmd_shuffle", module=__name__,
    build=_spmd_shuffle_point,
    notes="the wave program (_wave_program), per shard: hash -> one "
          "sort by destination + W masked counts -> one gather an "
          "array -> W dynamic_slices into the [W, rows] send buffer "
          "(zeroed past each count) -> all_to_all of segments and "
          "counts -> W dynamic_update_slices pack the segments front "
          "to front, zeroed from the total on; offsets and starts "
          "come from counts of a clean dest, never from data"))


def _spmd_fragment_point(cap, variant):
    from presto_tpu.expr import ir
    from presto_tpu.expr.compile import compile_expression
    from presto_tpu.operators.fused_fragment import (
        ChainStage, make_chain_body,
    )
    from presto_tpu.schema import ColumnSchema
    from presto_tpu.types import BIGINT, BOOLEAN, DOUBLE
    schema = {"x": ColumnSchema("x", BIGINT),
              "y": ColumnSchema("y", DOUBLE)}
    filt = compile_expression(
        ir.call("less_than", BOOLEAN, ir.ref("y", DOUBLE),
                ir.lit(0.5, DOUBLE)), schema)
    stages = (ChainStage(
        filt, (("x", compile_expression(ir.ref("x", BIGINT), schema)),
               ("y", compile_expression(ir.ref("y", DOUBLE), schema))),
        None),)
    chain_fn = make_chain_body(stages)
    mesh = _contract_mesh()
    w = int(mesh.shape[worker_axis])
    spec = P(worker_axis)

    def fn(batch):
        names = list(batch.columns)

        def body(rv, datas, masks):
            cols = {n: Column(d, m, batch.columns[n].type,
                              batch.columns[n].dictionary)
                    for n, d, m in zip(names, datas, masks)}
            out = chain_fn(Batch(cols, rv))
            kd = (out.columns["x"].data,)
            km = (out.columns["x"].mask,)
            o_datas = tuple(c.data for c in out.columns.values())
            o_masks = tuple(c.mask for c in out.columns.values())
            return _wave_body(w, worker_axis, out.row_valid, kd, km,
                              o_datas, o_masks)

        sm = jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 3,
                        out_specs=(spec,) * 4)
        datas = tuple(batch.columns[n].data for n in names)
        masks = tuple(batch.columns[n].mask for n in names)
        return sm(batch.row_valid, datas, masks)

    b, rb = abstract_batch(cap, [("x", BIGINT), ("y", DOUBLE)])
    return TracePoint(fn, (b,), (rb,))


register_contract(KernelContract(
    family="spmd_fragment", module=__name__,
    build=_spmd_fragment_point,
    notes="the chained wave (_chained_wave_program): a fused-fragment "
          "chain traced inside the shard_map body ahead of the same "
          "segment wave as spmd_shuffle (_wave_body; "
          "planner/fusion.fuse_exchange_sinks): rows the chain "
          "filters out get no bucket and never reach the wire"))
