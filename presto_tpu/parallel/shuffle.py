"""Hash repartitioning as an ICI all_to_all (the TPU-native rebuild of
the reference's shuffle: PartitionedOutputOperator.partitionPage
operator/PartitionedOutputOperator.java:360-417 producing per-consumer
buffers in PartitionedOutputBuffer.java:48, pulled over HTTP by
ExchangeClient.java:81).

A `ShardedBatch` is a Batch whose arrays carry a leading `workers` mesh
axis: global shape [W, rows] sharded so each chip holds one [rows] slice.
`hash_repartition` runs one shard_mapped program per chip:

  1. dest[i]   = hash(key columns)[i] mod W           (row -> consumer)
  2. bucketize = stable sort by dest + segment offsets -> scatter rows
                 into a [W, rows] send buffer (bucket d = rows for chip d;
                 a chip holds <= rows live rows, so bucket capacity =
                 rows is always overflow-free)
  3. jax.lax.all_to_all over the `workers` axis swaps buckets so chip d
     receives bucket d from every chip
  4. flatten [W, rows] -> [W*rows] — the received batch

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
from presto_tpu.parallel.mesh import worker_axis
from presto_tpu.telemetry import kernels as _kernels


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


def _bucketize(dest: jnp.ndarray, valid: jnp.ndarray, n_parts: int,
               arrays: Sequence[jnp.ndarray]
               ) -> List[jnp.ndarray]:
    """Scatter rows into [n_parts, rows] send buffers by dest bucket.

    Rows with valid=False go nowhere. Stable sort keeps input order
    within a bucket (not required by SQL, keeps results deterministic).
    """
    rows = dest.shape[0]
    dest = jnp.where(valid, dest, n_parts)  # invalid -> dropped bucket
    order = common.stable_argsort(dest)
    sdest = dest[order]
    # offset of each bucket's first row among the sorted rows
    counts = jax.ops.segment_sum(jnp.ones_like(sdest), sdest,
                                 num_segments=n_parts + 1)
    offsets = jnp.concatenate([jnp.zeros(1, counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(rows) - offsets[sdest]
    out = []
    for a in arrays:
        buf = jnp.zeros((n_parts + 1, rows), a.dtype)
        buf = buf.at[sdest, pos].set(a[order], mode="drop")
        out.append(buf[:n_parts])
    return out


def _shuffle_core(n_parts: int, axis: str,
                  row_valid: jnp.ndarray,
                  key_datas, key_masks, datas, masks):
    """Per-chip shuffle pipeline shared by every repartition entry
    point: hash keys -> bucketize -> all_to_all -> flatten. Returns the
    flat received (datas, masks, row_valid)."""
    h = common.row_hash(list(zip(key_datas, key_masks)))
    dest = jnp.abs(h) % n_parts
    send = _bucketize(dest.astype(jnp.int32), row_valid, n_parts,
                      list(datas) + list(masks) + [row_valid])
    recv = [jax.lax.all_to_all(b, axis, 0, 0, tiled=True) for b in send]
    flat = [b.reshape(-1) for b in recv]
    nd = len(datas)
    return tuple(flat[:nd]), tuple(flat[nd:2 * nd]), flat[2 * nd]




def hash_repartition(sb: ShardedBatch, key_names: Sequence[str]
                     ) -> ShardedBatch:
    """Repartition so rows with equal keys land on the same chip.

    Output rows_per_worker = W * input rows_per_worker (each chip can in
    the worst case receive every other chip's full slice; no overflow is
    possible by construction). Callers that need the batch small again
    compact after aggregation."""
    mesh, axis = sb.mesh, sb.axis
    w = sb.n_workers
    b = sb.batch
    names = b.names
    key_idx = [names.index(k) for k in key_names]
    datas = tuple(b.columns[n].data for n in names)
    masks = tuple(b.columns[n].mask for n in names)
    key_datas = tuple(datas[i] for i in key_idx)
    key_masks = tuple(masks[i] for i in key_idx)

    body = functools.partial(_shuffle_core, w, axis)
    spec = P(axis)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec,) * 5,
        out_specs=(spec, spec, spec))
    out_datas, out_masks, out_valid = fn(
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
# per mesh/shape/signature so repeated waves never retrace) hashes,
# all_to_alls, then PACKS the received rows to the front of each shard
# and counts them — the host reads the [W] counts once per wave and
# slices every consumer's shard down to its capacity bucket, which fixes
# the W× capacity blow-up of chained shuffles (each consumer batch ends
# up sized to its live rows, not to W * producer capacity).


def _wave_body(n_parts: int, axis: str, row_valid, key_datas,
               key_masks, datas, masks):
    """The per-chip wave pipeline: shuffle core, then pack live rows
    to the front (per-shard compaction) and count them — shared by
    the plain and the chained (fused-fragment) wave programs and by
    their KernelContract trace points."""
    r_datas, r_masks, valid = _shuffle_core(
        n_parts, axis, row_valid, key_datas, key_masks, datas, masks)
    order = common.partition_perm(valid)
    out_datas = tuple(f[order] for f in r_datas)
    out_masks = tuple(f[order] for f in r_masks)
    out_valid = valid[order]
    count = jnp.sum(valid).reshape(1)
    return out_datas, out_masks, out_valid, count


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
# round (the shuffle's bucketize drops dead lanes before the wire), and
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
    (zero-copy when each shard already lives on its mesh device)."""
    w = len(arrays)
    sh = NamedSharding(mesh, P(axis))
    devs = list(mesh.devices.reshape(-1))
    placed = []
    for a, d in zip(arrays, devs):
        if a.devices() != {d}:
            a = jax.device_put(a, d)
        placed.append(a)
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

    g_datas = tuple(
        _as_global([b.columns[n].data for b in batches], mesh, axis,
                   cap) for n in names)
    g_masks = tuple(
        _as_global([b.columns[n].mask for b in batches], mesh, axis,
                   cap) for n in names)
    g_valid = _as_global([b.row_valid for b in batches], mesh, axis,
                         cap)

    if chain is not None:
        remap_flags = tuple(
            key_remaps is not None and key_remaps[i] is not None
            for i in range(len(key_names)))
        fn, out_meta = _chained_wave_program(
            mesh, axis, w, chain, tmpl, tuple(key_names), remap_flags)
        tables = tuple(key_remaps[i]
                       for i, f in enumerate(remap_flags) if f)
        out_datas, out_masks, out_valid, counts = fn(
            g_valid, g_datas, g_masks, tables)
    else:
        key_datas, key_masks = [], []
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
        out_datas, out_masks, out_valid, counts = fn(
            g_valid, tuple(key_datas), tuple(key_masks), g_datas,
            g_masks)
        out_meta = tuple((n, tmpl.columns[n].type,
                          tmpl.columns[n].dictionary) for n in names)

    counts = np.asarray(counts)  # ONE host sync per wave
    out = []
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
    notes="the wave program (_wave_program): hash -> bucketize -> "
          "all_to_all -> pack + count, per shard"))


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
          "chain traced inside the shard_map body ahead of the "
          "shuffle (planner/fusion.fuse_exchange_sinks)"))
