"""Mesh exchange runtime: the data plane between plan fragments.

The reference moves pages between tasks through per-task OutputBuffers
(execution/buffer/PartitionedOutputBuffer.java:48) pulled over HTTP by
ExchangeClient.java:81. Here all fragment tasks live in one SPMD host
process, so an exchange is an in-process object that routes device
batches between producer and consumer task queues:

  - repartition (hash keys): producers contribute one batch each per
    "wave"; the wave runs ONE compiled shard_map program whose
    jax.lax.all_to_all rides ICI (parallel/shuffle.wave_repartition).
    Consumers receive compacted batches sized to their live rows.
  - repartition (no keys): round-robin whole batches across consumers
    (FIXED_ARBITRARY_DISTRIBUTION).
  - gather: every batch to the single consumer task's device.
  - broadcast: every batch replicated to every consumer device.
  - passthrough: producer i -> consumer i (fragment cut of a shared
    subtree; no data movement).

Producer/consumer progress is driven by the same round-robin driver
loop as every other operator, so stages stream (P5): a wave fires as
soon as each producer has one batch pending (finished producers are
padded with empty batches).
"""

from __future__ import annotations

import collections
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.batch import Batch, Column
from presto_tpu.operators.base import (
    DriverContext, Operator, OperatorContext, OperatorFactory,
)
from presto_tpu.ops import common
from presto_tpu.parallel.shuffle import wave_repartition
from presto_tpu.telemetry import kernels as _kernels


def build_remap_tables(hash_dicts, key_dictionaries):
    """Per-key remap tables: original dictionary codes -> unified hash
    dictionary codes (None for non-string keys). Shared by the ICI
    (MeshExchange) and DCN (HttpExchange) tiers so partition routing can
    never desynchronize between them."""
    if hash_dicts is None:
        return None
    remaps = []
    for dic, hd in zip(key_dictionaries, hash_dicts):
        if hd is None or dic is None:
            remaps.append(None)
        else:
            index = {v: i for i, v in enumerate(hd)}
            remaps.append(jnp.asarray(
                np.array([index[v] for v in dic] or [0],
                         dtype=np.int32)))
    return remaps


def partition_key_hash(batch: Batch, partition_keys: Sequence[str],
                       remaps) -> jnp.ndarray:
    """|hash| of the partition keys through the unified-dictionary
    remaps — the ONE place the exchange partition hash is computed (both
    exchange tiers and lifespan bucketing route through here)."""
    cols = []
    for i, k in enumerate(partition_keys):
        c = batch.columns[k]
        d = c.data
        if remaps is not None and remaps[i] is not None:
            d = remaps[i][d]
        cols.append((d, c.mask))
    return jnp.abs(common.row_hash(cols))


@functools.partial(_kernels.jit, family="exchange_partition",
                   static_argnums=(1, 3))
def partition_segments(batch: Batch, partition_keys: Tuple[str, ...],
                       remaps, n_consumers: int):
    """ONE dispatch for a whole hash repartition: sort rows by
    destination (one key sort, columns follow by gather) and
    return the sorted batch plus the destination segment bounds —
    segment c is rows [bounds[c], bounds[c+1]), dead rows parked at
    the end. The DCN push then does a single device->host transfer
    and slices per destination on the host, instead of per-consumer
    mask+compact+serialize rounds (reference seam: the block-level
    repartition of OptimizedPartitionedOutputOperator.java:82)."""
    h = partition_key_hash(batch, partition_keys, remaps)
    dest = (h % n_consumers).astype(jnp.int32)
    dest = jnp.where(batch.row_valid, dest, n_consumers)
    payloads = [batch.row_valid]
    for n in batch.names:
        payloads.extend(batch.columns[n].astuple())
    perm = common.stable_argsort(dest)
    out = [dest[perm]] + [p[perm] for p in payloads]
    cols = {}
    for i, n in enumerate(batch.names):
        c = batch.columns[n]
        cols[n] = Column(out[2 + 2 * i], out[3 + 2 * i], c.type,
                         c.dictionary)
    bounds = common.fast_searchsorted(
        out[0], jnp.arange(n_consumers + 1, dtype=jnp.int32),
        side="left")
    return Batch(cols, out[1]), bounds


# compile-vs-execute attribution for the repartition family —
# previously an uninstrumented module-level jit whose compile landed
# in exchange-push busy time
_instr = _kernels.instrument_kernel

partition_segments = _instr(partition_segments, "exchange_partition")


def edge_key_dicts(edge) -> List:
    """Dictionaries of an edge's partition-key fields (in key order)."""
    return [next((f.dictionary for f in edge.fields if f.symbol == k),
                 None)
            for k in edge.partition_keys]


DEFAULT_HOST_SPOOL_BYTES = 8 << 30


class MeshExchange:
    """One exchange edge: N producer tasks -> M consumer task queues.

    Grouped (bucket-wise) execution: with `lifespans` G > 1 the hash
    space is split W x G (reference: execution/Lifespan.java:26 driver
    groups); rows for the CURRENT lifespan queue on their consumer's
    device, rows for later lifespans spill DOWN the memory tiers —
    first to host RAM (the scarce tier is HBM), and past
    `host_spool_bytes` of host batches to DISK as compressed pages
    through the native codec (reference: spiller/
    FileSingleStreamSpiller.java:56 + GenericPartitioningSpiller —
    their partitioned spill is our per-lifespan bucketing). Batches
    return to the device when advance_lifespan() starts their bucket;
    spill files are deleted as they are read back. Producers that
    themselves run bucket-wise signal done once per lifespan;
    `producer_finishes` sets how many signals complete one producer."""

    def __init__(self, exchange_id: int, scheme: str,
                 partition_keys: Sequence[str],
                 hash_dicts, key_dictionaries,
                 mesh, n_producers: int, n_consumers: int,
                 lifespans: int = 1, producer_finishes: int = 1,
                 pool=None,
                 host_spool_bytes: int = DEFAULT_HOST_SPOOL_BYTES,
                 recoverable: bool = False):
        self.exchange_id = exchange_id
        self.scheme = scheme
        self.partition_keys = list(partition_keys)
        self.mesh = mesh
        self.devices = list(mesh.devices.reshape(-1)) if mesh is not None \
            else [None]
        self.n_producers = n_producers
        self.n_consumers = n_consumers
        self.lifespans = lifespans
        self.current_lifespan = 0
        self.pool = pool
        self._tag = f"exchange#{exchange_id}"
        self._finish_signals = [0] * n_producers
        self._finishes_required = producer_finishes
        self.queues: List[collections.deque] = [
            collections.deque() for _ in range(n_consumers)]
        # host-spooled batches per (lifespan, consumer), numpy pytrees
        self._spooled: Dict[int, List[collections.deque]] = {
            g: [collections.deque() for _ in range(n_consumers)]
            for g in range(1, lifespans)
        }
        self._pending: List[collections.deque] = [
            collections.deque() for _ in range(n_producers)]
        self._done = [False] * n_producers
        self._template: Optional[Batch] = None
        self._rr = 0
        #: fused-fragment chain absorbed into the wave program
        #: (planner/fusion.fuse_exchange_sinks; parallel/shuffle
        #: WaveChain) — producers then push raw chain-INPUT batches
        self._chain = None
        #: per-exchange wave accounting (EXPLAIN ANALYZE + the mesh
        #: bench's exchange bytes/row): live rows crossing the
        #: all_to_all and their wire bytes (batch_row_bytes schema)
        self.wave_count = 0
        self.wave_rows = 0
        self.wave_bytes = 0
        self._row_bytes: Optional[int] = None
        self._remaps = build_remap_tables(hash_dicts, key_dictionaries)
        # host/disk spool accounting
        self._host_spool_bytes = host_spool_bytes
        self._host_bytes = 0
        self._spill_dir: Optional[str] = None
        self._spill_seq = 0
        self.spilled_pages = 0  # observability + tests
        #: P7 recoverable grouped execution: keep a bucket's
        #: materialized pages until commit_lifespan() so a failed
        #: bucket can be restored and re-run (reference:
        #: PlanFragmenter.java:243-260 recoverable lifespans — the
        #: materialize-to-recover trade). Bucket 0 streams un-
        #: materialized and stays whole-query-retry territory.
        self.recoverable = recoverable
        self._retained: Optional[list] = None  # current bucket's spool

    # -- memory accounting -------------------------------------------------

    def _reserve(self, batch: Batch) -> None:
        if self.pool is not None:
            from presto_tpu.execution.memory import batch_bytes
            self.pool.reserve(self._tag, batch_bytes(batch))

    def _free(self, batch: Batch) -> None:
        if self.pool is not None:
            from presto_tpu.execution.memory import batch_bytes
            self.pool.free(self._tag, batch_bytes(batch))

    def _enqueue(self, consumer: int, batch: Batch) -> None:
        self._reserve(batch)
        self.queues[consumer].append(batch)

    # -- producer side -----------------------------------------------------

    def push(self, producer: int, batch: Batch) -> None:
        if self._template is None:
            self._template = batch
        scheme = self.scheme
        if scheme == "gather":
            self._enqueue(0, self._place(batch, 0))
        elif scheme == "broadcast":
            for c in range(self.n_consumers):
                self._enqueue(c, self._place(batch, c))
        elif scheme == "passthrough":
            self._enqueue(producer, batch)
        elif scheme == "repartition" and not self.partition_keys:
            c = self._rr % self.n_consumers
            self._rr += 1
            self._enqueue(c, self._place(batch, c))
        elif scheme == "repartition":
            if self.n_consumers == 1 and self.n_producers == 1 \
                    and self.lifespans == 1:
                self._enqueue(0, batch)
            elif self._collective:
                self._pending[producer].append(batch)
                self._try_wave()
            else:
                self._hash_split(batch)
        else:
            raise ValueError(f"unknown exchange scheme {scheme}")

    def producer_done(self, producer: int) -> None:
        self._finish_signals[producer] += 1
        if self._finish_signals[producer] >= self._finishes_required \
                and not self._done[producer]:
            self._done[producer] = True
            if self.scheme == "repartition" and self.partition_keys \
                    and self._collective:
                self._try_wave()

    # -- lifespans ---------------------------------------------------------

    def lifespan_drained(self) -> bool:
        """Current bucket fully delivered and consumed?"""
        return (all(self._done) and not any(self._pending)
                and not any(self.queues))

    def has_next_lifespan(self) -> bool:
        return self.current_lifespan + 1 < self.lifespans

    def advance_lifespan(self) -> None:
        """Reload the next bucket's spooled batches (host RAM or disk)
        onto their consumer devices. Under `recoverable`, the bucket's
        materialized pages are RETAINED until commit_lifespan() so a
        failed generation can restore_lifespan() and re-run."""
        self.current_lifespan += 1
        g = self.current_lifespan
        bucket = self._spooled.pop(g, [])
        self._deliver_spooled(bucket)
        if self.recoverable:
            self._retained = bucket
        else:
            self._discard_bucket(bucket)
            if self.current_lifespan + 1 >= self.lifespans:
                self._drop_spill_dir()

    def _deliver_spooled(self, bucket) -> None:
        from presto_tpu.telemetry import ledger as _ledger
        for c, dq in enumerate(bucket):
            dev = self.devices[c] if c < len(self.devices) \
                else self.devices[0]
            for tier, payload, nbytes in dq:
                if tier == "disk":
                    from presto_tpu.server.serde import batch_from_bytes
                    with _ledger.span("spool"):
                        with open(payload, "rb") as f:
                            raw = f.read()
                    host_batch = batch_from_bytes(raw)
                else:
                    host_batch = payload
                # pad on the HOST to the quantized capacity ladder:
                # exact tiny buckets would each compile fresh kernels
                # downstream; numpy padding costs nothing
                host_batch = _host_pad_quantized(host_batch)
                with _ledger.span("h2d"):
                    self._enqueue(c, jax.device_put(host_batch, dev))

    def _discard_bucket(self, bucket) -> None:
        import os
        for dq in bucket:
            for tier, payload, nbytes in dq:
                if tier == "disk":
                    try:
                        os.unlink(payload)
                    except OSError:
                        pass
                else:
                    self._host_bytes -= nbytes

    def commit_lifespan(self) -> None:
        """The current bucket completed: drop its retained pages."""
        if self._retained is not None:
            self._discard_bucket(self._retained)
            self._retained = None
        if self.current_lifespan + 1 >= self.lifespans:
            self._drop_spill_dir()

    def restore_lifespan(self) -> None:
        """Re-deliver the current bucket's retained pages after a
        failed generation (its device queues are dropped first — the
        failed attempt may have consumed some)."""
        assert self._retained is not None, \
            "restore without retained bucket (bucket 0 or committed)"
        for q in self.queues:
            while q:
                self._free(q.popleft())
        self._deliver_spooled(self._retained)

    def _drop_spill_dir(self) -> None:
        if self._spill_dir is not None:
            import shutil
            shutil.rmtree(self._spill_dir, ignore_errors=True)
            self._spill_dir = None

    def close(self) -> None:
        """Release every spooled resource — called when the query ends
        for ANY reason (error paths included), so spill files never
        outlive their query."""
        self._spooled = {}
        self._retained = None
        self._host_bytes = 0
        self._drop_spill_dir()

    def _spool(self, g: int, consumer: int, part: Batch,
               known_valid: int) -> None:
        """Park a later bucket's batch on the host tier, or on disk
        once host spool passes its budget. Sizes come from shape
        metadata — no device sync to decide the tier, and the caller
        already compacted `part` so serialization skips re-compaction."""
        import os
        import tempfile
        from presto_tpu.execution.memory import batch_bytes
        from presto_tpu.telemetry import ledger as _ledger
        nbytes = batch_bytes(part)
        if self._host_bytes + nbytes <= self._host_spool_bytes:
            self._host_bytes += nbytes
            with _ledger.span("d2h"):
                host = jax.device_get(part)
            self._spooled[g][consumer].append(("mem", host, nbytes))
            return
        from presto_tpu.server.serde import batch_to_bytes
        if self._spill_dir is None:
            self._spill_dir = tempfile.mkdtemp(
                prefix=f"presto-tpu-spill-{self.exchange_id}-")
        path = os.path.join(self._spill_dir,
                            f"{g}-{consumer}-{self._spill_seq}.page")
        self._spill_seq += 1
        payload = batch_to_bytes(part, assume_compact=True)
        with _ledger.span("spool"):
            with open(path, "wb") as f:
                f.write(payload)
        self.spilled_pages += 1
        self._spooled[g][consumer].append(("disk", path, nbytes))

    def _key_hash(self, batch: Batch):
        return partition_key_hash(batch, self.partition_keys,
                                  self._remaps)

    def _lifespan_of(self, h):
        return (h // max(self.n_consumers, 1)) % self.lifespans

    def _deliver_buckets(self, consumer: int, columns, base_mask,
                         g_of_row) -> None:
        """Current bucket to the consumer's device queue; later buckets
        spill to host (numpy pytrees, no HBM reserved). Spilled buckets
        are COMPACTED to their live rows first — shipping G-1
        full-capacity copies that differ only in their mask would
        multiply host RAM and PCIe traffic by G."""
        from presto_tpu.batch import bucket_capacity
        for g in range(self.current_lifespan, self.lifespans):
            part = Batch(columns, base_mask & (g_of_row == g))
            if g == self.current_lifespan:
                self._enqueue(consumer, part)
            else:
                n = int(jnp.sum(part.row_valid))
                if n == 0:
                    continue
                part = part.compact(bucket_capacity(n), known_valid=n)
                self._spool(g, consumer, part, n)

    def _route_lifespan(self, consumer: int, batch: Batch) -> None:
        if self.lifespans == 1:
            self._enqueue(consumer, batch)
            return
        g_of_row = self._lifespan_of(self._key_hash(batch))
        self._deliver_buckets(consumer, batch.columns, batch.row_valid,
                              g_of_row)

    # -- consumer side -----------------------------------------------------

    def pop(self, consumer: int) -> Optional[Batch]:
        q = self.queues[consumer]
        if not q:
            return None
        b = q.popleft()
        self._free(b)
        return b

    def has_output(self, consumer: int) -> bool:
        return bool(self.queues[consumer])

    def finished(self, consumer: int) -> bool:
        return (all(self._done)
                and not self.queues[consumer]
                and not any(self._pending))

    # -- fused-fragment absorption -----------------------------------------

    def chain_eligible(self) -> bool:
        """True when the wave path can absorb a producer-side fragment
        chain: a collective hash repartition with single-lifespan
        routing (retry ladders bump lifespans, which replans the
        fragment WITHOUT the fusion — the unfused path is the
        fallback, never a wrong answer)."""
        return (self.scheme == "repartition"
                and bool(self.partition_keys)
                and self.lifespans == 1
                and self._collective)

    def attach_chain(self, stages, chain_key, label: str) -> bool:
        """Absorb a fused-fragment chain into the wave program so the
        chain traces INSIDE the shard_map body (one jitted program per
        shape bucket: chain + segment wave + all_to_all). Idempotent
        across the W producer tasks planning the same fragment: the
        first attach wins and later attaches must agree on the key."""
        if not self.chain_eligible() or chain_key is None:
            return False
        from presto_tpu.parallel.shuffle import WaveChain
        if self._chain is not None:
            if self._chain.key != chain_key:
                raise AssertionError(
                    f"exchange {self.exchange_id}: conflicting fused "
                    f"chains {self._chain.key!r} vs {chain_key!r}")
            return True
        self._chain = WaveChain(tuple(stages), chain_key, label)
        return True

    # -- internals ---------------------------------------------------------

    @property
    def _collective(self) -> bool:
        w = len(self.devices)
        return (self.n_producers == w and self.n_consumers == w
                and w > 1)

    def _place(self, batch, consumer: int):
        """A batch (or one array) onto its consumer's chip, charged
        like every placement (parallel/mesh.place: the ledger's `d2d`
        and the bytes, nothing when it is already there) under a
        direction of the exchange's own, `exchange_d2d`."""
        dev = self.devices[consumer] if consumer < len(self.devices) \
            else self.devices[0]
        if dev is None:
            return batch
        from presto_tpu.parallel.mesh import place
        return place(batch, dev, counted_as="exchange_")

    def _hash_split(self, batch: Batch) -> None:
        """Non-collective repartition (producer/consumer counts differ
        from the mesh width, e.g. a single VALUES fragment spreading to
        W workers): split one batch by hash, route each slice. The key
        hash is computed once for both destination and lifespan."""
        h = self._key_hash(batch)
        dest = (h % self.n_consumers).astype(jnp.int32)
        g_of_row = self._lifespan_of(h) if self.lifespans > 1 else None
        for c in range(self.n_consumers):
            part = self._place(
                Batch(batch.columns, batch.row_valid & (dest == c)), c)
            if g_of_row is None:
                self._enqueue(c, part)
            else:
                self._deliver_buckets(c, part.columns, part.row_valid,
                                      self._place(g_of_row, c))

    def _pad_batch(self, cap: int, producer: int) -> Batch:
        t = self._template
        cols = {
            n: Column(jnp.zeros((cap,), c.data.dtype),
                      jnp.zeros((cap,), bool), c.type, c.dictionary)
            for n, c in t.columns.items()
        }
        b = Batch(cols, jnp.zeros((cap,), bool))
        return self._place(b, producer)

    def _try_wave(self) -> None:
        from presto_tpu.batch import quantized_capacity
        while True:
            have = [bool(p) for p in self._pending]
            if all(h or d for h, d in zip(have, self._done)):
                if not any(have):
                    return  # nothing left to flush
            else:
                return  # wait for slower producers
            cap = quantized_capacity(
                max(p[0].capacity for p in self._pending if p))
            wave = []
            for i, p in enumerate(self._pending):
                wave.append(p.popleft() if p
                            else self._pad_batch(cap, i))
            outs, counts = self._run_wave(wave)
            for c, b in enumerate(outs):
                self._route_lifespan(c, b)

    def _run_wave(self, wave):
        """One collective wave: the ICI all_to_all (plus any absorbed
        fragment chain) under its own ledger category, with live-row /
        wire-byte accounting. The collective belongs to the mesh as a
        whole, so per-device attribution is cleared for its span."""
        from presto_tpu.telemetry import ledger as _ledger
        from presto_tpu.telemetry.metrics import METRICS
        with _ledger.device_scope(None), \
                _ledger.span("exchange.all_to_all"), \
                _ledger.kernel_scope("exchange.all_to_all"):
            outs, counts = wave_repartition(
                self.mesh, wave, self.partition_keys,
                key_remaps=self._remaps, chain=self._chain,
                return_counts=True)
        rows = int(np.asarray(counts).sum())
        if self._row_bytes is None and outs:
            from presto_tpu.parallel.shuffle import batch_row_bytes
            self._row_bytes = batch_row_bytes(outs[0])
        nbytes = rows * (self._row_bytes or 0)
        self.wave_count += 1
        self.wave_rows += rows
        self.wave_bytes += nbytes
        METRICS.inc("presto_tpu_exchange_all_to_all_waves_total")
        METRICS.inc("presto_tpu_exchange_all_to_all_rows_total",
                    value=rows)
        METRICS.inc("presto_tpu_exchange_all_to_all_bytes_total",
                    value=nbytes)
        return outs, counts


def _host_pad_quantized(batch: Batch) -> Batch:
    """Numpy-pad a HOST-side batch up to the quantized capacity ladder
    (see batch.quantized_capacity) before it returns to the device."""
    import numpy as _np
    from presto_tpu.batch import quantized_capacity
    cap = quantized_capacity(batch.capacity)
    if cap == batch.capacity:
        return batch
    pad = cap - batch.capacity
    cols = {}
    for n, c in batch.columns.items():
        cols[n] = Column(
            _np.pad(_np.asarray(c.data), (0, pad)),
            _np.pad(_np.asarray(c.mask), (0, pad)), c.type,
            c.dictionary)
    return Batch(cols, _np.pad(_np.asarray(batch.row_valid), (0, pad)))


class ExchangeSinkOperator(Operator):
    """Tail of a producer task's pipeline; tees every batch into each
    consumer edge of this fragment's output (the analog of one
    OutputBuffer with several buffer ids).

    `staged` (P7 recoverable grouped execution): outputs buffer until
    finish() and flush atomically — a generation that fails mid-bucket
    has then published NOTHING downstream, so the bucket can re-run
    without duplicating rows (the reference's task-attempt output
    isolation, traded as materialize-then-release)."""

    def __init__(self, ctx: OperatorContext,
                 exchanges: Sequence[MeshExchange], producer: int,
                 staged: bool = False):
        super().__init__(ctx)
        self.exchanges = list(exchanges)
        self.producer = producer
        self.staged = staged
        self._staged_batches: List[Batch] = []
        self._finished = False

    def needs_input(self) -> bool:
        return not self._finished

    def add_input(self, batch: Batch) -> None:
        self._count_in(batch)
        if self.staged:
            self.ctx.reserve_batch(batch)
            self._staged_batches.append(batch)
            return
        for ex in self.exchanges:
            ex.push(self.producer, batch)

    def get_output(self) -> Optional[Batch]:
        return None

    def finish(self) -> None:
        if not self._finished:
            self._finished = True
            for b in self._staged_batches:
                for ex in self.exchanges:
                    ex.push(self.producer, b)
            self._staged_batches = []
            self.ctx.release_all()
            for ex in self.exchanges:
                ex.producer_done(self.producer)

    def is_finished(self) -> bool:
        return self._finished

    def close(self) -> None:
        # an ABORTED attempt (closed unfinished by the recovery path)
        # must publish nothing: drop the stage without flushing
        if not self._finished and self.staged:
            self._staged_batches = []
            self.ctx.release_all()
            self._finished = True
            return
        self.finish()


class ExchangeSourceOperator(Operator):
    """Head of a consumer task's pipeline (reference:
    ExchangeOperator.java:35 pulling from ExchangeClient).

    `device`, when set, pins popped batches to this subtask's chip —
    DCN pages deserialize on the default device, and a mesh-per-worker
    subtask must not mix devices inside its jitted operators."""

    def __init__(self, ctx: OperatorContext, exchange: MeshExchange,
                 consumer: int, device=None):
        super().__init__(ctx)
        self.exchange = exchange
        self.consumer = consumer
        self.device = device

    def needs_input(self) -> bool:
        return False

    def add_input(self, batch: Batch) -> None:
        raise RuntimeError("exchange source takes no input")

    def is_blocked(self):
        if self.exchange.has_output(self.consumer) or \
                self.exchange.finished(self.consumer):
            return False
        return f"waiting for exchange {self.exchange.exchange_id}"

    def get_output(self) -> Optional[Batch]:
        b = self.exchange.pop(self.consumer)
        if b is not None and self.device is not None:
            from presto_tpu.parallel.mesh import place
            b = place(b, self.device)
        return self._count_out(b) if b is not None else None

    def finish(self) -> None:
        pass

    def is_finished(self) -> bool:
        return self.exchange.finished(self.consumer) \
            and not self.exchange.has_output(self.consumer)


class ExchangeSinkOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int,
                 exchanges: Sequence[MeshExchange], producer: int,
                 staged: bool = False):
        super().__init__(operator_id, "exchange_sink")
        self.exchanges = exchanges
        self.producer = producer
        self.staged = staged

    def create(self, driver_context: DriverContext) -> Operator:
        return ExchangeSinkOperator(
            OperatorContext(self.operator_id, self.name, driver_context),
            self.exchanges, self.producer, self.staged)


class ExchangeSourceOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, exchange: MeshExchange,
                 consumer: int, device=None):
        super().__init__(operator_id, "exchange_source")
        self.exchange = exchange
        self.consumer = consumer
        self.device = device

    def create(self, driver_context: DriverContext) -> Operator:
        return ExchangeSourceOperator(
            OperatorContext(self.operator_id, self.name, driver_context),
            self.exchange, self.consumer, self.device)


# -- kernel contract (tools/kernelcheck.py) ----------------------------
from presto_tpu.analysis.contracts import (
    KernelContract, TracePoint, abstract_batch, register_contract,
)


def _partition_point(cap, variant):
    from presto_tpu.types import BIGINT, DOUBLE
    b, rb = abstract_batch(cap, [("k", BIGINT), ("v", DOUBLE)])
    return TracePoint(
        lambda bb: partition_segments.__wrapped__(
            bb, ("k",), None, 4),
        (b,), (rb,))


register_contract(KernelContract(
    family="exchange_partition", module=__name__,
    build=_partition_point,
    structure_varies=True,
    structure_reason="fast_searchsorted unrolls ceil(log2(n))+1 "
                     "gather/compare levels in Python on the CPU "
                     "backend — eqn count tracks the bucket"))
