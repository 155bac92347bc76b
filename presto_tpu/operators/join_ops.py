"""Join operators (reference: HashBuilderOperator.java:51 /
LookupJoinOperator.java:53 / HashSemiJoinOperator + SetBuilderOperator,
bridged exactly like the reference's LookupSourceFactory).

The build pipeline fills a JoinBridge; probe pipelines block on it
(Operator.is_blocked — the driver yields, the task executor keeps
running the build driver), then stream probe batches through the
searchsorted probe kernel."""

from __future__ import annotations

import collections
import functools
import time
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from presto_tpu.batch import (
    COMPACT_FLOOR, COMPACT_MIN, Batch, Column, begin_deferred_compact,
    bucket_capacity, count_flag_pack, end_deferred_compact,
    operator_capacity, pad_for_kernel, remap_column, start_async_copy,
)
from presto_tpu.native.pages import to_host
from presto_tpu.operators.base import (
    DriverContext, Operator, OperatorContext, OperatorFactory,
)
from presto_tpu.ops import common as ops_common
from presto_tpu.ops import join as join_ops
from presto_tpu.telemetry import kernels as _kernels
from presto_tpu.telemetry.metrics import METRICS

#: lanes the lookup join's probes searched / gathered output columns at
_LANES = "presto_tpu_join_probe_lanes_total"
#: the least bytes the sorted layout's builds and probes read
_SORTED_BYTES = "presto_tpu_join_sorted_bytes_total"
#: the build row index a lookup join gathers each output row by (int32)
_ROW_INDEX_BYTES = 4


def _key_bytes(batch: Batch, key_names) -> int:
    """Bytes a row of `batch`'s key columns holds: static widths."""
    return sum(batch.columns[k].data.dtype.itemsize for k in key_names)


class JoinCapacityExceeded(Exception):
    """A probe batch's true join output exceeded the optimistic output
    capacity (probe capacity x expansion factor). Detected ON DEVICE and
    surfaced once per query via DriverContext.deferred_checks; the
    runner retries with the suggested larger factor — the sync-free
    sibling of GroupLimitExceeded."""

    def __init__(self, suggested: int):
        super().__init__(
            f"join output overflowed; retry with expansion factor "
            f"{suggested}")
        self.suggested = suggested


#: hash partitions for spilled join builds. Uses hash bits 32+ so the
#: split is independent of the shuffle (h % n_consumers) and lifespan
#: ((h // n) % G) bucketing — sharing low bits would collapse every
#: row of a task into one spill part.
SPILL_PARTS = 8


def _spill_part_of(h, n_parts: int):
    return jnp.mod(h >> 32, n_parts)


class SpilledBuild:
    """Build side partitioned by key hash and parked in host RAM
    (reference: spiller/GenericPartitioningSpiller.java:47). The probe
    operator asks for one partition's BuildTable at a time, so device
    residency is ~1/n_parts of the build side."""

    def __init__(self, n_parts: int, key_names: Tuple[str, ...],
                 schema_cols, host_parts, key_dicts=None):
        self.n_parts = n_parts
        self.key_names = key_names
        self.schema_cols = schema_cols
        self.key_dicts = key_dicts
        self.host_parts = host_parts  # part -> [host-side Batch]

    def build_part(self, p: int) -> join_ops.BuildTable:
        import jax
        batches = [jax.device_put(b) for b in self.host_parts[p]]
        if batches:
            cap = bucket_capacity(sum(b.capacity for b in batches))
            merged = Batch.concat(batches, cap)
        else:
            # empty part still needs the unified dictionaries so its
            # (all-masked) probe outputs concat with other parts'
            from presto_tpu.batch import empty_batch
            merged = _remap_keys(empty_batch(self.schema_cols),
                                 self.key_names, self.key_dicts)
        return join_ops.build_for_backend(merged, self.key_names)


def spill_batch_to_host(b: Batch, part_dev, parts_out: List[list],
                        ctx) -> None:
    """Move one device batch to host RAM, split by partition id — ONE
    device->host transfer for the whole batch, then numpy slicing (no
    per-part device syncs, no shape-specialized compaction kernels:
    the spill path must not trigger a jit compile storm)."""
    import jax
    from presto_tpu.batch import Column
    from presto_tpu.execution.memory import batch_bytes
    host, hpart = jax.device_get((b, part_dev))
    live = np.asarray(host.row_valid)
    for p in range(len(parts_out)):
        sel = live & (hpart == p)
        n = int(sel.sum())
        if n == 0:
            continue
        cap = bucket_capacity(n)
        cols = {}
        for name, c in host.columns.items():
            d = np.zeros(cap, dtype=np.asarray(c.data).dtype)
            m = np.zeros(cap, dtype=bool)
            d[:n] = np.asarray(c.data)[sel]
            m[:n] = np.asarray(c.mask)[sel]
            cols[name] = Column(d, m, c.type, c.dictionary)
        rv = np.zeros(cap, dtype=bool)
        rv[:n] = True
        sub = Batch(cols, rv)
        parts_out[p].append(sub)
        ctx.count_spill(1, batch_bytes(sub))


class JoinBridge:
    """Shared build-side handoff (reference: LookupSourceFactory).
    Exactly one of `table` (in-memory) or `spilled` (partitioned,
    host-resident) is set once the build finishes."""

    def __init__(self):
        self.table: Optional[join_ops.BuildTable] = None
        self.spilled: Optional[SpilledBuild] = None

    @property
    def ready(self) -> bool:
        return self.table is not None or self.spilled is not None


class HashBuildOperator(Operator):
    """Sink of the build pipeline: accumulates batches, indexes on
    finish (reference: HashBuilderOperator.java:51).

    `key_dicts` (parallel to key_names; None for non-string keys) is the
    planner-computed *unified* dictionary for each string key: both join
    sides re-encode their codes onto it so code equality == string
    equality across tables."""

    def __init__(self, ctx: OperatorContext, bridge: JoinBridge,
                 key_names: Tuple[str, ...],
                 key_dicts: Optional[List[Optional[tuple]]] = None,
                 schema_cols: Optional[Sequence[tuple]] = None,
                 spillable: bool = False,
                 df_publish: Optional[List[tuple]] = None,
                 consumer_layouts: Sequence[str] = ("sorted",)):
        super().__init__(ctx)
        self.bridge = bridge
        self.key_names = key_names
        self.key_dicts = key_dicts
        self.schema_cols = schema_cols
        self._batches: List[Batch] = []
        self._spill = None  # part -> [host Batch] once revoked
        self._total = None
        self._finished = False
        #: why this build keeps the sorted layout (a `reason` of
        #: presto_tpu_join_direct_fallback_total), None while the
        #: direct one is still possible. What the plan fixes is known
        #: here; dtype, spread and uniqueness only the rows show
        #: (ops/join.py, "The DIRECT layout"). A build revoked to host
        #: RAM never gets this far: its parts are indexed one by one,
        #: sorted (SpilledBuild.build_part)
        self._sorted_because: Optional[str] = (
            "join_type" if "direct" not in consumer_layouts
            else "multi_key" if len(key_names) != 1 else None)
        #: int64 [rows, min key, max key] while the direct layout is
        #: possible: takes the place of `_total`
        self._key_stats = None
        #: dynamic filtering: [(key_name, df_id, registry)] — running
        #: min/max per named key, published at finish
        self._df_publish = df_publish or []
        self._df_state: dict = {}
        if spillable:
            self.ctx.register_revocable(self._revoke)

    def needs_input(self) -> bool:
        return not self._finished

    def add_input(self, batch: Batch) -> None:
        self._count_in(batch)
        # bucket build inputs too: the dynamic-filter bounds fold and
        # the finish-time concat both key jit caches on batch shapes
        batch = pad_for_kernel(batch)
        batch = _remap_keys(batch, self.key_names, self.key_dicts)
        for key, df_id, _reg in self._df_publish:
            from presto_tpu.execution import dynamic_filters as df
            c = batch.columns[key]
            st = self._df_state.get(df_id)
            if st is None:
                st = df.bounds_init(c.data.dtype)
            self._df_state[df_id] = df.bounds_step(
                st, c.data, c.mask & batch.row_valid)
        if self._spill is not None:
            # once revoked, later input goes straight to host partitions
            self._spill_batches([batch])
            return
        self.ctx.reserve_batch(batch)  # held until close: the built
        # table the bridge exposes is the same order of magnitude
        self._batches.append(batch)
        # running live-row total, prefetched: the async d2h copy is in
        # flight while later batches stream, so finish()'s one blocking
        # read usually finds the bytes already on the host instead of
        # paying a full device roundtrip
        if self._direct_candidate(batch):
            # the key's range rides the same fetch
            c = batch.columns[self.key_names[0]]
            self._key_stats = running = join_ops.key_stats_step(
                join_ops.key_stats_init() if self._key_stats is None
                else self._key_stats, c.data, c.mask, batch.row_valid)
        else:
            t = jnp.sum(batch.row_valid)
            self._total = running = t if self._total is None \
                else self._total + t
        try:
            running.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass

    def _direct_candidate(self, batch: Batch) -> bool:
        """Still possible after seeing this batch's key dtype: `key -
        min` addresses a table only for an integer (BIGINT, INTEGER,
        DATE, dictionary codes after the unified remap)."""
        if self._sorted_because is None and not jnp.issubdtype(
                batch.columns[self.key_names[0]].data.dtype,
                jnp.integer):
            self._sorted_because = "dtype"
        return self._sorted_because is None

    # -- spill (memory revocation) ------------------------------------

    def _revoke(self) -> int:
        """Pool callback: move buffered build batches to host RAM,
        hash-partitioned (reference: HashBuilderOperator.java:159-179
        SPILLING_INPUT). Runs on the slow path only — the per-part
        compaction syncs are irrelevant next to freeing HBM."""
        if self._finished or not self._batches:
            return 0
        from presto_tpu.execution.memory import batch_bytes
        freed = sum(batch_bytes(b) for b in self._batches)
        self._spill_batches(self._batches)
        self._batches = []
        self._total = None
        self.ctx.release_all()
        return freed

    def _spill_batches(self, batches: List[Batch]) -> None:
        if self._spill is None:
            self._spill = [[] for _ in range(SPILL_PARTS)]
        for b in batches:
            keys = [b.columns[k].astuple() for k in self.key_names]
            part = _spill_part_of(ops_common.row_hash(keys),
                                  SPILL_PARTS)
            spill_batch_to_host(b, part, self._spill, self.ctx)

    def get_output(self) -> Optional[Batch]:
        return None

    def _publish_df(self, merged: Optional[Batch]) -> None:
        """Publish per-key dynamic filters: running bounds always,
        plus a bounded DISTINCT SET computed in one shot from the
        merged build column when it is resident (the spill path keeps
        bounds only). The overflow resolution is one host sync — at
        build finish, next to the existing total-count sync."""
        from presto_tpu.execution import dynamic_filters as df
        for key, df_id, reg in self._df_publish:
            if df_id in self._df_state:
                mn, mx = self._df_state[df_id]
                dset = None
                if merged is not None:
                    c = merged.columns[key]
                    vals, n, ovf = df.distinct_set(
                        c.data, c.mask & merged.row_valid)
                    if not bool(to_host(ovf)):
                        dset = (vals, n)
                reg.publish(df_id, mn, mx, dset)
            else:
                # empty build side: publish the impossible range (and
                # the empty set) so inner-join probe scans prune
                # everything
                col = dict(
                    (n, t) for n, t, _ in (self.schema_cols or []))
                if key in col:
                    mn, mx = df.bounds_init(col[key].np_dtype)
                    reg.publish(df_id, mn, mx)

    def finish(self) -> None:
        if self._finished:
            return
        t0 = time.perf_counter_ns()
        # the build barrier by name on jax.profiler's timeline (no
        # ledger charge: the kernels and waits inside keep theirs)
        with TraceAnnotation("join_build:finish"):
            self._finish()
        METRICS.inc("presto_tpu_join_build_finish_ns_total",
                    time.perf_counter_ns() - t0)

    def _finish(self) -> None:
        self._finished = True
        self.ctx.unregister_revocable()
        if self._spill is not None:
            self._publish_df(None)
            if self._batches:  # revoked mid-stream leftovers
                self._spill_batches(self._batches)
                self._batches = []
                self.ctx.release_all()
            self.bridge.spilled = SpilledBuild(
                SPILL_PARTS, self.key_names, self.schema_cols,
                self._spill, self.key_dicts)
            return
        # one device->host sync for the whole build side (not per batch)
        key_stats = to_host(self._key_stats) \
            if self._key_stats is not None else None
        if key_stats is not None:
            total = int(key_stats[0])
        else:
            total = int(to_host(self._total)) \
                if self._total is not None else 0
        # shape bucketing: the probe kernel's jit cache keys on the
        # BUILD table shape too — landing build capacities on the
        # coarse ladder lets different tables/scale factors reuse one
        # compiled probe (padding-clip keeps the dead tail out of
        # every search span, see ops/join.py)
        cap = operator_capacity(total)
        # packing live rows to the front pays only when it lets the
        # merged batch shrink onto a smaller rung. When the inputs'
        # lanes already fit the rung it would move rows inside a batch
        # of the same shape, and no reader needs the order: the direct
        # layout addresses rows by index (`slot_of`), the sorted one
        # permutes every row itself and clips its spans at the first
        # dead one, `distinct_set` and FULL's unmatched rows go by
        # `row_valid`. So the lanes stay where they arrived
        packed = sum(b.capacity for b in self._batches) > cap
        if packed:
            merged = Batch.concat(self._batches, cap, live_rows=total)
        elif self._batches:
            merged = Batch.concat_lanes(self._batches, cap)
        elif self.schema_cols is not None:
            # a pruned/empty build side is a legal input (e.g. a fully
            # pushed-down scan): index an all-invalid batch
            from presto_tpu.batch import empty_batch
            merged = _remap_keys(empty_batch(self.schema_cols),
                                 self.key_names, self.key_dicts)
        else:
            raise RuntimeError("empty build side needs schema plumbing")
        self._publish_df(merged)
        table = self._build_direct(merged, key_stats)
        if table is None:
            METRICS.inc("presto_tpu_join_direct_fallback_total",
                        reason=self._sorted_because)
            table = join_ops.build_for_backend(merged, self.key_names)
        # what was indexed, from numbers this finish already holds
        # (the one fetch above and static shapes: no new sync)
        METRICS.inc("presto_tpu_join_builds_total", layout=table.layout)
        METRICS.inc("presto_tpu_join_build_rows_total", total,
                    layout=table.layout)
        METRICS.inc("presto_tpu_join_build_lanes_total", merged.capacity,
                    layout=table.layout)
        METRICS.inc("presto_tpu_join_build_batches_total",
                    len(self._batches), layout=table.layout)
        # by 0 all the same: the series then says "never packed"
        METRICS.inc("presto_tpu_join_build_packed_lanes_total",
                    merged.capacity if packed else 0,
                    layout=table.layout)
        if table.layout == "direct":
            METRICS.inc("presto_tpu_join_direct_table_slots_total",
                        table.slot_of.shape[0])
        else:
            METRICS.inc(_SORTED_BYTES,
                        total * _key_bytes(merged, self.key_names))
        self.bridge.table = table
        self._batches = []

    def _build_direct(self, merged: Batch, key_stats):
        """The direct table when the build side allows it, else None
        with `_sorted_because` saying why not. `key_stats` is the
        host's copy of `_key_stats` (None: no batch ever arrived)."""
        if not self._direct_candidate(merged):
            return None
        stats = self._key_stats
        if key_stats is None:       # an empty build: the empty range
            stats = key_stats = join_ops.key_stats_init()
        table_len = join_ops.direct_table_len(
            int(key_stats[1]), int(key_stats[2]), merged.capacity)
        if table_len is None:
            self._sorted_because = "spread"
            return None
        table = join_ops.build_direct(merged, self.key_names[0], stats,
                                      table_len)
        if table is None:
            self._sorted_because = "duplicate"
            return None
        self.ctx.reserve_bytes(4 * table_len)
        return table

    def is_finished(self) -> bool:
        return self._finished

    def close(self) -> None:
        # drop the build table so a closed lifespan instance releases
        # its REAL HBM, not just its pool ledger entry
        self.ctx.unregister_revocable()
        self._batches = []
        self._spill = None
        self.bridge.table = None
        self.bridge.spilled = None


#: probe-kernel LRU cache keyed by the join shape + fused-expression
#: fingerprints, so re-running a query (or another query with the same
#: join + projection forest) reuses the compiled XLA program — the
#: same contract as core._FP_KERNEL_CACHE.
_PROBE_KERNEL_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_PROBE_KERNEL_CACHE_MAX = 256


class ProbeKernel(NamedTuple):
    """One join shape's probe programs (make_probe_kernel).

    `whole(table, batch, matched, out_capacity)` -> (Batch, overflow,
    live, matched) is the probe in one dispatch. An aligned probe
    (ops/join.py:aligned_expansion) whose live count is worth waiting
    for runs as two instead, the count read on the host between them:
    `front(table, batch, matched)` -> (side, brow, verified, overflow,
    live, matched) searches at the batch's width and gathers no build
    column; `back(build, side, brow, verified, live, width)` -> Batch
    gathers both sides' columns at the STATIC `width` the count
    allows, and runs the fused downstream filter/projections there."""
    whole: Callable
    front: Callable
    back: Callable


def make_probe_kernel(key_names: Tuple[str, ...], join_type: str,
                      probe_output: Tuple[str, ...],
                      build_output: Tuple[str, ...],
                      build_keys: Tuple[str, ...],
                      build_rename: Optional[dict] = None,
                      fused_filter=None,
                      fused_projections=None,
                      input_dicts=None,
                      verify: str = "hash",
                      pre=None, pre_key=None, pre_key_dicts=None):
    """Build the jitted fused probe->project programs (a ProbeKernel).

    In `whole` the candidate search, row expansion, build-side rename,
    and the DOWNSTREAM filter/projection forest all trace into ONE
    dispatch, so expanded join rows are materialized once — not
    gathered at the probe and then re-read by a separate FilterProject
    pass over the same out_capacity-wide arrays. `front` and `back`
    are the same bodies cut where the live rows are known (the aligned
    layout only): rename, filter and projections move to the back.
    `matched` is the FULL join's per-build-row flag array (pass None
    otherwise; it passes through untouched).

    `pre` extends the fusion UPSTREAM (the whole-fragment compiler,
    operators/fused_fragment.py): a traceable batch -> batch chain —
    the scan-side filter/project forest — applied inside the probe
    dispatch before hashing, including the unified-dictionary key
    remap (`pre_key_dicts`, parallel to key_names) that the operator
    otherwise performs host-side per batch. The remap tables bake in
    as constants: the chain output's dictionaries are static column
    metadata at trace time. `pre_key` fingerprints the chain for the
    kernel cache."""
    rename = tuple(sorted((build_rename or {}).items()))
    fused_projections = tuple(fused_projections or ())
    exprs = ([fused_filter] if fused_filter is not None else []) \
        + [ce for _, ce in fused_projections]
    key = None
    if all(ce.ir is not None for ce in exprs) \
            and (pre is None or pre_key is not None):
        try:
            from presto_tpu.expr.ir import fingerprint
            key = (key_names, join_type, probe_output, build_output,
                   build_keys, rename, verify, input_dicts,
                   pre_key, tuple(pre_key_dicts or ()),
                   fingerprint(fused_filter.ir)
                   if fused_filter is not None else None,
                   tuple((n, fingerprint(ce.ir), ce.dictionary)
                         for n, ce in fused_projections))
            cached = _PROBE_KERNEL_CACHE.get(key)
            if cached is not None:
                _PROBE_KERNEL_CACHE.move_to_end(key)
                return cached
        except TypeError:  # unhashable literal — just don't cache
            key = None

    rn_map = dict(rename)

    _pre_batch = None
    if pre is not None:
        def _pre_batch(b: Batch) -> Batch:
            # same unified-dictionary alignment the unfused operator
            # performs host-side per batch — here it traces into the
            # fragment program (the remap tables bake in as constants)
            return _remap_keys(pre(b), key_names, pre_key_dicts)

    def _project(out: Batch):
        """Rename + fused filter/projections over the expanded batch
        (traced INSIDE the expand dispatch, so join output rows
        materialize once). Returns (batch, live count)."""
        cols = {rn_map.get(n, n): c for n, c in out.columns.items()} \
            if rename else dict(out.columns)
        rv = out.row_valid
        if fused_filter is not None or fused_projections:
            cap = rv.shape[0]
            env = {n: (c.data, c.mask) for n, c in cols.items()}
            if fused_filter is not None:
                with jax.named_scope("filter"):
                    d, m = fused_filter.fn(env)
                    rv = rv & jnp.broadcast_to(d & m, (cap,))
            if fused_projections:
                cols = {}
                with jax.named_scope("project"):
                    for name, ce in fused_projections:
                        d, m = ce.fn(env)
                        d = jnp.broadcast_to(
                            jnp.asarray(d, ce.type.np_dtype), (cap,))
                        cols[name] = Column(
                            d, jnp.broadcast_to(m, (cap,)), ce.type,
                            ce.dictionary)
        out = Batch(cols, rv)
        return out, jnp.sum(rv)

    def _expand_project(table, batch, lo_enc, h2, matched,
                        out_capacity: int):
        with jax.named_scope("join_probe"):
            out, overflow, _, matched = join_ops._expand_from_enc(
                table, batch, key_names, lo_enc, matched,
                out_capacity, join_type, probe_output, build_output,
                build_keys, verify, h2=h2)
        out, live = _project(out)
        return out, overflow, live, matched

    def _front(table, batch, lo_enc, matched):
        with jax.named_scope("join_probe"):
            return join_ops.aligned_front(
                table, batch, key_names, lo_enc, matched, join_type,
                probe_output, build_keys, verify)

    def _materialize(build, side, brow, verified, live, width: int):
        with jax.named_scope("join_probe"):
            packed = join_ops.aligned_back(
                build, side, brow, verified, live, width, join_type,
                build_output)
        # the fused filter narrows row_valid at `width` lanes: no
        # second compaction follows
        return _project(packed)[0]

    # a probe with a fused upstream chain is a whole-fragment program:
    # family `fragment`, device name `fragment_join_probe`. The back
    # half is `join_probe_materialize` under either front: the
    # join_probe group's device time is the whole probe's
    family, part = ("fragment", "join_probe") if pre is not None \
        else ("join_probe", None)
    back = _kernels.jit(_materialize, "join_probe", "materialize",
                        static_argnums=(5,))
    if ops_common.cpu_backend():
        # staged: the candidate search materializes ONCE (see
        # ops/join.py on XLA:CPU fusion re-materialization); the probe
        # hash2 rides across the boundary so expand needn't rehash
        def staged(p):
            return p if part is None else f"{part}_{p}"
        stage1 = _kernels.jit(_front, family, staged("stage1"))
        stage2 = _kernels.jit(_expand_project, family, staged("stage2"),
                              static_argnums=(5,))
        if _pre_batch is None:
            def search(table, batch):
                if table.layout == "direct":
                    return batch, join_ops._direct_jit(
                        table, batch, key_names), None
                h, h2 = join_ops._hash_jit(batch, key_names)
                return batch, join_ops._search_jit(table, h, h2,
                                                   verify), h2
            jit_list = [stage1, stage2, join_ops._hash_jit,
                        join_ops._search_jit, join_ops._direct_jit]
        else:
            # the upstream chain + remap fold into the HASH dispatch
            # (stage0): still two probe-side materializations, but
            # the former FilterProject dispatch — and its deferred
            # count/compact round — are gone
            @functools.partial(_kernels.jit, family=family,
                               part=f"{part}_stage0")
            def stage0(batch):
                b = _pre_batch(batch)
                h, h2 = join_ops._probe_hashes(b, key_names)
                return b, h, h2

            # a direct table hashes nothing: its stage0 is the chain
            # and the one-gather search
            @functools.partial(_kernels.jit, family=family,
                               part=f"{part}_stage0_direct")
            def stage0_direct(table, batch):
                b = _pre_batch(batch)
                return b, join_ops._direct_enc(table, b, key_names)

            def search(table, batch):
                if table.layout == "direct":
                    return stage0_direct(table, batch) + (None,)
                b, h, h2 = stage0(batch)
                return b, join_ops._search_jit(table, h, h2, verify), h2
            jit_list = [stage0, stage0_direct, stage1, stage2,
                        join_ops._search_jit]

        def whole(table, batch, matched, out_capacity: int):
            b, lo_enc, h2 = search(table, batch)
            return stage2(table, b, lo_enc, h2, matched, out_capacity)

        def front(table, batch, matched):
            b, lo_enc, _ = search(table, batch)
            return stage1(table, b, lo_enc, matched)
    else:
        def _search(table, batch):
            if _pre_batch is not None:
                batch = _pre_batch(batch)
            with jax.named_scope("join_probe"):
                return batch, join_ops._candidates_enc(
                    table, batch, key_names, verify)

        def _whole(table, batch, matched, out_capacity: int):
            batch, lo_enc = _search(table, batch)
            return _expand_project(table, batch, lo_enc, None, matched,
                                   out_capacity)

        def _front_of(table, batch, matched):
            batch, lo_enc = _search(table, batch)
            return _front(table, batch, lo_enc, matched)

        # one program a build layout, so that a module is the sorted
        # layout's hash-and-search or the direct one's gather, never
        # both: `join_probe` / `fragment_join_probe` probe a direct
        # table, `..._sorted` a sorted one
        parts = {"direct": part,
                 "sorted": "sorted" if part is None else f"{part}_sorted"}
        wholes = {layout: _kernels.jit(_whole, family, p,
                                       static_argnums=(3,))
                  for layout, p in parts.items()}
        fronts = {layout: _kernels.jit(_front_of, family, p)
                  for layout, p in parts.items()}
        jit_list = [*wholes.values(), *fronts.values()]

        def whole(table, batch, matched, out_capacity: int):
            return wholes[table.layout](table, batch, matched,
                                        out_capacity)

        def front(table, batch, matched):
            return fronts[table.layout](table, batch, matched)

    # compile-vs-execute attribution rides the cached kernels. Both
    # forms are host wrappers over several jits — on the CPU the
    # per-probe stages plus the shared module-level search jits, on
    # the TPU one program a layout — so all executable caches are
    # polled for compile detection (one list for both: a stage the
    # other form compiled reads as this one's at worst). A probe with
    # a fused upstream chain is a whole-fragment program (`fragment`
    # family).
    kernel = ProbeKernel(
        _kernels.instrument_kernel(whole, family, jits=jit_list),
        _kernels.instrument_kernel(front, family, jits=jit_list),
        _kernels.instrument_kernel(back, "join_probe"))

    if key is not None:
        _PROBE_KERNEL_CACHE[key] = kernel
        while len(_PROBE_KERNEL_CACHE) > _PROBE_KERNEL_CACHE_MAX:
            _PROBE_KERNEL_CACHE.popitem(last=False)
    return kernel


class LookupJoinOperator(Operator):
    """Probe side (reference: LookupJoinOperator.java:53, processProbe:392).

    Per probe batch: ONE fused dispatch (candidate runs + expansion) and
    ZERO host syncs. The output capacity is probe capacity x
    `expansion_factor` (1 is exact for every FK->PK join, where each
    probe row matches at most one build row); the kernel's on-device
    overflow flag accumulates across batches and is fetched once per
    query by the drive loop — tripping it retries the query with a 4x
    factor via JoinCapacityExceeded.

    An aligned probe (unique-run or direct build, output as wide as
    the batch) above COMPACT_FLOOR materializes LATE: the dispatch is
    the search and the count alone, and the batch's second dispatch,
    one driver pass later, gathers both sides' columns at the live
    rows' bucket (ProbeKernel.front / .back). It takes the place of
    the deferred shrink, which no longer follows such a probe."""

    def __init__(self, ctx: OperatorContext, bridge: JoinBridge,
                 key_names: Tuple[str, ...], join_type: str,
                 probe_output: Sequence[str], build_output: Sequence[str],
                 build_rename: Optional[dict] = None,
                 build_keys: Optional[Tuple[str, ...]] = None,
                 key_dicts: Optional[List[Optional[tuple]]] = None,
                 expansion_factor: int = 1,
                 probe_schema: Optional[Sequence[tuple]] = None,
                 probe_kernel=None, tail_kernel=None,
                 pre_fused: bool = False):
        super().__init__(ctx)
        self.bridge = bridge
        #: the upstream filter/project chain (and the unified-dict key
        #: remap) are traced INSIDE the probe kernel — the host-side
        #: per-batch remap must not run twice
        self.pre_fused = pre_fused
        self.key_names = key_names
        self.build_keys = build_keys  # None -> kernel defaults
        self.key_dicts = key_dicts
        self.join_type = join_type
        self.probe_output = tuple(probe_output)
        self.build_output = tuple(build_output)
        self.build_rename = build_rename or {}
        self.expansion_factor = max(1, int(expansion_factor))
        # fused probe->project kernel (built by the factory; a bare
        # operator constructed without one gets the unfused default)
        self._kernel = probe_kernel if probe_kernel is not None else \
            make_probe_kernel(
                tuple(key_names), join_type, self.probe_output,
                self.build_output,
                tuple(build_keys) if build_keys else tuple(key_names),
                self.build_rename)
        # FULL OUTER tail projection: the fused filter/projections must
        # also apply to the unmatched-build batch (None = identity)
        self._tail_kernel = tail_kernel
        # FULL OUTER state: per-build-row matched flags (device array,
        # scatter-updated by every probe dispatch) and the NULL probe
        # side's schema. Key columns take the planner's unified
        # dictionary — probe outputs were remapped onto it, and the
        # final unmatched batch must concat with them.
        self._matched = None
        self._outer_emitted = False
        if probe_schema is not None and key_dicts:
            fix = {k: d for k, d in zip(key_names, key_dicts)
                   if d is not None}
            probe_schema = [(n, t, fix.get(n, dic))
                            for n, t, dic in probe_schema]
        self.probe_schema = tuple(probe_schema) if probe_schema \
            is not None else None
        self._overflow = None
        # two-slot output queue: a probed batch is emitted one driver
        # PASS after its dispatch, so its live-count d2h copy (started
        # at dispatch) genuinely overlaps the next batch's probe
        # instead of blocking microseconds later in the same pass
        self._pending: List = []
        self._finishing = False
        # spilled-build probe state: current partition's table, the
        # host-buffered probe rows of later partitions, and the replay
        # cursor through them
        self._cur_table = None
        self._cur_part = -1
        self._probe_bufs = None
        #: the layout of the build this operator probed and its keys'
        #: bytes a row, for count_drained (None: never probed)
        self._layout: Optional[str] = None
        self._probe_key_bytes = 0
        ctx.driver_context.deferred_checks.append(self._deferred_check)

    def _deferred_check(self):
        """(flag_array | None, exception factory) for the drive loop's
        single end-of-query fetch."""
        if self._overflow is None:
            return None, None
        return self._overflow, \
            lambda: JoinCapacityExceeded(self.expansion_factor * 4)

    def is_blocked(self):
        return False if self.bridge.ready else "waiting for join build"

    def needs_input(self) -> bool:
        return self.bridge.ready and len(self._pending) < 2 \
            and not self._finishing

    def _probe(self, table, batch: Batch) -> Callable[[], Batch]:
        """Dispatch one batch's probe; the returned call emits its
        output a driver pass later, when the live count is on the
        host."""
        # a direct table's keys are unique: no probe row expands, so
        # the output is aligned to the probe batch whatever the factor
        cap = batch.capacity if table.layout == "direct" else \
            bucket_capacity(batch.capacity * self.expansion_factor)
        if self.join_type == "full" and self._matched is None:
            self._matched = jnp.zeros(table.sorted_hash.shape[0],
                                      dtype=bool)
        self._layout = table.layout
        # the build's key columns: the probe's share their types, and
        # a fused upstream chain makes the probe's inside the kernel
        self._probe_key_bytes = _key_bytes(
            table.batch, self.build_keys or self.key_names)
        METRICS.inc(_LANES, batch.capacity, stage="searched")
        # selective joins emit few rows into a fat capacity; left
        # uncompacted that padding would ride every downstream
        # exchange/pad/spool. An aligned probe gathers its columns
        # only once the count is known, at the live rows' width; any
        # other hands its count to the deferred-compact protocol.
        late = cap > COMPACT_FLOOR and join_ops.aligned_expansion(
            table, self.join_type, cap, batch.capacity)
        if late:
            side, brow, verified, ovf, live, matched = \
                self._kernel.front(table, batch, self._matched)
            emit = functools.partial(
                self._materialize, table.batch, side, brow, verified,
                start_async_copy(live))
        else:
            out, ovf, total, matched = self._kernel.whole(
                table, batch, self._matched, cap)
            METRICS.inc(_LANES, cap, stage="materialized")
            emit = functools.partial(
                end_deferred_compact,
                *begin_deferred_compact(out, total))
        if self.join_type == "full":
            self._matched = matched
        self._overflow = ovf if self._overflow is None \
            else self._overflow | ovf
        return emit

    def _materialize(self, build: Batch, side: Batch, brow, verified,
                     live) -> Batch:
        """The aligned probe's back half, at the width
        end_deferred_compact would shrink to: the count's copy started
        at dispatch, so this read is normally a cache hit."""
        n = int(to_host(live))
        width = min(operator_capacity(n, floor=COMPACT_MIN),
                    side.capacity)
        METRICS.inc(_LANES, width, stage="materialized")
        if width < side.capacity:
            count_flag_pack(side.capacity, width)
        return self._kernel.back(build, side, brow, verified, live,
                                 width)

    def add_input(self, batch: Batch) -> None:
        self._count_in(batch)
        # pad BEFORE remap/probe: the probe kernel (and its output
        # capacity) key on the probe batch shape
        batch = pad_for_kernel(batch)
        if not self.pre_fused:
            batch = _remap_keys(batch, self.key_names, self.key_dicts)
        if self.bridge.table is not None:
            self._pending.append(self._probe(self.bridge.table, batch))
            return
        # spilled build: probe the resident partition now, park the
        # rest of the batch's rows on the host per partition
        assert self.join_type != "full", \
            "full join builds are planned non-spillable"
        assert not self.pre_fused, \
            "fusion pass must not pre-fuse a spillable join probe " \
            "(the spill partitioner reads key columns host-side)"
        import jax
        sp = self.bridge.spilled
        if self._probe_bufs is None:
            self._probe_bufs = [[] for _ in range(sp.n_parts)]
            self._cur_part = 0
            self._cur_table = sp.build_part(0)
        keys = [batch.columns[k].astuple() for k in self.key_names]
        part = _spill_part_of(ops_common.row_hash(keys), sp.n_parts)
        later = [[] for _ in range(sp.n_parts)]
        spill_batch_to_host(Batch(batch.columns,
                                  batch.row_valid & (part != 0)),
                            part, later, self.ctx)
        for p in range(1, sp.n_parts):
            self._probe_bufs[p].extend(later[p])
        self._pending.append(self._probe(
            self._cur_table, batch.filter(part == 0)))

    def _emit_outer(self) -> Batch:
        """FULL OUTER tail: the never-matched build rows, NULL probe
        side. One blocking compact — once per query, after the last
        probe batch, so there is nothing left to overlap with."""
        assert self.probe_schema is not None, \
            "full join needs the probe schema for its NULL side"
        table = self.bridge.table
        matched = self._matched if self._matched is not None else \
            jnp.zeros(table.sorted_hash.shape[0], dtype=bool)
        out, total = join_ops.unmatched_build(
            table, matched, self.probe_schema, self.build_output)
        if self.build_rename:
            out = out.rename(self.build_rename)
        if self._tail_kernel is not None:
            # once per query: route the outer tail through the same
            # filter/projections the probe kernel fused
            out = self._tail_kernel(out)
            total = jnp.sum(out.row_valid)
        self._outer_emitted = True
        b, tok = begin_deferred_compact(out, total)
        return end_deferred_compact(b, tok)

    def get_output(self) -> Optional[Batch]:
        # emit the HEAD only once a second batch is queued behind it
        # (or input ended): by then its count fetch has overlapped a
        # full probe dispatch
        if self._pending and (len(self._pending) > 1
                              or self._finishing):
            return self._count_out(self._pending.pop(0)())
        if self._pending or not self._finishing:
            return None
        if self.join_type == "full" and not self._outer_emitted:
            return self._count_out(self._emit_outer())
        if self._probe_bufs is None:
            return None
        # drain the parked partitions: restore one probe batch per call
        import jax
        sp = self.bridge.spilled
        while self._cur_part < sp.n_parts:
            if self._probe_bufs[self._cur_part]:
                host = self._probe_bufs[self._cur_part].pop(0)
                emit = self._probe(self._cur_table, jax.device_put(host))
                return self._count_out(emit())
            if self._cur_part + 1 >= sp.n_parts:
                break
            self._cur_part += 1
            self._cur_table = sp.build_part(self._cur_part)
        self._probe_bufs = None  # fully drained
        self._cur_table = None
        return None

    def finish(self) -> None:
        self._finishing = True

    def is_finished(self) -> bool:
        return self._finishing and not self._pending \
            and self._probe_bufs is None \
            and (self.join_type != "full" or self._outer_emitted)

    def count_drained(self, stats) -> None:
        """The live probe rows by the layout they were probed against
        and, against a sorted build, what its search and row index
        read (presto_tpu_join_sorted_bytes_total)."""
        if self._layout is None:
            return
        METRICS.inc("presto_tpu_join_probe_rows_total", stats.input_rows,
                    layout=self._layout)
        if self._layout == "sorted":
            METRICS.inc(_SORTED_BYTES,
                        stats.input_rows * self._probe_key_bytes
                        + stats.output_rows * _ROW_INDEX_BYTES)


class SemiJoinOperator(Operator):
    """WHERE x IN (subquery) / EXISTS — filters probe rows by membership
    (reference: HashSemiJoinOperator; `negate` gives NOT IN/NOT EXISTS
    anti-join semantics for non-null keys).

    Semi joins are usually highly selective, so outputs go through the
    same one-round-delayed count/compact protocol as lookup-join
    outputs: left at full capacity, the dead lanes would ride every
    downstream sort/merge/exchange (the round-3 Q18 failure mode —
    56-live-row batches at 64k capacity feeding the final
    aggregation)."""

    row_series = ("presto_tpu_semi_join_probe_rows_total",
                  "presto_tpu_semi_join_matched_rows_total")

    def __init__(self, ctx: OperatorContext, bridge: JoinBridge,
                 key_names: Tuple[str, ...], negate: bool,
                 build_keys: Optional[Tuple[str, ...]] = None,
                 key_dicts: Optional[List[Optional[tuple]]] = None):
        super().__init__(ctx)
        self.bridge = bridge
        self.key_names = key_names
        self.build_keys = build_keys
        self.key_dicts = key_dicts
        self.negate = negate
        # two-slot queue: emit a batch one driver pass after its
        # dispatch so the live-count d2h copy overlaps the next probe
        self._pending: List = []
        self._finishing = False

    def is_blocked(self):
        return False if self.bridge.ready else "waiting for semi build"

    def needs_input(self) -> bool:
        return self.bridge.ready and len(self._pending) < 2 \
            and not self._finishing

    def add_input(self, batch: Batch) -> None:
        self._count_in(batch)
        # pad first so the mark kernel keys on the bucket AND the
        # filtered output batch shares the padded capacity
        batch = pad_for_kernel(batch)
        probe = _remap_keys(batch, self.key_names, self.key_dicts)
        found, valid = join_ops.semi_mark(self.bridge.table, probe,
                                          self.key_names, self.build_keys)
        keep = (~found & valid) if self.negate else found
        self._pending.append(begin_deferred_compact(batch.filter(keep)))

    def get_output(self) -> Optional[Batch]:
        if self._pending and (len(self._pending) > 1
                              or self._finishing):
            out, total = self._pending.pop(0)
            return self._count_out(end_deferred_compact(out, total))
        return None

    def finish(self) -> None:
        self._finishing = True

    def is_finished(self) -> bool:
        return self._finishing and not self._pending


def _remap_keys(batch: Batch, key_names, key_dicts) -> Batch:
    """Align string key columns to the planner's unified dictionaries."""
    if not key_dicts:
        return batch
    cols = dict(batch.columns)
    for name, dic in zip(key_names, key_dicts):
        if dic is not None and cols[name].dictionary != dic:
            cols[name] = remap_column(cols[name], dic)
    return Batch(cols, batch.row_valid)


class HashBuildOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, bridge: JoinBridge,
                 key_names: Sequence[str],
                 key_dicts: Optional[List[Optional[tuple]]] = None,
                 schema_cols: Optional[Sequence[tuple]] = None,
                 spillable: bool = False,
                 df_publish: Optional[List[tuple]] = None,
                 consumer_layouts: Sequence[str] = ("sorted",)):
        super().__init__(operator_id, "hash_build")
        self.bridge = bridge
        self.key_names = tuple(key_names)
        self.key_dicts = key_dicts
        self.schema_cols = schema_cols
        self.spillable = spillable
        self.df_publish = df_publish
        #: the build layouts the operator across the bridge can probe
        #: (its factory's `readable_layouts`): a static fact of the plan
        self.consumer_layouts = tuple(consumer_layouts)

    def create(self, driver_context: DriverContext) -> Operator:
        return HashBuildOperator(
            OperatorContext(self.operator_id, self.name, driver_context),
            self.bridge, self.key_names, self.key_dicts,
            self.schema_cols, self.spillable, self.df_publish,
            self.consumer_layouts)


class LookupJoinOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, bridge: JoinBridge,
                 key_names: Sequence[str], join_type: str,
                 probe_output: Sequence[str], build_output: Sequence[str],
                 build_rename: Optional[dict] = None,
                 build_keys: Optional[Sequence[str]] = None,
                 key_dicts: Optional[List[Optional[tuple]]] = None,
                 expansion_factor: int = 1,
                 probe_schema: Optional[Sequence[tuple]] = None):
        super().__init__(operator_id, f"lookup_join({join_type})")
        self.bridge = bridge
        self.key_names = tuple(key_names)
        self.build_keys = tuple(build_keys) if build_keys else None
        self.key_dicts = key_dicts
        self.join_type = join_type
        self.probe_output = probe_output
        self.build_output = build_output
        self.build_rename = build_rename
        self.expansion_factor = expansion_factor
        self.probe_schema = probe_schema
        self._fused_filter = None
        self._fused_projections = None
        self._fused_dicts = None
        #: estimated surviving-row fraction of the PLANNING-TIME fused
        #: filter (probe-tail fusion; None = no filter / unknown) —
        #: read by planner/fusion.py so chains this probe feeds into
        #: fold terminals inherit the sparsity its in-trace filter
        #: leaves behind
        self.fused_selectivity = None
        #: provenance of fused_selectivity ("static" | "history")
        self.fused_sel_provenance = "static"
        self._pre = None        # (body, chain_key) upstream chain
        self._kernels = None

    @staticmethod
    def readable_layouts(join_type: str) -> Tuple[str, ...]:
        """Build layouts a lookup join of `join_type` can probe. The
        direct one has no hash runs, so only the aligned expansion
        reads it: inner and left. FULL keeps per-build-row matched
        flags in sorted order; semi/anti joins are another operator."""
        return join_ops.LAYOUTS if join_type in ("inner", "left") \
            else ("sorted",)

    @property
    def fused(self) -> bool:
        return self._fused_filter is not None \
            or self._fused_projections is not None

    @property
    def pre_fused(self) -> bool:
        return self._pre is not None

    def fuse(self, filter_expr, projections, input_dicts=None,
             selectivity=None, sel_provenance: str = "static") -> None:
        """Planner peephole: absorb the FilterProject that would
        otherwise follow this join, so the expression forest evaluates
        inside the probe dispatch (expanded rows materialize ONCE).
        `selectivity` is the absorbed filter's estimated surviving
        fraction (kept on `fused_selectivity` for the fusion pass's
        selective-chain gate). Only legal before the first create()."""
        assert self._kernels is None, "fuse() after create()"
        assert not self.fused, "join already fused a projection"
        self._fused_filter = filter_expr
        self._fused_projections = list(projections) if projections \
            else None
        self._fused_dicts = input_dicts
        if filter_expr is not None:
            self.fused_selectivity = selectivity
            self.fused_sel_provenance = sel_provenance

    def fuse_pre(self, pre, pre_key, name: str) -> None:
        """Whole-fragment fusion (planner/fusion.py): absorb the
        UPSTREAM filter/project chain, so scan -> chain -> probe [->
        fused projections] runs as one traced program per batch (the
        unified-dictionary key remap moves into the trace with it).
        Only legal before the first create(); the pass excludes full
        joins and spill-eligible builds."""
        assert self._kernels is None, "fuse_pre() after create()"
        assert self._pre is None, "join already fused an upstream chain"
        assert self.join_type != "full", \
            "full-join probes keep the host-side remap (outer tail)"
        self._pre = (pre, pre_key)
        self.name = name

    def _build_kernels(self):
        pre, pre_key = self._pre if self._pre is not None \
            else (None, None)
        pre_key_dicts = tuple(d if d is not None else None
                              for d in (self.key_dicts or ())) \
            if pre is not None and self.key_dicts else None
        probe_kernel = make_probe_kernel(
            self.key_names, self.join_type, tuple(self.probe_output),
            tuple(self.build_output),
            self.build_keys if self.build_keys else self.key_names,
            self.build_rename, self._fused_filter,
            self._fused_projections, self._fused_dicts,
            pre=pre, pre_key=pre_key, pre_key_dicts=pre_key_dicts)
        tail_kernel = None
        if self.join_type == "full" and self.fused:
            from presto_tpu.operators.core import (
                make_filter_project_kernel,
            )
            tail_kernel = make_filter_project_kernel(
                self._fused_filter, self._fused_projections or [],
                self._fused_dicts)
        return probe_kernel, tail_kernel

    def create(self, driver_context: DriverContext) -> Operator:
        if self._kernels is None:
            self._kernels = self._build_kernels()
        probe_kernel, tail_kernel = self._kernels
        return LookupJoinOperator(
            OperatorContext(self.operator_id, self.name, driver_context),
            self.bridge, self.key_names, self.join_type,
            self.probe_output, self.build_output, self.build_rename,
            self.build_keys, self.key_dicts, self.expansion_factor,
            self.probe_schema, probe_kernel, tail_kernel,
            pre_fused=self.pre_fused)


class SemiJoinOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, bridge: JoinBridge,
                 key_names: Sequence[str], negate: bool = False,
                 build_keys: Optional[Sequence[str]] = None,
                 key_dicts: Optional[List[Optional[tuple]]] = None):
        super().__init__(operator_id, "semi_join")
        self.bridge = bridge
        self.key_names = tuple(key_names)
        self.build_keys = tuple(build_keys) if build_keys else None
        self.key_dicts = key_dicts
        self.negate = negate

    def create(self, driver_context: DriverContext) -> Operator:
        return SemiJoinOperator(
            OperatorContext(self.operator_id, self.name, driver_context),
            self.bridge, self.key_names, self.negate, self.build_keys,
            self.key_dicts)
