"""Aggregation operator (reference: HashAggregationOperator.java:47 with
InMemoryHashAggregationBuilder; AggregationOperator for global aggs;
steps PARTIAL/FINAL/SINGLE as in AggregationNode.Step).

The device kernel is ops/hashagg.agg_step — a functional fold. This
operator owns the fold state, grows `max_groups` on overflow (the
rehash analog: the pre-step state is kept until the post-step overflow
flag is checked, so no data is lost), and finalizes on finish().
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.batch import Batch, Column, bucket_capacity
from presto_tpu.expr.compile import CompiledExpr
from presto_tpu.operators.base import (
    DriverContext, Operator, OperatorContext, OperatorFactory,
)
from presto_tpu.ops import hashagg
from presto_tpu.telemetry import kernels as _kernels
from presto_tpu.types import Type


class GroupLimitExceeded(Exception):
    """Raised at finalize when distinct groups exceeded max_groups.
    Carries the suggested retry size; the runner re-executes the query
    with session max_groups raised."""

    def __init__(self, suggested: int):
        super().__init__(f"group-by overflow; retry with {suggested}")
        self.suggested = suggested


@dataclasses.dataclass
class AggSpec:
    """One aggregate in the operator's output."""
    out_name: str
    function: hashagg.AggFunction
    input: Optional[CompiledExpr]       # None for count(*)
    mask: Optional[CompiledExpr] = None  # FILTER (WHERE ...) — later


# AggFunction instances are frozen dataclasses -> hashable static
# args; the factories are lru_cached so the same spec hits the jit
# cache across queries.
#: log-depth tree merge of buffered per-batch partials (sort path),
#: instrumented as its own kernel family (previously its compile time
#: landed in busy as "execute" — the attribution gap flagged in
#: CHANGES.md after the telemetry PR)
_jit_merge = _kernels.jit(hashagg.merge_partials, "hashagg_merge",
                          static_argnums=(1, 2))
_instr = _kernels.instrument_kernel
_merge_instr = _instr(_jit_merge, "hashagg_merge")


def merge_states(states, aggs, out_cap: int):
    """merge_partials, one jitted dispatch. (A host-lexsort split was
    measured here in round 5 and LOST: the eager np.asarray sync per
    merge flushes the driver's async overlap, costing more than the
    in-jit sort saves — the split only pays at operator points that
    already sync, like the join build's finish().)"""
    return _merge_instr(tuple(states), aggs, out_cap)
#: buffered partials per merge round: each merge sorts FANIN x P rows,
#: so the per-input-row sort cost stays ~(1 + 1/FANIN + ...) ~ 1.15x
_MERGE_FANIN = 8

#: live-group count of a partial (consumed one round later, async)
_jit_count = _instr(
    _kernels.jit(lambda valid: jnp.sum(valid), "agg_count"),
    "agg_count")

#: Smallest state capacity the shrink protocol packs down to. Keeps the
#: compiled-shape set bounded (tiny partials all land on one bucket) and
#: leaves the default-small aggregations (max_groups 4096) untouched.
_SHRINK_FLOOR = 4096


@functools.partial(_kernels.jit, family="agg_shrink",
                   static_argnums=(1,))
def _shrink_state(st: "hashagg.GroupByState", cap: int):
    """Slice a PACKED sort-path state down to `cap` slots. Safe because
    _group_reduce lands live groups at the front (valid = slots < n);
    callers guarantee cap >= live via the observed count."""
    return hashagg.GroupByState(
        [(d[:cap], m[:cap]) for d, m in st.keys],
        [tuple(a[:cap] for a in t) for t in st.states],
        st.valid[:cap], st.overflow)


_shrink_state = _instr(_shrink_state, "agg_shrink")

#: Whole-step kernel cache keyed by the expression IRs + agg layout so a
#: re-executed (or structurally identical) query reuses the compiled XLA
#: program. Fusing key/input evaluation INTO the fold step matters:
#: evaluated eagerly, each key expression and agg input costs a
#: separate dispatch per batch — fused, one dispatch moves a whole
#: batch through
#: eval + group-by (the PageProcessor-into-accumulator analog of
#: sql/gen/AccumulatorCompiler).
import collections as _collections

_AGG_STEP_CACHE: "_collections.OrderedDict" = _collections.OrderedDict()
_AGG_STEP_CACHE_MAX = 256


def make_agg_step_kernel(key_exprs: Sequence[CompiledExpr],
                         specs: Sequence["AggSpec"], mode: str,
                         domains: Optional[Tuple[int, ...]],
                         input_dicts=None, presorted: bool = False,
                         pre=None, pre_key=None,
                         pre_compacted: bool = False):
    """Build (or fetch) the jitted (state, batch) -> state fold step.

    `input_dicts` is the (name, dictionary) token of the dict-encoded
    input columns the expressions were compiled against — compiled
    closures bake those dictionaries into lookup-table constants, so
    the same IR against different dictionaries is a DIFFERENT kernel
    (same rule as the filter/project cache).

    `pre` is an optional traceable batch -> batch body composed ahead
    of the expression eval INSIDE the same trace — the whole-fragment
    fusion path (operators/fused_fragment.py) passes the upstream
    filter/project chain here, so scan -> filter -> project -> agg
    step runs as ONE jitted program per batch. `pre_key` is its
    structural fingerprint; a pre without a key is uncacheable (the
    planner only fuses fingerprintable chains). Fused kernels report
    under the `fragment` telemetry family.

    `pre_compacted` marks a HISTORY-SIZED compacting body
    (fused_fragment.make_compacting_chain_body): `pre` then returns
    (batch, overflow flag) and the kernel returns (state, flag) — the
    operator accumulates the flag and the deferred-check protocol
    fails the run if any batch overflowed its measured bucket."""
    aggs = tuple(s.function for s in specs)
    exprs = list(key_exprs) + [s.input for s in specs
                               if s.input is not None] \
        + [s.mask for s in specs if s.mask is not None]
    key = None
    if all(e.ir is not None for e in exprs) \
            and (pre is None or pre_key is not None):
        try:
            # fingerprints, not raw IR: see operators/core.py — IR
            # hash/eq is exponential on lambda-produced DAGs
            from presto_tpu.expr.ir import fingerprint as _fp
            key = (mode, domains, input_dicts, presorted, pre_key,
                   pre_compacted,
                   tuple((_fp(ke.ir), ke.dictionary)
                         for ke in key_exprs),
                   tuple((s.out_name if mode == "final" else None,
                          _fp(s.input.ir) if s.input is not None
                          else None,
                          _fp(s.mask.ir) if s.mask is not None
                          else None,
                          s.function) for s in specs))
            cached = _AGG_STEP_CACHE.get(key)
            if cached is not None:
                _AGG_STEP_CACHE.move_to_end(key)
                return cached
        except TypeError:
            key = None

    def _chained(batch: Batch):
        """(the batch after the fused upstream chain, the chain's
        compaction-overflow flag or None)."""
        if pre is None:
            return batch, None
        if pre_compacted:
            return pre(batch)
        return pre(batch), None

    def _batch_parts(batch: Batch):
        env = {n: (c.data, c.mask) for n, c in batch.columns.items()}
        cap = batch.capacity
        key_cols = []
        for ke in key_exprs:
            d, m = ke.fn(env)
            key_cols.append((jnp.broadcast_to(d, (cap,)),
                             jnp.broadcast_to(m, (cap,))))
        agg_inputs, agg_weights, merge = [], [], []
        for s in specs:
            if mode == "final":
                parts = tuple(
                    batch.columns[f"{s.out_name}__s{i}"].data
                    for i in range(len(s.function.state_dtypes)))
                agg_inputs.append(parts)
                agg_weights.append(batch.row_valid)
                merge.append(True)
                continue
            if s.input is None:
                agg_inputs.append(None)
                w = batch.row_valid
            else:
                d, m = s.input.fn(env)
                agg_inputs.append(jnp.broadcast_to(d, (cap,)))
                w = batch.row_valid & jnp.broadcast_to(m, (cap,))
            if s.mask is not None:
                # FILTER (WHERE ...): NULL counts as excluded; groups
                # still form from row_valid — only contributions gate
                fd, fm = s.mask.fn(env)
                w = w & jnp.broadcast_to(fd & fm, (cap,))
            agg_weights.append(w)
            merge.append(False)
        # row_valid must come from the CHAINED batch: a fused upstream
        # filter narrows it inside this trace, and groups must not
        # form from rows the chain filtered out
        return (batch.row_valid, key_cols, agg_inputs, agg_weights,
                tuple(merge))

    # a kernel with a fused upstream chain is a whole-fragment program:
    # family `fragment`, device name `fragment_agg_step`; the unfused
    # presorted grouping (the streaming operator's step) keeps its
    # family and gets a device name of its own, `agg_step_presorted`
    if pre is not None:
        family, part = "fragment", "agg_step"
    else:
        family, part = "agg_step", "presorted" if presorted else None

    if domains is not None:
        @functools.partial(_kernels.jit, family=family, part=part)
        def kernel(state, batch: Batch):
            batch, ovf = _chained(batch)
            with jax.named_scope("agg_step"):
                row_valid, key_cols, agg_inputs, agg_weights, merge = \
                    _batch_parts(batch)
                out = hashagg.direct_step(
                    state, row_valid, key_cols, domains, agg_inputs,
                    agg_weights, aggs, merge)
            return (out, ovf) if pre_compacted else out
    else:
        # sort path: expression eval + per-batch compaction fused into
        # ONE dispatch; out_cap is static so one Python kernel serves
        # every max_groups retry size. presorted=True (the streaming
        # operator) swaps the variadic sort for boundary detection on
        # the already-key-ordered rows.
        group_fn = hashagg.presorted_aggregate if presorted \
            else hashagg.batch_aggregate

        @functools.partial(_kernels.jit, family=family, part=part,
                           static_argnums=(0,))
        def kernel(out_cap: int, batch: Batch):
            batch, ovf = _chained(batch)
            with jax.named_scope("agg_step"):
                row_valid, key_cols, agg_inputs, agg_weights, merge = \
                    _batch_parts(batch)
                out = group_fn(
                    row_valid, key_cols, agg_inputs, agg_weights,
                    aggs, out_cap, merge)
            return (out, ovf) if pre_compacted else out

    # compile-vs-execute attribution rides the cached kernel (same
    # contract as core's filter_project instrumentation)
    kernel = _kernels.instrument_kernel(kernel, family)

    if key is not None:
        _AGG_STEP_CACHE[key] = kernel
        while len(_AGG_STEP_CACHE) > _AGG_STEP_CACHE_MAX:
            _AGG_STEP_CACHE.popitem(last=False)
    return kernel


_AGG_FIN_CACHE: "_collections.OrderedDict" = _collections.OrderedDict()


def make_agg_finalize_kernel(mode: str, key_names, key_types, key_dicts,
                             domains, out_names, aggs):
    """Jitted state -> output-batch drain (one dispatch instead of an
    eager op per key/state column)."""
    key = (mode, tuple(key_names), tuple(key_types), tuple(key_dicts),
           domains, tuple(out_names), aggs)
    cached = _AGG_FIN_CACHE.get(key)
    if cached is not None:
        _AGG_FIN_CACHE.move_to_end(key)
        return cached

    @functools.partial(_kernels.jit, family="agg_finalize")
    def fin(state):
        if domains is not None:
            f = hashagg.direct_intermediate if mode == "partial" \
                else hashagg.direct_finalize
            return f(state, key_names, key_types, key_dicts, domains,
                     out_names, aggs)
        if mode == "partial":
            return hashagg.intermediate_batch(
                state, key_names, key_types, key_dicts, out_names, aggs)
        return hashagg.finalize(state, key_names, key_types, key_dicts,
                                out_names, aggs)

    fin = _kernels.instrument_kernel(fin, "agg_finalize")

    _AGG_FIN_CACHE[key] = fin
    while len(_AGG_FIN_CACHE) > _AGG_STEP_CACHE_MAX:
        _AGG_FIN_CACHE.popitem(last=False)
    return fin

#: Max slot-table size for the direct-indexing (sort-free) group-by path.
DIRECT_SLOTS_MAX = 1 << 16


def _direct_domains(key_exprs) -> Optional[Tuple[int, ...]]:
    """Per-key code domain when every key is dictionary-encoded or
    boolean (the small-domain fast path); None otherwise."""
    doms = []
    for ke in key_exprs:
        if ke.dictionary is not None:
            doms.append(len(ke.dictionary))
        elif ke.type.name == "boolean":
            doms.append(2)
        else:
            return None
    slots = 1
    for d in doms:
        slots *= d + 1
    return tuple(doms) if slots <= DIRECT_SLOTS_MAX else None


class AggregationOperator(Operator):
    def __init__(self, ctx: OperatorContext, key_names: Sequence[str],
                 key_exprs: Sequence[CompiledExpr],
                 specs: Sequence[AggSpec], mode: str,
                 max_groups: int, step_kernel=None,
                 chain_compacted: bool = False):
        super().__init__(ctx)
        self.key_names = list(key_names)
        self.key_exprs = list(key_exprs)
        self.specs = list(specs)
        self.mode = mode  # "single" | "partial" | "final"
        self.max_groups = max_groups
        self._domains = _direct_domains(key_exprs)
        self._kernel = step_kernel if step_kernel is not None else \
            make_agg_step_kernel(key_exprs, specs, mode, self._domains)
        #: history-sized compacting chain fused ahead of the fold: the
        #: kernel returns (state, overflow) and any overflow fails the
        #: run through the deferred-check protocol (sync-free — the
        #: flag accumulates on device, ONE host read after the drive)
        self._chain_compacted = chain_compacted
        self._chain_ovf = None
        if chain_compacted:
            ctx.driver_context.deferred_checks.append(
                self._chain_overflow_check)
        if self._domains is not None:
            slots = 1
            for d in self._domains:
                slots *= d + 1
            self._state = hashagg.direct_init(
                [s.function for s in self.specs], slots)
        else:
            # sort path: per-batch compacted partials sized by the
            # BATCH (distinct <= rows), then SHRUNK to their OBSERVED
            # live-group bucket one driver round later (async d2h count,
            # the join-output compaction protocol) and tree-merged at
            # capacities derived from live counts — never from stats
            # estimates or batch capacity. The reference sizes its
            # tables from observation the same way
            # (InMemoryHashAggregationBuilder grows from actual group
            # count, never pre-allocates the estimate).
            self._state = None
            self._cap = bucket_capacity(max_groups)
            #: cap -> [(state, live_upper_bound)]
            self._levels: Dict[int, list] = {}
            #: states awaiting their async live count: [(state, count)]
            self._pending: list = []
            self._host_spill: list = []  # [(host_state, live)]
            self.ctx.register_revocable(self._revoke)
        self._finishing = False
        self._emitted = False

    # -- operator protocol -------------------------------------------------

    def needs_input(self) -> bool:
        return not self._finishing

    def add_input(self, batch: Batch) -> None:
        from presto_tpu.batch import pad_for_kernel
        self._count_in(batch)
        # kernel shape bucketing: the step kernel keys its jit cache on
        # the batch CAPACITY — padding to the coarse ladder makes every
        # split/scale-factor variant of this query hit one trace
        batch = pad_for_kernel(batch)
        # ONE dispatch per batch: expression eval + grouping are fused,
        # and no per-batch overflow sync — the flag accumulates on
        # device and is checked ONCE at get_output. A blocking
        # device->host read per batch costs a full roundtrip and
        # serializes the pipeline.
        if self._domains is not None:
            if self._chain_compacted:
                self._state, ovf = self._kernel(self._state, batch)
                self._note_chain_ovf(ovf)
            else:
                self._state = self._kernel(self._state, batch)
            return
        c0 = min(self._cap, bucket_capacity(batch.capacity))
        if self._chain_compacted:
            st, ovf = self._kernel(c0, batch)
            self._note_chain_ovf(ovf)
        else:
            st = self._kernel(c0, batch)
        self._enqueue(st)
        self._drain_pending(keep=1)

    def _note_chain_ovf(self, ovf) -> None:
        """OR one batch's overflow flag into the accumulator — an
        async device op, never a host sync."""
        self._chain_ovf = ovf if self._chain_ovf is None \
            else self._chain_ovf | ovf

    def _chain_overflow_check(self):
        from presto_tpu.operators.fused_fragment import (
            FusedChainCompactOverflow,
        )

        def make_exc():
            return FusedChainCompactOverflow(
                f"{self.ctx.name}: a batch's surviving rows exceeded "
                "the history-sized compaction bucket (data shifted "
                "since the measurement) — retrying without "
                "history-driven fusion")
        return self._chain_ovf, make_exc

    # -- sort-path partial management ---------------------------------
    #
    # Every state (per-batch partial or merge output) passes through a
    # one-slot pending queue: its live-group count's d2h copy starts at
    # dispatch and is consumed ONE DRIVER ROUND LATER, by which time the
    # transfer has overlapped real work — the hot loop never blocks on a
    # fresh roundtrip. The resolved count drives (a) shrinking the state
    # to its live bucket and (b) sizing every downstream merge, so a
    # 56-row aggregation never sorts a stats-estimated half-million-slot
    # shape (the round-3 Q18 failure mode).

    @staticmethod
    def _state_bytes(st) -> int:
        return sum(x.dtype.itemsize * x.size
                   for x in jax.tree_util.tree_leaves(st))

    @staticmethod
    def _state_cap(st) -> int:
        return st.valid.shape[0]

    def _live_cap(self, lives: int) -> int:
        """Capacity for a merge of states with `lives` total live
        groups: distinct(union) <= sum of live counts, so this can only
        flag overflow when max_groups truly overflows. Under kernel
        shape bucketing the target sits on the coarse ladder so merge
        and finalize shapes stay within a handful of specializations."""
        from presto_tpu.batch import operator_capacity
        return min(self._cap,
                   operator_capacity(lives, floor=_SHRINK_FLOOR))

    def _enqueue(self, st) -> None:
        from presto_tpu.batch import start_async_copy
        cnt = start_async_copy(_jit_count(st.valid))
        if self.ctx.driver_context.memory is not None:
            self.ctx.driver_context.memory.reserve(
                self.ctx.tag, self._state_bytes(st))
        self._pending.append((st, cnt))

    def _drain_pending(self, keep: int) -> None:
        pool = self.ctx.driver_context.memory
        while len(self._pending) > keep:
            if keep and len(self._pending) <= keep + 2:
                # a merge output's count may have been dispatched only
                # this round — give it a FIXED backlog of overlap time
                # (bounded at keep+2). This used to probe
                # cnt.is_ready(), but which states merge together must
                # not depend on transfer timing: merge grouping
                # changes float-sum rounding, so any unrelated device
                # work (telemetry row counters, a concurrent query)
                # would perturb low-order result bits — the history
                # on/off byte-identity oracle caught exactly that.
                break
            st, cnt = self._pending.pop(0)
            from presto_tpu.native.pages import to_host
            live = int(to_host(cnt))
            cap = self._state_cap(st)
            tgt = min(cap, self._live_cap(live))
            if tgt < cap:
                shrunk = _shrink_state(st, tgt)
                if pool is not None:
                    pool.free(self.ctx.tag, self._state_bytes(st))
                    pool.reserve(self.ctx.tag,
                                 self._state_bytes(shrunk))
                st = shrunk
            self._push(st, live)

    def _push(self, st, live: int) -> None:
        """Buffer a counted partial, keyed by CAPACITY: merges then
        always see FANIN equal-shaped states, so the jit specialization
        count is bounded by the handful of power-of-two caps — not by
        the combinatorics of mixed-cap tuples. Merge outputs re-enter
        the pending queue (append only — the _drain_pending loop picks
        them up next iteration; no recursion)."""
        cap = self._state_cap(st)
        buf = self._levels.setdefault(cap, [])
        buf.append((st, live))
        if len(buf) >= _MERGE_FANIN:
            aggs = tuple(s.function for s in self.specs)
            states = tuple(s for s, _ in buf)
            lives = sum(l for _, l in buf)
            merged = merge_states(states, aggs, self._live_cap(lives))
            if self.ctx.driver_context.memory is not None:
                self.ctx.driver_context.memory.free(
                    self.ctx.tag,
                    sum(self._state_bytes(s) for s in states))
            self._levels[cap] = []
            self._enqueue(merged)

    def _merge_mixed(self, entries):
        """Merge leftover (state, live) pairs of assorted caps with a
        bounded set of kernel shapes: same-cap groups first (padded to
        FANIN with empty states so each cap has ONE specialization),
        then a pairwise ladder across ascending caps — every output
        sized from live counts."""
        aggs = tuple(s.function for s in self.specs)
        key_types = [k.type for k in self.key_exprs]
        by_cap: Dict[int, list] = {}
        for s, l in entries:
            by_cap.setdefault(self._state_cap(s), []).append((s, l))
        level: list = []
        for cap in sorted(by_cap):
            group = by_cap[cap]
            if len(group) == 1:
                level.append(group[0])
                continue
            lives = sum(l for _, l in group)
            while len(group) < _MERGE_FANIN:
                group.append(
                    (hashagg.init_state(key_types, aggs, cap), 0))
            merged = merge_states(tuple(s for s, _ in group), aggs,
                                  self._live_cap(lives))
            level.append((merged, lives))
        level.sort(key=lambda e: self._state_cap(e[0]))
        while len(level) > 1:
            (sa, la), (sb, lb) = level.pop(0), level.pop(0)
            m = merge_states((sa, sb), aggs, self._live_cap(la + lb))
            level.append((m, la + lb))
            level.sort(key=lambda e: self._state_cap(e[0]))
        return level[0][0]

    def _revoke(self) -> int:
        """Pool callback: park every buffered partial in host RAM.
        Pending (uncounted) states get their live count from the host
        copy itself — the revoke path is allowed to sync."""
        entries = [e for buf in self._levels.values() for e in buf]
        for st, cnt in self._pending:
            entries.append((st, None))
        self._pending = []
        if not entries:
            return 0
        freed = sum(self._state_bytes(s) for s, _ in entries)
        for s, live in entries:
            host = jax.device_get(s)
            if live is None:
                live = int(np.sum(np.asarray(host.valid)))
            self._host_spill.append((host, live))
            self.ctx.count_spill(1, self._state_bytes(host))
        self._levels = {}
        pool = self.ctx.driver_context.memory
        if pool is not None:
            pool.free_all(self.ctx.tag)
        return freed

    def _final_state(self):
        aggs = tuple(s.function for s in self.specs)
        key_types = [k.type for k in self.key_exprs]
        self._drain_pending(keep=0)
        entries = [e for buf in self._levels.values() for e in buf]
        self._levels = {}
        if self._host_spill:
            # spilled run: restore + merge host-resident partials one
            # same-cap FANIN group at a time, keeping only one merge
            # group on device at once
            for s, l in entries:
                self._host_spill.append((jax.device_get(s), l))
            work = sorted(self._host_spill,
                          key=lambda e: self._state_cap(e[0]))
            self._host_spill = []
            while len(work) > _MERGE_FANIN:
                group = work[:_MERGE_FANIN]
                lives = sum(l for _, l in group)
                merged = merge_states(
                    tuple(jax.device_put(s) for s, _ in group), aggs,
                    self._live_cap(lives))
                work = work[_MERGE_FANIN:]
                work.append((jax.device_get(merged), lives))
                work.sort(key=lambda e: self._state_cap(e[0]))
            if not work:
                return hashagg.init_state(key_types, aggs, self._cap)
            return self._merge_mixed(
                [(jax.device_put(s), l) for s, l in work])
        if not entries:
            return hashagg.init_state(key_types, aggs, self._cap)
        if len(entries) > 1:
            return self._merge_mixed(entries)
        return entries[0][0]

    def get_output(self) -> Optional[Batch]:
        if not self._finishing or self._emitted:
            return None
        live = None
        if self._domains is None:
            self._state = self._final_state()
            self.ctx.unregister_revocable()
            # ONE host fetch serves both the overflow check and the
            # live-group count (the count drives output compaction —
            # a stats-overshot state capacity must not ride downstream
            # as a huge mostly-dead batch). The fetch blocks on every
            # async-dispatched agg kernel the state depends on — split
            # the device's catch-up (device_wait) from the copy (d2h),
            # same discipline as pages.to_host.
            from presto_tpu.telemetry import ledger as _ledger
            pair = (self._state.overflow, jnp.sum(self._state.valid))
            with _ledger.span("device_wait"):
                jax.block_until_ready(pair)
            with _ledger.span("d2h"):
                overflow, live = jax.device_get(pair)
            if bool(overflow):
                # groups were dropped — the query must re-run with a
                # larger table (reference analog: MultiChannelGroupByHash
                # rehash :87, except the retry is at query level to keep
                # the hot loop sync-free)
                raise GroupLimitExceeded(self.max_groups * 4)
        self._emitted = True
        key_types = tuple(k.type for k in self.key_exprs)
        key_dicts = tuple(k.dictionary for k in self.key_exprs)
        aggs = tuple(s.function for s in self.specs)
        names = tuple(s.out_name for s in self.specs)
        fin = make_agg_finalize_kernel(
            self.mode, tuple(self.key_names), key_types, key_dicts,
            self._domains, names, aggs)
        out = fin(self._state)
        if live is not None:
            from presto_tpu.batch import quantized_capacity
            cap = quantized_capacity(int(live))
            if cap < out.capacity:
                # groups are already packed at the front of the state
                out = out.compact(cap, known_valid=int(live))
        # (global aggregation over zero rows already yields one live row:
        #  the kernel's global path pins group 0, so count(*) = 0 works)
        return self._count_out(out)

    def finish(self) -> None:
        self._finishing = True

    def is_finished(self) -> bool:
        return self._finishing and self._emitted

    def close(self) -> None:
        # drop device references so retired lifespan instances release
        # their HBM
        self._state = None
        if self._domains is None:
            self.ctx.unregister_revocable()
            self.ctx.release_all()
            self._levels = {}
            self._pending = []
            self._host_spill = []


@functools.partial(_kernels.jit, family="agg_stream",
                   static_argnums=(2,))
def _stream_step_jit(carry: "hashagg.GroupByState",
                     partial: "hashagg.GroupByState", aggs):
    """One streaming-aggregation round, all arithmetic — NO re-grouping
    sort (the round-4 formulation merged carry+partial through the full
    sort-based merge_partials: a second 1M-row variadic sort per batch).

    The stream is globally key-sorted, so the carried boundary group
    can only interact with the batch's FIRST packed group:
      - same key  -> fold carry's states into slot 0 (masked .at[0] op)
      - different -> the carry is COMPLETE: emit it as its own
                     single-group state ahead of the batch's groups
    Then every group but the last is complete (emit), and the last is
    sliced out as the new carry. An empty batch (no groups) passes the
    carry through untouched.

    Returns (carry_emit[1], emit[cap], carry_out[1], emit_live)."""
    ng = jnp.sum(partial.valid)
    has_groups = ng > 0
    has_carry = carry.valid[0]
    same = has_carry & has_groups
    for (cd, cm), (pd, pm) in zip(carry.keys, partial.keys):
        eq = jnp.where(cm[0] & pm[0], cd[0] == pd[0],
                       ~cm[0] & ~pm[0])
        same = same & eq

    # fold carry into slot 0, gated: the contribution is the reduce
    # identity unless `same` (so no branch, no shift of the big arrays)
    new_states = []
    for cst, pst, agg in zip(carry.states, partial.states, aggs):
        comps = []
        for ca, pa, r, comp in zip(cst, pst, agg.reduces,
                                   agg.state_dtypes):
            c0 = jnp.where(same, ca[0],
                           hashagg._ident_for(r, comp)).astype(pa.dtype)
            if r == "sum":
                comps.append(pa.at[0].add(c0))
            elif r == "min":
                comps.append(pa.at[0].min(c0))
            else:
                comps.append(pa.at[0].max(c0))
        new_states.append(tuple(comps))

    carry_emit = hashagg.GroupByState(
        carry.keys, carry.states,
        carry.valid & (has_carry & has_groups & ~same),
        jnp.asarray(False))

    last = jnp.maximum(ng - 1, 0)
    cap = partial.valid.shape[0]
    emit_valid = partial.valid & (jnp.arange(cap) < last)
    emit = hashagg.GroupByState(partial.keys, new_states, emit_valid,
                                partial.overflow | carry.overflow)

    def slice1(a):
        return jax.lax.dynamic_slice_in_dim(a, last, 1, axis=0)

    def keep1(new, old):
        return jnp.where(has_groups, slice1(new), old)
    carry_out = hashagg.GroupByState(
        [(keep1(d, od), keep1(m, om))
         for (d, m), (od, om) in zip(partial.keys, carry.keys)],
        [tuple(keep1(a, oa) for a, oa in zip(st, ost))
         for st, ost in zip(new_states, carry.states)],
        keep1(partial.valid, carry.valid), jnp.asarray(False))
    return carry_emit, emit, carry_out, last


#: streaming boundary-fold, attributed like the other agg kernels
_stream_step = _instr(_stream_step_jit, "agg_stream")


class StreamingAggregationOperator(Operator):
    """Aggregation over an input ALREADY SORTED by the group keys
    (ascending, nulls last — the canonical packing order of the
    grouping kernel), emitting each group as soon as its key range is
    passed (reference: operator/StreamingAggregationOperator.java).

    Memory is O(batch), independent of total group count: no
    max_groups table, no overflow retry — the property the reference
    operator exists for. Output batches hold groups in key order, so
    an ORDER BY on the group keys above this operator is a no-op."""

    row_series = ("presto_tpu_agg_stream_rows_total",
                  "presto_tpu_agg_stream_groups_total")

    def __init__(self, ctx: OperatorContext, key_names: Sequence[str],
                 key_exprs: Sequence[CompiledExpr],
                 specs: Sequence[AggSpec], step_kernel=None,
                 mode: str = "single"):
        super().__init__(ctx)
        self.key_names = list(key_names)
        self.key_exprs = list(key_exprs)
        self.specs = list(specs)
        self.mode = mode  # "single" | "partial" (final merges shuffled
        # states, whose arrival order is not key-sorted)
        self._kernel = step_kernel if step_kernel is not None else \
            make_agg_step_kernel(key_exprs, specs, mode, None,
                                 presorted=True)
        self._carry = None
        self._pending: list = []  # [(emit_state, live_count_async)]
        self._finishing = False
        self._emitted_tail = False

    def needs_input(self) -> bool:
        return not self._finishing and len(self._pending) < 4

    def _finalize_kernel(self):
        key_types = tuple(k.type for k in self.key_exprs)
        key_dicts = tuple(k.dictionary for k in self.key_exprs)
        aggs = tuple(s.function for s in self.specs)
        names = tuple(s.out_name for s in self.specs)
        return make_agg_finalize_kernel(
            self.mode, tuple(self.key_names), key_types, key_dicts,
            None, names, aggs)

    def add_input(self, batch: Batch) -> None:
        from presto_tpu.batch import pad_for_kernel, start_async_copy
        self._count_in(batch)
        batch = pad_for_kernel(batch)
        aggs = tuple(s.function for s in self.specs)
        c0 = bucket_capacity(batch.capacity)
        partial = self._kernel(c0, batch)
        if self._carry is None:
            key_types = [k.type for k in self.key_exprs]
            self._carry = hashagg.init_state(key_types, aggs, 1)
        # a completed carry group (key change at the batch boundary)
        # precedes this batch's groups in key order, so it goes out as
        # its own 1-row batch ahead of the main emission
        carry_emit, emit, self._carry, live = _stream_step(
            self._carry, partial, aggs)
        self._pending.append((carry_emit, None))
        self._pending.append((emit, start_async_copy(live)))

    def get_output(self) -> Optional[Batch]:
        from presto_tpu.batch import end_deferred_compact
        if self._pending and (len(self._pending) > 1
                              or self._finishing):
            emit, live = self._pending.pop(0)
            out = self._finalize_kernel()(emit)
            return self._count_out(end_deferred_compact(out, live))
        if self._pending or not self._finishing or self._emitted_tail:
            return None
        self._emitted_tail = True
        if self._carry is None:
            return None  # zero input batches: grouped agg of nothing
        return self._count_out(self._finalize_kernel()(self._carry))

    def finish(self) -> None:
        self._finishing = True

    def is_finished(self) -> bool:
        return self._finishing and not self._pending \
            and self._emitted_tail

    def close(self) -> None:
        self._carry = None
        self._pending = []


class StreamingAggregationOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, key_names: Sequence[str],
                 key_exprs: Sequence[CompiledExpr],
                 specs: Sequence[AggSpec], input_dicts=None,
                 mode: str = "single"):
        super().__init__(operator_id,
                         "aggregation(streaming)" if mode == "single"
                         else f"aggregation(streaming-{mode})")
        self.key_names = key_names
        self.key_exprs = key_exprs
        self.specs = specs
        self.mode = mode
        self._input_dicts = input_dicts
        self._created = False
        self._step_kernel = make_agg_step_kernel(
            key_exprs, specs, mode, None, input_dicts, presorted=True)

    def fuse_pre(self, pre, pre_key, name: str) -> None:
        """Whole-fragment fusion: rebuild the step kernel with the
        upstream filter/project chain traced ahead of the key eval
        (planner/fusion.py; only legal before the first create)."""
        assert not self._created, "fuse_pre() after create()"
        self._step_kernel = make_agg_step_kernel(
            self.key_exprs, self.specs, self.mode, None,
            self._input_dicts, presorted=True, pre=pre,
            pre_key=pre_key)
        self.name = name

    def create(self, driver_context: DriverContext) -> Operator:
        self._created = True
        return StreamingAggregationOperator(
            OperatorContext(self.operator_id, self.name, driver_context),
            self.key_names, self.key_exprs, self.specs,
            self._step_kernel, mode=self.mode)


class AggregationOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, key_names: Sequence[str],
                 key_exprs: Sequence[CompiledExpr],
                 specs: Sequence[AggSpec], mode: str = "single",
                 max_groups: int = 4096, input_dicts=None):
        super().__init__(operator_id, f"aggregation({mode})")
        self.key_names = key_names
        self.key_exprs = key_exprs
        self.specs = specs
        self.mode = mode
        self.max_groups = max_groups
        self._input_dicts = input_dicts
        self._created = False
        self._step_kernel = make_agg_step_kernel(
            key_exprs, specs, mode, _direct_domains(key_exprs),
            input_dicts)

    def fuse_pre(self, pre, pre_key, name: str,
                 compacted: bool = False) -> None:
        """Whole-fragment fusion: rebuild the step kernel with the
        upstream filter/project chain traced ahead of the key eval
        (planner/fusion.py; only legal before the first create).
        `compacted` marks a history-sized compacting body — `pre`
        returns (batch, overflow) and the operator runs the deferred
        overflow check (docs/ADAPTIVE.md)."""
        assert not self._created, "fuse_pre() after create()"
        self._step_kernel = make_agg_step_kernel(
            self.key_exprs, self.specs, self.mode,
            _direct_domains(self.key_exprs), self._input_dicts,
            pre=pre, pre_key=pre_key, pre_compacted=compacted)
        self._chain_compacted = compacted
        self.name = name

    def create(self, driver_context: DriverContext) -> Operator:
        self._created = True
        return AggregationOperator(
            OperatorContext(self.operator_id, self.name, driver_context),
            self.key_names, self.key_exprs, self.specs, self.mode,
            self.max_groups, self._step_kernel,
            chain_compacted=getattr(self, "_chain_compacted", False))


# -- kernel contracts (tools/kernelcheck.py) ---------------------------
#
# agg_step kernels are built per plan from compiled key/input
# expressions; the contracts trace the shared hashagg cores the built
# kernels dispatch to (batch_aggregate / presorted_aggregate /
# merge_partials / finalize) with representative agg layouts over the
# dtype lattice. Dead rows must contribute reduce identities — the
# taint walk proves init/_gate neutralize every contribution before
# the segment reductions.
from presto_tpu.analysis.contracts import (
    KernelContract, TracePoint, register_contract, sds,
)


def _contract_aggs():
    from presto_tpu.types import DOUBLE, REAL
    return (hashagg.make_count(None), hashagg.make_sum(DOUBLE, DOUBLE),
            hashagg.make_min(REAL))


def _agg_inputs(cap):
    import numpy as np
    rv = sds((cap,), np.bool_)
    kd, km = sds((cap,), np.int64), sds((cap,), np.bool_)
    sd = sds((cap,), np.float64)
    md = sds((cap,), np.float32)
    return (rv, kd, km, sd, rv, md, rv), \
        ("mask", "data", "mask", "data", "mask", "data", "mask")


def _agg_step_point(cap, variant):
    aggs = _contract_aggs()
    presorted = variant.get("presorted", False)
    group = hashagg.presorted_aggregate if presorted \
        else hashagg.batch_aggregate

    def fn(rv, kd, km, sd, sw, md, mw):
        return group(rv, [(kd, km)], [None, sd, md], [rv, sw, mw],
                     aggs, 4096)
    args, roles = _agg_inputs(cap)
    return TracePoint(fn, args, roles)


def _agg_finalize_point(cap, variant):
    from presto_tpu.types import BIGINT
    import jax as _jax
    aggs = _contract_aggs()
    st = hashagg.init_state([BIGINT], aggs, min(cap, 65536))
    rst = _jax.tree_util.tree_map(lambda _: "clean", st)
    return TracePoint(
        lambda s: hashagg.finalize(s, ["k"], [BIGINT], [None],
                                   ["c", "s", "m"], aggs),
        (st,), (rst,))


def _agg_merge_point(cap, variant):
    from presto_tpu.types import BIGINT
    import jax as _jax
    aggs = _contract_aggs()
    st = hashagg.init_state([BIGINT], aggs, min(cap, 65536))
    rst = _jax.tree_util.tree_map(lambda _: "clean", st)
    return TracePoint(
        lambda a, b: hashagg.merge_partials((a, b), aggs,
                                            min(cap, 65536)),
        (st, st), (rst, rst))


def _agg_count_point(cap, variant):
    import numpy as np
    return TracePoint(lambda v: jnp.sum(v),
                      (sds((cap,), np.bool_),), ("mask",))


def _agg_shrink_point(cap, variant):
    from presto_tpu.types import BIGINT
    import jax as _jax
    aggs = _contract_aggs()
    st = hashagg.init_state([BIGINT], aggs, cap)
    rst = _jax.tree_util.tree_map(lambda _: "clean", st)
    return TracePoint(
        lambda s: _shrink_state.__wrapped__(s, _SHRINK_FLOOR),
        (st,), (rst,))


register_contract(KernelContract(
    family="agg_step", module=__name__, build=_agg_step_point,
    notes="sort-path grouped fold (batch_aggregate core)"))
register_contract(KernelContract(
    family="agg_step", module=__name__,
    build=lambda cap, v: _agg_step_point(cap, {"presorted": True}),
    notes="streaming (presorted) grouping core"))
register_contract(KernelContract(
    family="agg_finalize", module=__name__, build=_agg_finalize_point))
register_contract(KernelContract(
    family="hashagg_merge", module=__name__, build=_agg_merge_point))
register_contract(KernelContract(
    family="agg_count", module=__name__, build=_agg_count_point))
# the shrink's source capacity must sit ABOVE its 4096-slot floor on
# every sampled point — at cap == floor the slices vanish from the
# trace, which is a different (and never co-resident) program
register_contract(KernelContract(
    family="agg_shrink", module=__name__, build=_agg_shrink_point,
    buckets=(16384, 65536, 262144)))


def _agg_stream_point(cap, variant):
    from presto_tpu.types import BIGINT
    import jax as _jax
    aggs = _contract_aggs()
    carry = hashagg.init_state([BIGINT], aggs, 1)
    partial = hashagg.init_state([BIGINT], aggs, cap)
    rc = _jax.tree_util.tree_map(lambda _: "clean", carry)
    rp = _jax.tree_util.tree_map(lambda _: "clean", partial)
    return TracePoint(
        lambda c, p: _stream_step_jit(c, p, aggs),
        (carry, partial), (rc, rp))


register_contract(KernelContract(
    family="agg_stream", module=__name__, build=_agg_stream_point,
    notes="streaming boundary fold: carry[1] x partial[cap]"))
