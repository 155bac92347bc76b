"""Whole-fragment kernel composition (the fragment compiler).

The reference engine JIT-compiles whole filter/project/probe chains
into one method per pipeline (presto-bytecode + sql/gen
PageFunctionCompiler / AccumulatorCompiler) instead of interpreting
operator-by-operator. The XLA analog: take a maximal deterministic
leaf-fragment chain — scan -> filter -> project -> [join probe] ->
agg step / topn / limit / distinct — and trace the ENTIRE chain into
ONE jitted program, so the Driver loop degenerates to

    scan batch -> fused_kernel(batch) -> emit / fold

Per batch this removes: one jit dispatch per FilterProject stage, the
intermediate materialization of each stage's output, and — the big
host-glue item — the deferred count/compact round between a selective
filter and its consumer (an async d2h count + a blocking host read +
a compaction dispatch per batch, see batch.begin_deferred_compact).
The terminal fold's own machinery (agg overflow retries, partial
merging, topn state, LIMIT early-exit) is untouched: fusion composes
the chain INTO the terminal's existing kernel body, it does not
reimplement the operator protocol.

Composed kernels are instrumented as the `fragment` kernel family
(telemetry/kernels.py), so EXPLAIN ANALYZE and /v1/metrics attribute
their compile-vs-execute split separately from the unfused families.
They ride the kernel shape-bucket ladder (operators still
pad_for_kernel at entry) and the persistent XLA compilation cache
exactly like unfused kernels — one fused trace per capacity bucket.

Correctness bar: byte-identity with fusion off. The chain preserves
row positions (filters only narrow row_valid, exactly like the
unfused FilterProject), dead lanes contribute reduce identities, and
every downstream sort/group kernel orders rows stably — so skipping
the intermediate compaction changes shapes, never values or order.
Eligibility is decided by planner/fusion.py, which records an explicit
fallback reason for every chain it declines (docs/
FRAGMENT_COMPILATION.md)."""

from __future__ import annotations

import collections
import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from presto_tpu.batch import Batch, Column, pad_for_kernel
from presto_tpu.operators.base import (
    DriverContext, Operator, OperatorContext, OperatorFactory,
)
from presto_tpu.operators.core import (
    FilterProjectOperator, LimitOperator,
)
from presto_tpu.operators.sort_ops import (
    DistinctOperator, TopNOperator,
)
from presto_tpu.ops import sort as sort_kernels
from presto_tpu.telemetry import kernels as _kernels


# -- chain stages ------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ChainStage:
    """One FilterProject link of a fused chain: the same (filter,
    projection forest, input-dictionary token) triple the standalone
    operator compiles — kept as expressions so the whole run re-traces
    inside the terminal's kernel."""
    filter_expr: object  # Optional[CompiledExpr]
    projections: Tuple[Tuple[str, object], ...]
    input_dicts: object


def stages_from_factory(f) -> Optional[Tuple[ChainStage, ...]]:
    """ChainStage of a FilterProjectOperatorFactory, or None when the
    factory predates the expression plumbing (built directly)."""
    filter_expr = getattr(f, "filter_expr", "missing")
    projections = getattr(f, "projections", None)
    if filter_expr == "missing" or projections is None:
        return None
    return (ChainStage(filter_expr, tuple(projections),
                       getattr(f, "input_dicts", None)),)


def chain_fingerprint(stages: Sequence[ChainStage]):
    """Hashable structural fingerprint of a chain (the kernel-cache
    key component), or None when any expression lacks a cacheable IR —
    an ir=None CompiledExpr is indistinguishable from another, and a
    collision would silently fuse the wrong program (same rule as
    operators/core._FP_KERNEL_CACHE)."""
    from presto_tpu.expr.ir import fingerprint
    out = []
    for st in stages:
        exprs = ([st.filter_expr] if st.filter_expr is not None
                 else []) + [ce for _, ce in st.projections]
        if any(ce.ir is None for ce in exprs):
            return None
        try:
            out.append((
                fingerprint(st.filter_expr.ir)
                if st.filter_expr is not None else None,
                tuple((n, fingerprint(ce.ir), ce.dictionary)
                      for n, ce in st.projections),
                st.input_dicts))
        except TypeError:
            return None
    key = tuple(out)
    try:
        hash(key)
    except TypeError:
        return None
    return key


def chain_selective(stages: Sequence[ChainStage]) -> bool:
    return any(st.filter_expr is not None for st in stages)


class FusedChainCompactOverflow(Exception):
    """A history-sized in-trace compaction saw more surviving rows
    than its measured bucket (the data shifted since the measurement):
    the compacted batch DROPPED rows, so the whole execution's output
    is untrusted. Raised by the deferred-check protocol after the
    drive completes; the runner retries the query once with
    history-driven fusion off (the gated PARTIAL path, which is
    always correct)."""


#: headroom multiplier over the measured selectivity when sizing the
#: in-trace compaction bucket: the smallest power-of-four fraction
#: >= measured * HEADROOM, so a batch up to HEADROOM x more selective
#: than history still fits (worse skew trips the overflow retry)
COMPACT_HEADROOM = 2.0


def compact_ratio(sel: float) -> Optional[float]:
    """Power-of-four fraction of input capacity a measured-selective
    chain compacts to inside the fused trace, or None when the
    measurement leaves no whole bucket of certain headroom (compacting
    would buy nothing — the plain gate decides then)."""
    if sel is None or sel <= 0:
        return None
    target = min(1.0, sel * COMPACT_HEADROOM)
    r = 1.0
    while r / 4 >= target:
        r /= 4
    return r if r < 1.0 else None


def make_compacting_chain_body(stages: Sequence[ChainStage],
                               ratio: float):
    """The history-driven full-fusion body: chain -> in-trace
    compaction to `ratio` x input capacity -> (batch, overflow flag).

    This is what the measured selectivity BUYS: the PARTIAL path pays
    a host count round-trip + a separate compaction dispatch per batch
    because it cannot know the surviving-row bucket until runtime;
    with a measured fraction the bucket is known at plan time, so the
    compact folds into the SAME program as the chain and the terminal
    fold — and the fold works over the compacted width, which is why
    the selectivity gate exists at all. Overflow (live > bucket) drops
    rows INSIDE the trace, so the flag rides out and the deferred
    check fails the run before results are trusted."""
    chain = make_chain_body(stages)

    def body(batch: Batch):
        out = chain(batch)
        cap = out.capacity  # static at trace time
        from presto_tpu.batch import COMPACT_MIN, operator_capacity
        from presto_tpu.ops.common import first_true_indices
        comp_cap = operator_capacity(int(cap * ratio),
                                     floor=COMPACT_MIN)
        live = jnp.sum(out.row_valid)
        if comp_cap >= cap:
            return out, jnp.asarray(False)
        # bounded nonzero + gather, the _compact_shrink_jit shape —
        # inlined here so it traces into the surrounding program
        with jax.named_scope("compact"):
            idx = first_true_indices(out.row_valid, comp_cap, cap - 1)
            rv = jnp.arange(comp_cap) < live
            cols = {n: Column(c.data[idx], c.mask[idx] & rv, c.type,
                              c.dictionary)
                    for n, c in out.columns.items()}
        return Batch(cols, rv), live > comp_cap
    return body


def make_chain_body(stages: Sequence[ChainStage]):
    """The traceable chain: batch -> batch, applying each stage's
    filter (narrowing row_valid) and projection forest in sequence —
    semantically identical to running the standalone FilterProject
    kernels back to back, minus the per-stage materialization. Each
    stage's ops carry a `jax.named_scope` of its kind (`filter`,
    `project`; the terminals add theirs), which is what splits one
    fused module's device time by operator in xprof."""
    stages = tuple(stages)

    def body(batch: Batch) -> Batch:
        for st in stages:
            env = {n: (c.data, c.mask)
                   for n, c in batch.columns.items()}
            cap = batch.capacity
            rv = batch.row_valid
            if st.filter_expr is not None:
                with jax.named_scope("filter"):
                    d, m = st.filter_expr.fn(env)
                    rv = rv & jnp.broadcast_to(d & m, (cap,))
            cols = {}
            with jax.named_scope("project"):
                for name, ce in st.projections:
                    d, m = ce.fn(env)
                    d = jnp.broadcast_to(
                        jnp.asarray(d, ce.type.np_dtype), (cap,))
                    cols[name] = Column(
                        d, jnp.broadcast_to(m, (cap,)), ce.type,
                        ce.dictionary)
            batch = Batch(cols, rv)
        return batch
    return body


# -- fused-kernel LRU --------------------------------------------------
#
# Same contract as the filter/project and probe kernel LRUs: the
# instrumented wrapper (and with it the warm jit cache) travels with
# the cache hit, so a re-planned query re-uses the compiled fragment
# program and reports execute-only.

_FUSED_KERNEL_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_FUSED_KERNEL_CACHE_MAX = 256


def _cached_fragment_kernel(key, builder):
    if key is not None:
        cached = _FUSED_KERNEL_CACHE.get(key)
        if cached is not None:
            _FUSED_KERNEL_CACHE.move_to_end(key)
            return cached
    kernel = _kernels.instrument_kernel(builder(), "fragment")
    if key is not None:
        _FUSED_KERNEL_CACHE[key] = kernel
        while len(_FUSED_KERNEL_CACHE) > _FUSED_KERNEL_CACHE_MAX:
            _FUSED_KERNEL_CACHE.popitem(last=False)
    return kernel


def clear_fused_kernel_cache() -> None:
    """Restart simulation hook (execution/compile_cache)."""
    _FUSED_KERNEL_CACHE.clear()


# -- terminal-less chain: N FilterProjects -> one program --------------

class FusedChainOperatorFactory(OperatorFactory):
    """A run of >= 2 adjacent FilterProjects with no fusable terminal
    collapses into ONE FilterProjectOperator driving the composed
    chain kernel (the deferred-compact protocol runs once, at the
    chain's tail, instead of once per stage)."""

    def __init__(self, operator_id: int, name: str,
                 stages: Sequence[ChainStage], chain_key):
        super().__init__(operator_id, name)
        # retained for the exchange-sink rewrite (planner/fusion
        # fuse_exchange_sinks absorbs the chain into a repartition
        # exchange's shard_map wave program)
        self.stages = tuple(stages)
        self.chain_key = chain_key
        body = make_chain_body(stages)
        self._kernel = _cached_fragment_kernel(
            ("chain", chain_key) if chain_key is not None else None,
            lambda: _kernels.jit(body, "fragment", "chain"))
        self._selective = chain_selective(stages)

    def create(self, driver_context: DriverContext) -> Operator:
        return FilterProjectOperator(
            OperatorContext(self.operator_id, self.name,
                            driver_context),
            self._kernel, self._selective)


# -- chain -> LIMIT ----------------------------------------------------

class FusedLimitOperator(LimitOperator):
    """chain + LIMIT in one dispatch: only the fold step differs —
    the inherited async early-termination protocol (the limit-reached
    flag is fetched without blocking, so a fused fragment still stops
    pulling scan batches within a couple of driver rounds) is core.
    LimitOperator's, verbatim. The kernel additionally folds the
    emitted-count update into the same program, removing the separate
    jnp.sum dispatch per batch."""

    def __init__(self, ctx: OperatorContext, kernel, n: int):
        super().__init__(ctx, n)
        self._kernel = kernel

    def _step(self, batch: Batch):
        return self._kernel(pad_for_kernel(batch), self._n,
                            self._emitted)


class FusedLimitOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, name: str,
                 stages: Sequence[ChainStage], chain_key, n: int):
        super().__init__(operator_id, name)
        self.n = n
        body = make_chain_body(stages)

        def builder():
            def fn(batch: Batch, n, emitted):
                batch = body(batch)
                with jax.named_scope("limit"):
                    out = sort_kernels._limit_batch_impl(
                        batch, n, emitted)
                    return out, emitted + jnp.sum(out.row_valid)
            return _kernels.jit(fn, "fragment", "limit")
        self._kernel = _cached_fragment_kernel(
            ("limit", chain_key) if chain_key is not None else None,
            builder)

    def create(self, driver_context: DriverContext) -> Operator:
        return FusedLimitOperator(
            OperatorContext(self.operator_id, self.name,
                            driver_context),
            self._kernel, self.n)


# -- chain -> TopN -----------------------------------------------------

class FusedTopNOperator(TopNOperator):
    """chain + bounded top-N fold in one dispatch per batch: the
    inherited sort_ops.TopNOperator protocol is untouched, only the
    fold step runs the composed kernel (n stays a traced operand so
    LIMIT constants share one compiled fragment per shape)."""

    def __init__(self, ctx: OperatorContext, kernel, n: int,
                 key_names: Sequence[str], descending: Sequence[bool],
                 nulls_first: Sequence[bool],
                 schema_cols: Sequence[tuple]):
        super().__init__(ctx, n, tuple(key_names), tuple(descending),
                         tuple(nulls_first), schema_cols)
        self._kernel = kernel

    def _step(self, batch: Batch) -> Batch:
        return self._kernel(self._state, batch, self.n)


class FusedTopNOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, name: str,
                 stages: Sequence[ChainStage], chain_key, n: int,
                 key_names: Sequence[str], descending: Sequence[bool],
                 nulls_first: Sequence[bool],
                 schema_cols: Sequence[tuple]):
        super().__init__(operator_id, name)
        self.n = n
        self.schema_cols = schema_cols
        keys = self.key_names = tuple(key_names)
        desc = self.descending = tuple(descending)
        nf = self.nulls_first = tuple(nulls_first)
        body = make_chain_body(stages)

        def builder():
            def fn(state: Batch, batch: Batch, n):
                batch = body(batch)
                with jax.named_scope("topn"):
                    return sort_kernels._topn_step_impl(
                        state, batch, n, keys, desc, nf)
            return _kernels.jit(fn, "fragment", "topn")
        self._kernel = _cached_fragment_kernel(
            ("topn", chain_key, keys, desc, nf)
            if chain_key is not None else None,
            builder)

    def create(self, driver_context: DriverContext) -> Operator:
        return FusedTopNOperator(
            OperatorContext(self.operator_id, self.name,
                            driver_context),
            self._kernel, self.n, self.key_names, self.descending,
            self.nulls_first, self.schema_cols)


# -- chain -> DISTINCT -------------------------------------------------

class FusedDistinctOperator(DistinctOperator):
    """chain + dedup fold in one dispatch: the inherited grow-on-full
    protocol re-merges the OLD STATE through the plain distinct kernel
    (the chain applies to incoming batches exactly once); only the
    batch-incorporating step runs the composed kernel."""

    def __init__(self, ctx: OperatorContext, kernel,
                 schema_cols: Sequence[tuple], capacity: int = 4096):
        super().__init__(ctx, schema_cols, capacity)
        self._kernel = kernel

    def _step(self, batch: Batch) -> Batch:
        return self._kernel(self._state, batch)


class FusedDistinctOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, name: str,
                 stages: Sequence[ChainStage], chain_key,
                 schema_cols: Sequence[tuple], capacity: int = 4096):
        super().__init__(operator_id, name)
        self.schema_cols = schema_cols
        self.capacity = capacity
        body = make_chain_body(stages)

        def builder():
            def fn(state: Batch, batch: Batch):
                batch = body(batch)
                with jax.named_scope("distinct"):
                    return sort_kernels._distinct_step_impl(
                        state, batch)
            return _kernels.jit(fn, "fragment", "distinct")
        self._kernel = _cached_fragment_kernel(
            ("distinct", chain_key) if chain_key is not None else None,
            builder)

    def create(self, driver_context: DriverContext) -> Operator:
        return FusedDistinctOperator(
            OperatorContext(self.operator_id, self.name,
                            driver_context),
            self._kernel, self.schema_cols, self.capacity)


# -- kernel contract (tools/kernelcheck.py) ----------------------------
#
# The fragment family is every whole-fragment composition; the
# contract traces the chain->limit composition (the FusedLimit builder
# body, verbatim) — chain semantics are shared with filter_project via
# make_chain_body, terminal folds are each checked under their own
# family's contract. LIMIT n and the emitted count MUST ride as traced
# operands (the variant axis).
from presto_tpu.analysis.contracts import (
    KernelContract, TracePoint, abstract_batch, register_contract,
)


def _fragment_point(cap, variant):
    import numpy as np
    from presto_tpu.expr import ir
    from presto_tpu.expr.compile import compile_expression
    from presto_tpu.schema import ColumnSchema
    from presto_tpu.types import BIGINT, BOOLEAN, DOUBLE
    schema = {"x": ColumnSchema("x", BIGINT),
              "y": ColumnSchema("y", DOUBLE)}
    filt = compile_expression(
        ir.call("less_than", BOOLEAN, ir.ref("y", DOUBLE),
                ir.lit(0.5, DOUBLE)), schema)
    stages = [ChainStage(
        filt, (("x", compile_expression(ir.ref("x", BIGINT), schema)),),
        None)]
    body = make_chain_body(stages)

    def fn(batch, n, emitted):
        out = sort_kernels._limit_batch_impl(body(batch), n, emitted)
        return out, emitted + jnp.sum(out.row_valid)

    b, rb = abstract_batch(cap, [("x", BIGINT), ("y", DOUBLE)])
    n = np.int64(variant.get("n", 10))
    return TracePoint(fn, (b, n, np.int64(0)),
                      (rb, "clean", "clean"))


register_contract(KernelContract(
    family="fragment", module=__name__, build=_fragment_point,
    variants=({"n": 10}, {"n": 500})))
