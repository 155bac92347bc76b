"""Core operators: Values, TableScan, FilterAndProject, Limit, Output.

Reference surface: ValuesOperator, TableScanOperator.java:43,
ScanFilterAndProjectOperator.java:58 / FilterAndProjectOperator.java:32,
LimitOperator, and the PageConsumerOperator test sink
(testing/PageConsumerOperator.java).
"""

from __future__ import annotations

import collections
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from presto_tpu.batch import Batch, Column
from presto_tpu.expr.compile import CompiledExpr
from presto_tpu.operators.base import (
    DriverContext, Operator, OperatorContext, OperatorFactory,
)
from presto_tpu.ops import sort as sort_ops
from presto_tpu.telemetry import kernels as _kernels


class SourceOperator(Operator):
    """Base for operators that originate data (no input)."""

    def needs_input(self) -> bool:
        return False

    def add_input(self, batch: Batch) -> None:
        raise RuntimeError(f"{self.ctx.name} takes no input")


class ValuesOperator(SourceOperator):
    def __init__(self, ctx: OperatorContext, batches: List[Batch]):
        super().__init__(ctx)
        self._batches = list(batches)
        self._finished = False

    def get_output(self) -> Optional[Batch]:
        if self._batches:
            return self._count_out(self._batches.pop(0))
        self._finished = True
        return None

    def finish(self) -> None:
        pass

    def is_finished(self) -> bool:
        return self._finished and not self._batches


class ValuesOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, batches: List[Batch]):
        super().__init__(operator_id, "values")
        self.batches = batches
        self._created = False

    def create(self, driver_context: DriverContext) -> Operator:
        # each driver gets the batches once (single-driver pipelines)
        assert not self._created, "values pipeline must be single-driver"
        self._created = True
        return ValuesOperator(
            OperatorContext(self.operator_id, "values", driver_context),
            self.batches)


class TableScanOperator(SourceOperator):
    """Pulls batches from a connector page source (reference:
    TableScanOperator.java:43; splits arrive via the factory).

    `df_specs` [(column, df_id, registry)] wires dynamic filtering:
    once the corresponding join build has published its key bounds,
    every scanned batch narrows row_valid with one fused compare — the
    probe operator's bridge-block guarantees the bounds exist before
    this scan is ever pulled (see execution/dynamic_filters.py)."""

    def __init__(self, ctx: OperatorContext,
                 batch_iter: Iterator[Batch], df_specs=None,
                 cache_box=None):
        super().__init__(ctx)
        self._iter = batch_iter
        self._df_specs = df_specs or []
        #: {"hits": n, "misses": n} shared with the page-source-cache
        #: wrapper around the split loop (planner batch_iter closure)
        self._cache_box = cache_box
        self._finished = False

    def get_output(self) -> Optional[Batch]:
        if self._finished:
            return None
        try:
            b = next(self._iter)
        except StopIteration:
            self._finished = True
            return None
        finally:
            if self._cache_box is not None:
                self.ctx.stats.cache_hits = self._cache_box["hits"]
                self.ctx.stats.cache_misses = self._cache_box["misses"]
        for col, df_id, reg in self._df_specs:
            f = reg.get(df_id)
            if f is not None:
                from presto_tpu.execution.dynamic_filters import apply
                b = apply(b, col, f)
        # (live-row counts stay device-side; EXPLAIN ANALYZE
        #  materializes them once at drain)
        return self._count_out(b)

    def finish(self) -> None:
        pass

    def is_finished(self) -> bool:
        return self._finished


class TableScanOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, name: str,
                 batch_iter_factory: Callable[[], Iterator[Batch]],
                 df_specs=None, cache_box=None):
        super().__init__(operator_id, name)
        self._factory = batch_iter_factory
        self._df_specs = df_specs
        self._cache_box = cache_box

    def create(self, driver_context: DriverContext) -> Operator:
        return TableScanOperator(
            OperatorContext(self.operator_id, self.name, driver_context),
            self._factory(), self._df_specs, self._cache_box)


#: jit-kernel LRU cache keyed by the (hashable) expression IR so re-running
#: a query — or another query with the same filter/projection forest —
#: reuses the compiled XLA program (reference analog: PageFunctionCompiler's
#: size-bounded generated-class cache, sql/gen/PageFunctionCompiler.java:118).
_FP_KERNEL_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_FP_KERNEL_CACHE_MAX = 512


def make_filter_project_kernel(
        filter_expr: Optional[CompiledExpr],
        projections: Sequence[Tuple[str, CompiledExpr]],
        input_dicts: Optional[Tuple[Tuple[str, tuple], ...]] = None):
    """Build the jitted batch->batch kernel. XLA fuses the whole
    expression forest with the mask updates (the PageProcessor analog,
    operator/project/PageProcessor.java:57).

    `input_dicts` is the (name, dictionary) tuple of the dict-encoded
    input columns the expressions were compiled against. It MUST be part
    of the cache key: compiled kernels bake input dictionaries into
    constants (LIKE lookup tables, string-comparison ranks), so the same
    IR compiled against another schema is a different kernel."""
    # A CompiledExpr built directly (ir=None) is indistinguishable from
    # "no filter" / another ir=None projection in the key — never cache
    # those, a collision would silently return the wrong kernel.
    exprs = ([filter_expr] if filter_expr else []) + [ce for _, ce in projections]
    if any(ce.ir is None for ce in exprs):
        key = None
    else:
        try:
            # keys carry structural FINGERPRINTS, not the IR itself:
            # IR __hash__/__eq__ recurse by value, exponential on the
            # shared-accumulator DAGs lambdas produce (expr/ir.py
            # fingerprint)
            from presto_tpu.expr.ir import fingerprint
            key = (fingerprint(filter_expr.ir) if filter_expr
                   else None,
                   tuple((n, fingerprint(ce.ir), ce.dictionary)
                         for n, ce in projections),
                   input_dicts)
            cached = _FP_KERNEL_CACHE.get(key)
            if cached is not None:
                _FP_KERNEL_CACHE.move_to_end(key)
                return cached
        except TypeError:  # unhashable literal somewhere — just don't cache
            key = None

    # the traced body is the whole-fragment compiler's single-stage
    # chain (operators/fused_fragment.py) — ONE definition of the
    # filter/project semantics, so fused and unfused results cannot
    # drift (lazy import: fused_fragment imports this module)
    from presto_tpu.operators.fused_fragment import (
        ChainStage, make_chain_body,
    )
    kernel = _kernels.jit(make_chain_body(
        [ChainStage(filter_expr, tuple(projections), input_dicts)]),
        "filter_project")

    # compile-vs-execute attribution travels WITH the cached kernel:
    # an LRU hit keeps its warm jit cache, so its calls report execute
    # only (telemetry/kernels.py)
    kernel = _kernels.instrument_kernel(kernel, "filter_project")

    if key is not None:
        _FP_KERNEL_CACHE[key] = kernel
        while len(_FP_KERNEL_CACHE) > _FP_KERNEL_CACHE_MAX:
            _FP_KERNEL_CACHE.popitem(last=False)
    return kernel


# -- kernel contract (tools/kernelcheck.py) ----------------------------
#
# filter_project kernels are built per plan from compiled expression
# forests; the contract traces a REPRESENTATIVE forest (comparison
# filter + arithmetic/conditional projections over the dtype lattice)
# through the same make_chain_body the production kernel uses, so the
# checked program is the checked code path, not a stand-in.
from presto_tpu.analysis.contracts import (
    KernelContract, TracePoint, abstract_batch, register_contract,
)


def _fp_point(cap, variant):
    from presto_tpu.expr import ir
    from presto_tpu.expr.compile import compile_expression
    from presto_tpu.schema import ColumnSchema
    from presto_tpu.types import BIGINT, BOOLEAN, DOUBLE
    schema = {"x": ColumnSchema("x", BIGINT),
              "y": ColumnSchema("y", DOUBLE)}
    filt = compile_expression(
        ir.call("greater_than", BOOLEAN, ir.ref("x", BIGINT),
                ir.lit(5, BIGINT)), schema)
    proj = compile_expression(
        ir.call("multiply", DOUBLE, ir.ref("y", DOUBLE),
                ir.lit(2.0, DOUBLE)), schema)
    from presto_tpu.operators.fused_fragment import (
        ChainStage, make_chain_body,
    )
    body = make_chain_body(
        [ChainStage(filt, (("x", compile_expression(
            ir.ref("x", BIGINT), schema)), ("y2", proj)), None)])
    b, rb = abstract_batch(cap, [("x", BIGINT), ("y", DOUBLE)])
    return TracePoint(body, (b,), (rb,))


register_contract(KernelContract(
    family="filter_project", module=__name__, build=_fp_point))


class FilterProjectOperator(Operator):
    """`selective` (a filter is present) enables the one-round-delayed
    count/compact protocol on outputs: a selective filter that emits a
    handful of rows into a fat batch otherwise sends every downstream
    operator sorting/merging dead lanes. Pure projections never change
    row_valid, so they skip the count dispatch entirely."""

    def __init__(self, ctx: OperatorContext, kernel,
                 selective: bool = False):
        super().__init__(ctx)
        self._kernel = kernel
        self._selective = selective
        self._pending: List = []
        self._finishing = False

    def needs_input(self) -> bool:
        return len(self._pending) < (2 if self._selective else 1) \
            and not self._finishing

    def add_input(self, batch: Batch) -> None:
        from presto_tpu.batch import begin_deferred_compact, \
            pad_for_kernel
        self._count_in(batch)
        # kernel shape bucketing: the fused expression kernel's jit
        # cache keys on the batch capacity — pad to the coarse ladder
        # so every split size of every scale factor reuses one trace
        out = self._kernel(pad_for_kernel(batch))
        if self._selective:
            self._pending.append(begin_deferred_compact(out))
        else:
            self._pending.append((out, None))

    def get_output(self) -> Optional[Batch]:
        emit_at = 1 if self._selective and not self._finishing else 0
        if len(self._pending) > emit_at:
            from presto_tpu.batch import end_deferred_compact
            out, total = self._pending.pop(0)
            return self._count_out(end_deferred_compact(out, total))
        return None

    def finish(self) -> None:
        self._finishing = True

    def is_finished(self) -> bool:
        return self._finishing and not self._pending


class FilterProjectOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int,
                 filter_expr: Optional[CompiledExpr],
                 projections: Sequence[Tuple[str, CompiledExpr]],
                 input_dicts: Optional[Tuple[Tuple[str, tuple], ...]] = None,
                 selectivity: Optional[float] = None,
                 sel_provenance: str = "static"):
        super().__init__(operator_id, "filter_project")
        self._kernel = make_filter_project_kernel(filter_expr, projections,
                                                  input_dicts)
        self._selective = filter_expr is not None
        # kept for the whole-fragment fusion pass (planner/fusion.py):
        # adjacent FilterProjects collapse into the downstream
        # terminal's trace, which needs the expression forest — not
        # the already-jitted kernel — plus the planner's estimated
        # fraction of surviving rows (None = unknown), which gates
        # fold-terminal fusion: a highly selective chain keeps its
        # deferred compaction instead of handing the fold full-width
        # dead lanes
        self.filter_expr = filter_expr
        self.projections = tuple(projections)
        self.input_dicts = input_dicts
        self.selectivity = selectivity
        #: "history" when `selectivity` is a MEASURED prior-execution
        #: fraction, "static" for derived heuristics — the fusion gate
        #: treats measured selectivity as licence for history-driven
        #: full fusion with in-trace compaction (planner/fusion.py)
        self.sel_provenance = sel_provenance

    def create(self, driver_context: DriverContext) -> Operator:
        return FilterProjectOperator(
            OperatorContext(self.operator_id, self.name, driver_context),
            self._kernel, self._selective)


class LimitOperator(Operator):
    """LIMIT n (reference: LimitOperator). Tracks emitted rows as a
    device scalar to avoid per-batch recompiles.

    Early termination never BLOCKS on the device: the limit-reached
    flag is fetched asynchronously and only consulted once its transfer
    has completed (`is_ready`), so the hot loop stays free of
    device->host roundtrips — at worst the operator pulls a couple of
    extra batches before noticing the limit was hit (each is still
    correctly truncated by limit_batch)."""

    def __init__(self, ctx: OperatorContext, n: int):
        super().__init__(ctx)
        self._n = n
        self._emitted = jnp.asarray(0, jnp.int64)
        self._flag = None  # device bool: emitted >= n
        self._pending: Optional[Batch] = None
        self._finishing = False
        self._done = False

    def needs_input(self) -> bool:
        if not self._done and self._flag is not None:
            try:
                ready = self._flag.is_ready()
            except AttributeError:  # non-Array (e.g. np scalar)
                ready = True
            if ready and bool(self._flag):
                self._done = True  # stop pulling input
        return self._pending is None and not self._finishing \
            and not self._done

    def _step(self, batch: Batch):
        """(truncated batch, new emitted count) — the whole-fragment
        compiler overrides this with a kernel that folds the upstream
        chain AND the count update into the same dispatch
        (operators/fused_fragment.py); the early-termination protocol
        around it is shared."""
        # n rides as a TRACED operand (like _emitted): LIMIT 10 and
        # LIMIT 500 share one compiled kernel per batch shape
        out = sort_ops.limit_batch(batch, self._n, self._emitted)
        return out, self._emitted + jnp.sum(out.row_valid)

    def add_input(self, batch: Batch) -> None:
        self._count_in(batch)
        out, self._emitted = self._step(batch)
        self._flag = self._emitted >= self._n
        try:
            self._flag.copy_to_host_async()
        except AttributeError:
            pass
        self._pending = out

    def get_output(self) -> Optional[Batch]:
        out, self._pending = self._pending, None
        return self._count_out(out)

    def finish(self) -> None:
        self._finishing = True

    def is_finished(self) -> bool:
        return (self._finishing or self._done) and self._pending is None


class LimitOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, n: int):
        super().__init__(operator_id, "limit")
        self.n = n

    def create(self, driver_context: DriverContext) -> Operator:
        return LimitOperator(
            OperatorContext(self.operator_id, self.name, driver_context),
            self.n)


class OutputCollectorOperator(Operator):
    """Terminal sink gathering result batches (reference analog:
    testing/PageConsumerOperator.java + MaterializedResult)."""

    def __init__(self, ctx: OperatorContext, sink: List[Batch]):
        super().__init__(ctx)
        self.sink = sink
        self._finishing = False

    def needs_input(self) -> bool:
        return not self._finishing

    def add_input(self, batch: Batch) -> None:
        self._count_in(batch)
        self.sink.append(batch)

    def get_output(self) -> Optional[Batch]:
        return None

    def finish(self) -> None:
        self._finishing = True

    def is_finished(self) -> bool:
        return self._finishing


class OutputCollectorOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, sink: List[Batch]):
        super().__init__(operator_id, "output")
        self.sink = sink

    def create(self, driver_context: DriverContext) -> Operator:
        return OutputCollectorOperator(
            OperatorContext(self.operator_id, self.name, driver_context),
            self.sink)
