"""Cross join, EnforceSingleRow, and local union plumbing (reference:
NestedLoopBuildOperator/NestedLoopJoinOperator, EnforceSingleRowOperator,
and operator/exchange/LocalExchange.java:64 for the union queue)."""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.telemetry import kernels as _kernels
from presto_tpu.batch import Batch, Column, bucket_capacity
from presto_tpu.operators.base import (
    DriverContext, Operator, OperatorContext, OperatorFactory,
)


class NestedLoopBridge:
    """Materialized build side for cross joins."""

    def __init__(self):
        self.batch: Optional[Batch] = None

    @property
    def ready(self) -> bool:
        return self.batch is not None


class NestedLoopBuildOperator(Operator):
    def __init__(self, ctx: OperatorContext, bridge: NestedLoopBridge,
                 schema_cols: Optional[Sequence[tuple]] = None):
        super().__init__(ctx)
        self.bridge = bridge
        self.schema_cols = schema_cols
        self._batches: List[Batch] = []
        self._finished = False

    def needs_input(self) -> bool:
        return not self._finished

    def add_input(self, batch: Batch) -> None:
        self._count_in(batch)
        self.ctx.reserve_batch(batch)
        self._batches.append(batch)

    def get_output(self) -> Optional[Batch]:
        return None

    def finish(self) -> None:
        if self._finished:
            return
        self._finished = True
        if not self._batches:
            if self.schema_cols is None:
                raise RuntimeError("empty cross-join build needs "
                                   "schema plumbing (planner bug)")
            from presto_tpu.batch import empty_batch
            self.bridge.batch = empty_batch(self.schema_cols)
            return
        total = int(sum(jnp.sum(b.row_valid) for b in self._batches))
        self.bridge.batch = Batch.concat(
            self._batches, bucket_capacity(max(total, 1)),
            live_rows=total)
        self._batches = []

    def is_finished(self) -> bool:
        return self._finished

    def close(self) -> None:
        self._batches = []
        self.bridge.batch = None


class NestedLoopJoinOperator(Operator):
    """Cross product; build sides here are small by construction
    (scalar subqueries, EXISTS counts, tiny dimension tables)."""

    def __init__(self, ctx: OperatorContext, bridge: NestedLoopBridge):
        super().__init__(ctx)
        self.bridge = bridge
        self._pending: Optional[Batch] = None
        self._finishing = False

    def is_blocked(self):
        return False if self.bridge.ready else "waiting for nl build"

    def needs_input(self) -> bool:
        return self.bridge.ready and self._pending is None \
            and not self._finishing

    def add_input(self, batch: Batch) -> None:
        self._count_in(batch)
        build = self.bridge.batch
        nb = build.num_valid()
        np_rows = batch.num_valid()
        out_cap = bucket_capacity(max(nb * np_rows, 1))
        if out_cap > 1 << 24:
            raise RuntimeError(
                f"cross join would materialize {nb * np_rows} rows; "
                "add a join condition")
        self._pending = _cross_product(
            batch.compact(), build.compact(), out_cap)

    def get_output(self) -> Optional[Batch]:
        out, self._pending = self._pending, None
        return self._count_out(out)

    def finish(self) -> None:
        self._finishing = True

    def is_finished(self) -> bool:
        return self._finishing and self._pending is None


import functools


@functools.partial(_kernels.jit, family="nested_loop",
                   static_argnums=(2,))
def _cross_product(probe: Batch, build: Batch, out_cap: int) -> Batch:
    nb_valid = jnp.sum(build.row_valid)
    np_valid = jnp.sum(probe.row_valid)
    slots = jnp.arange(out_cap)
    pid = slots // jnp.maximum(nb_valid, 1)
    bid = slots % jnp.maximum(nb_valid, 1)
    live = slots < (nb_valid * np_valid)
    pid = jnp.clip(pid, 0, probe.capacity - 1)
    bid = jnp.clip(bid, 0, build.capacity - 1)
    cols: Dict[str, Column] = {}
    for name, c in probe.columns.items():
        cols[name] = Column(c.data[pid], c.mask[pid] & live, c.type,
                            c.dictionary)
    for name, c in build.columns.items():
        cols[name] = Column(c.data[bid], c.mask[bid] & live, c.type,
                            c.dictionary)
    return Batch(cols, live)


# compile-vs-execute attribution for the nested-loop (cross join)
# family — previously an uninstrumented module-level jit
_instr = _kernels.instrument_kernel

_cross_product = _instr(_cross_product, "nested_loop")


class AssignUniqueIdOperator(Operator):
    """Appends a unique BIGINT row-id column (reference:
    AssignUniqueIdOperator): id = batch_offset + position. Padding rows
    get ids too (harmless — their row_valid is False)."""

    def __init__(self, ctx: OperatorContext, symbol: str,
                 start: int = 0, stride: int = 1):
        super().__init__(ctx)
        self.symbol = symbol
        # ids = start + k * stride keeps ids unique across the tasks of
        # a distributed fragment (task t of W uses start=t, stride=W)
        self._start = start
        self._stride = stride
        self._offset = 0
        self._pending: Optional[Batch] = None
        self._finishing = False

    def needs_input(self) -> bool:
        return self._pending is None and not self._finishing

    def add_input(self, batch: Batch) -> None:
        self._count_in(batch)
        from presto_tpu.types import BIGINT
        ids = self._start + self._stride * (
            self._offset + jnp.arange(batch.capacity, dtype=jnp.int64))
        self._offset += batch.capacity
        cols = dict(batch.columns)
        cols[self.symbol] = Column(ids, jnp.ones(batch.capacity, bool),
                                   BIGINT, None)
        self._pending = Batch(cols, batch.row_valid)

    def get_output(self) -> Optional[Batch]:
        out, self._pending = self._pending, None
        return self._count_out(out)

    def finish(self) -> None:
        self._finishing = True

    def is_finished(self) -> bool:
        return self._finishing and self._pending is None


class AssignUniqueIdOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, symbol: str,
                 start: int = 0, stride: int = 1):
        super().__init__(operator_id, "assign_unique_id")
        self.symbol = symbol
        self.start = start
        self.stride = stride

    def create(self, driver_context: DriverContext) -> Operator:
        return AssignUniqueIdOperator(
            OperatorContext(self.operator_id, self.name, driver_context),
            self.symbol, self.start, self.stride)


class UnnestOperator(Operator):
    """Static-length UNNEST replication (reference:
    operator/unnest/UnnestOperator.java — ours unrolls fixed-size
    ARRAY constructors): replica i of each input batch selects every
    array's i-th element column; arrays shorter than the longest pad
    NULL; ordinality is the constant i+1. String element columns are
    re-encoded onto the output field's union dictionary so one output
    code space covers all replicas."""

    def __init__(self, ctx: OperatorContext,
                 items: Sequence[Tuple[str, List[str], Optional[str]]],
                 ordinality_symbol: Optional[str],
                 out_dicts: Dict[str, Optional[tuple]]):
        super().__init__(ctx)
        self.items = list(items)
        self.ordinality_symbol = ordinality_symbol
        self.out_dicts = out_dicts
        self.depth = max(len(syms) for _, syms, _ in items)
        self._pending: List[Batch] = []
        self._finishing = False

    def needs_input(self) -> bool:
        return not self._pending and not self._finishing

    def add_input(self, batch: Batch) -> None:
        from presto_tpu.batch import remap_column
        from presto_tpu.types import BIGINT
        self._count_in(batch)
        cap = batch.capacity
        for i in range(self.depth):
            cols = dict(batch.columns)
            row_keep = None
            for out_sym, elem_syms, len_sym in self.items:
                # dynamic length (split etc.): element i exists for a
                # row iff i < its true length; static arrays use the
                # slot count
                if len_sym is not None:
                    lcol = batch.columns[len_sym]
                    in_arr = lcol.mask & (lcol.data > i)
                else:
                    in_arr = None  # statically in range (or padding)
                if i < len(elem_syms):
                    col = batch.columns[elem_syms[i]]
                    target = self.out_dicts.get(out_sym)
                    if target is not None \
                            and col.dictionary != target:
                        col = remap_column(col, target)
                    if in_arr is not None:
                        col = Column(col.data, col.mask & in_arr,
                                     col.type, col.dictionary)
                    item_has = in_arr if in_arr is not None else \
                        jnp.ones(cap, bool)
                else:  # zip padding: NULL element
                    ref = batch.columns[elem_syms[0]]
                    col = Column(ref.data, jnp.zeros(cap, bool),
                                 ref.type,
                                 self.out_dicts.get(out_sym))
                    item_has = jnp.zeros(cap, bool) \
                        if in_arr is None else in_arr
                cols[out_sym] = col
                row_keep = item_has if row_keep is None \
                    else (row_keep | item_has)
            if self.ordinality_symbol is not None:
                cols[self.ordinality_symbol] = Column(
                    jnp.full(cap, i + 1, jnp.int64),
                    jnp.ones(cap, bool), BIGINT, None)
            rv = batch.row_valid if row_keep is None \
                else batch.row_valid & row_keep
            self._pending.append(Batch(cols, rv))

    def get_output(self) -> Optional[Batch]:
        if not self._pending:
            return None
        return self._count_out(self._pending.pop(0))

    def finish(self) -> None:
        self._finishing = True

    def is_finished(self) -> bool:
        return self._finishing and not self._pending


class UnnestOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, items, ordinality_symbol,
                 out_dicts):
        super().__init__(operator_id, "unnest")
        self.items = items
        self.ordinality_symbol = ordinality_symbol
        self.out_dicts = out_dicts

    def create(self, driver_context: DriverContext) -> Operator:
        return UnnestOperator(
            OperatorContext(self.operator_id, self.name, driver_context),
            self.items, self.ordinality_symbol, self.out_dicts)


class GroupIdOperator(Operator):
    """GROUPING SETS replication (reference: GroupIdOperator.java): each
    input batch is emitted once per grouping set with the key columns
    NOT in that set masked to NULL, plus a constant group-id column and
    one constant column per grouping(...) call. Aggregation args flow
    through unchanged — only the materialized key copies are nulled."""

    def __init__(self, ctx: OperatorContext,
                 groupings: Sequence[Tuple[str, ...]],
                 gid_symbol: str,
                 grouping_outputs: Sequence[Tuple[str, Tuple[int, ...]]]):
        super().__init__(ctx)
        self.groupings = list(groupings)
        self.gid_symbol = gid_symbol
        self.grouping_outputs = list(grouping_outputs)
        self._all_keys = set().union(*map(set, self.groupings)) \
            if self.groupings else set()
        # constant gid/grouping columns cached per batch capacity
        self._consts: Dict[int, List[Dict[str, Column]]] = {}
        self._pending: List[Batch] = []
        self._finishing = False

    def needs_input(self) -> bool:
        return not self._pending and not self._finishing

    def _const_cols(self, cap: int) -> List[Dict[str, Column]]:
        from presto_tpu.types import BIGINT
        cached = self._consts.get(cap)
        if cached is None:
            true_mask = jnp.ones(cap, bool)
            cached = []
            for g in range(len(self.groupings)):
                cols = {self.gid_symbol: Column(
                    jnp.full(cap, g, jnp.int64), true_mask, BIGINT,
                    None)}
                for sym, vals in self.grouping_outputs:
                    cols[sym] = Column(
                        jnp.full(cap, vals[g], jnp.int64), true_mask,
                        BIGINT, None)
                cached.append(cols)
            self._consts[cap] = cached
        return cached

    def add_input(self, batch: Batch) -> None:
        self._count_in(batch)
        cap = batch.capacity
        consts = self._const_cols(cap)
        null_mask = jnp.zeros(cap, bool)
        for g, present in enumerate(self.groupings):
            cols = dict(batch.columns)
            for name in self._all_keys:
                if name not in present:
                    col = batch.columns[name]
                    cols[name] = Column(col.data, null_mask,
                                        col.type, col.dictionary)
            cols.update(consts[g])
            self._pending.append(Batch(cols, batch.row_valid))

    def get_output(self) -> Optional[Batch]:
        if not self._pending:
            return None
        return self._count_out(self._pending.pop(0))

    def finish(self) -> None:
        self._finishing = True

    def is_finished(self) -> bool:
        return self._finishing and not self._pending


class GroupIdOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int,
                 groupings: Sequence[Tuple[str, ...]],
                 gid_symbol: str,
                 grouping_outputs: Sequence[Tuple[str, Tuple[int, ...]]]):
        super().__init__(operator_id, "group_id")
        self.groupings = groupings
        self.gid_symbol = gid_symbol
        self.grouping_outputs = grouping_outputs

    def create(self, driver_context: DriverContext) -> Operator:
        return GroupIdOperator(
            OperatorContext(self.operator_id, self.name, driver_context),
            self.groupings, self.gid_symbol, self.grouping_outputs)


class EnforceSingleRowOperator(Operator):
    """Scalar subquery contract (reference: EnforceSingleRowOperator):
    error on >1 row; a 0-row input yields one all-NULL row."""

    def __init__(self, ctx: OperatorContext):
        super().__init__(ctx)
        self._batches: List[Batch] = []
        self._finishing = False
        self._emitted = False

    def needs_input(self) -> bool:
        return not self._finishing

    def add_input(self, batch: Batch) -> None:
        self._count_in(batch)
        self._batches.append(batch)

    def get_output(self) -> Optional[Batch]:
        if not self._finishing or self._emitted:
            return None
        self._emitted = True
        total = int(sum(jnp.sum(b.row_valid) for b in self._batches))
        if total > 1:
            raise RuntimeError(
                "Scalar sub-query has returned multiple rows")
        if total == 1:
            merged = Batch.concat(self._batches, 16, live_rows=total)
            self._batches = []
            return self._count_out(merged)
        # no rows: one row of NULLs
        proto = self._batches[0]
        cols = {}
        for name, c in proto.columns.items():
            cols[name] = Column(jnp.zeros(16, c.data.dtype),
                                jnp.zeros(16, bool), c.type, c.dictionary)
        rv = jnp.zeros(16, bool).at[0].set(True)
        self._batches = []
        return self._count_out(Batch(cols, rv))

    def finish(self) -> None:
        self._finishing = True

    def is_finished(self) -> bool:
        return self._finishing and self._emitted


class LocalQueue:
    """In-process exchange between pipelines (LocalExchange.java:64)."""

    def __init__(self, producers: int):
        self.items: List[Batch] = []
        self.open_producers = producers

    def push(self, batch: Batch) -> None:
        self.items.append(batch)

    def producer_done(self) -> None:
        self.open_producers -= 1

    @property
    def finished(self) -> bool:
        return self.open_producers <= 0 and not self.items


class LocalQueueSinkOperator(Operator):
    """Tail of a producer pipeline; renames symbols to the consumer's."""

    def __init__(self, ctx: OperatorContext, queue: LocalQueue,
                 rename: Dict[str, str]):
        super().__init__(ctx)
        self.queue = queue
        self.rename = rename
        self._finished = False

    def needs_input(self) -> bool:
        return not self._finished

    def add_input(self, batch: Batch) -> None:
        self._count_in(batch)
        self.queue.push(batch.rename(self.rename) if self.rename
                        else batch)

    def get_output(self) -> Optional[Batch]:
        return None

    def finish(self) -> None:
        if not self._finished:
            self._finished = True
            self.queue.producer_done()

    def is_finished(self) -> bool:
        return self._finished

    def close(self) -> None:
        self.finish()


class LocalQueueSourceOperator(Operator):
    def __init__(self, ctx: OperatorContext, queue: LocalQueue):
        super().__init__(ctx)
        self.queue = queue

    def needs_input(self) -> bool:
        return False

    def add_input(self, batch: Batch) -> None:
        raise RuntimeError("source takes no input")

    def is_blocked(self):
        if self.queue.items or self.queue.finished:
            return False
        return "waiting for local exchange"

    def get_output(self) -> Optional[Batch]:
        if self.queue.items:
            return self._count_out(self.queue.items.pop(0))
        return None

    def finish(self) -> None:
        pass

    def is_finished(self) -> bool:
        return self.queue.finished


class Spool:
    """Materialized output of a subtree shared by several plan parents
    (planner-level CSE). Filled ONCE by a SpoolSinkOperator pipeline and
    replayed to every consumer, so a DAG-shaped plan (e.g. the probe
    side of a unique-id EXISTS decorrelation feeding both a JoinNode and
    a SemiJoinNode) executes the shared subtree exactly once — rather
    than twice with a fragile bit-identical-replay assumption.

    Batches are released (slot set to None) once every registered
    consumer's cursor has passed them, so device memory is not pinned
    for the whole query."""

    def __init__(self):
        self.batches: List[Optional[Batch]] = []
        self.done = False
        self._cursors: List[int] = []

    def register_consumer(self) -> int:
        self._cursors.append(0)
        return len(self._cursors) - 1

    def advance(self, consumer: int, position: int) -> None:
        self._cursors[consumer] = position
        floor = min(self._cursors)
        for i in range(floor):
            self.batches[i] = None


class SpoolSinkOperator(Operator):
    def __init__(self, ctx: OperatorContext, spool: Spool):
        super().__init__(ctx)
        self.spool = spool
        self._finished = False

    def needs_input(self) -> bool:
        return not self._finished

    def add_input(self, batch: Batch) -> None:
        self._count_in(batch)
        self.ctx.reserve_batch(batch)
        self.spool.batches.append(batch)

    def get_output(self) -> Optional[Batch]:
        return None

    def finish(self) -> None:
        if not self._finished:
            self._finished = True
            self.spool.done = True

    def is_finished(self) -> bool:
        return self._finished

    def close(self) -> None:
        self.finish()


class SpoolSourceOperator(Operator):
    """Replays a finished spool; each consumer has its own cursor."""

    def __init__(self, ctx: OperatorContext, spool: Spool,
                 consumer: int):
        super().__init__(ctx)
        self.spool = spool
        self._consumer = consumer
        self._i = 0

    def needs_input(self) -> bool:
        return False

    def add_input(self, batch: Batch) -> None:
        raise RuntimeError("source takes no input")

    def is_blocked(self):
        return False if self.spool.done else "waiting for spool fill"

    def get_output(self) -> Optional[Batch]:
        if self.spool.done and self._i < len(self.spool.batches):
            b = self.spool.batches[self._i]
            assert b is not None, "spool batch released before replay"
            self._i += 1
            self.spool.advance(self._consumer, self._i)
            return self._count_out(b)
        return None

    def finish(self) -> None:
        pass

    def is_finished(self) -> bool:
        return self.spool.done and self._i >= len(self.spool.batches)


class _SimpleFactory(OperatorFactory):
    def __init__(self, operator_id: int, name: str, fn):
        super().__init__(operator_id, name)
        self._fn = fn

    def create(self, driver_context: DriverContext) -> Operator:
        return self._fn(OperatorContext(self.operator_id, self.name,
                                        driver_context))


def nested_loop_build_factory(op_id: int, bridge: NestedLoopBridge,
                              schema_cols=None):
    return _SimpleFactory(
        op_id, "nl_build",
        lambda ctx: NestedLoopBuildOperator(ctx, bridge, schema_cols))


def nested_loop_join_factory(op_id: int, bridge: NestedLoopBridge):
    return _SimpleFactory(op_id, "nl_join",
                          lambda ctx: NestedLoopJoinOperator(ctx, bridge))


def enforce_single_row_factory(op_id: int):
    return _SimpleFactory(op_id, "enforce_single_row",
                          EnforceSingleRowOperator)


def queue_sink_factory(op_id: int, queue: LocalQueue,
                       rename: Dict[str, str]):
    return _SimpleFactory(op_id, "local_sink",
                          lambda ctx: LocalQueueSinkOperator(ctx, queue,
                                                             rename))


def queue_source_factory(op_id: int, queue: LocalQueue):
    return _SimpleFactory(op_id, "local_source",
                          lambda ctx: LocalQueueSourceOperator(ctx, queue))


def spool_sink_factory(op_id: int, spool: Spool):
    return _SimpleFactory(op_id, "spool_sink",
                          lambda ctx: SpoolSinkOperator(ctx, spool))


def spool_source_factory(op_id: int, spool: Spool):
    consumer = spool.register_consumer()
    return _SimpleFactory(
        op_id, "spool_source",
        lambda ctx: SpoolSourceOperator(ctx, spool, consumer))


# -- kernel contract (tools/kernelcheck.py) ----------------------------
from presto_tpu.analysis.contracts import (
    KernelContract, TracePoint, abstract_batch, register_contract,
)


def _cross_point(cap, variant):
    from presto_tpu.types import BIGINT, DOUBLE
    p, rp = abstract_batch(cap, [("a", BIGINT), ("b", DOUBLE)])
    bld, rbld = abstract_batch(4096, [("c", BIGINT)])
    return TracePoint(
        lambda pp, bb: _cross_product.__wrapped__(pp, bb, cap),
        (p, bld), (rp, rbld))


register_contract(KernelContract(
    family="nested_loop", module=__name__, build=_cross_point))
