"""Operator protocol (reference: operator/Operator.java:20 —
needsInput/addInput/getOutput/finish/isBlocked — and OperatorContext /
DriverContext stats plumbing, operator/OperatorContext.java)."""

from __future__ import annotations

import abc
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

from presto_tpu.batch import Batch


class RetryableTaskError(Exception):
    """A TRANSIENT task failure (lost device, dropped RPC, injected
    fault): the mesh driver may re-run just the failed lifespan
    generation from its retained exchange inputs instead of the whole
    query (P7 recoverable grouped execution; reference:
    PlanFragmenter.java:243-260 recoverable lifespans). Deterministic
    errors (OOM, overflow protocols) must NOT use this type — their
    retries need changed settings, not a re-roll."""


@dataclasses.dataclass
class OperatorStats:
    """Per-operator counters surfaced through EXPLAIN ANALYZE / REST
    (reference: operator/OperatorStats.java).

    Row counts accumulate as DEVICE scalars (async adds, no host sync
    on the hot path) and materialize once when the query drains; busy
    time is only meaningful in profiled runs, where the driver blocks
    on each operator's output (device-inclusive timing)."""
    input_batches: int = 0
    input_rows: int = 0
    output_batches: int = 0
    output_rows: int = 0
    busy_seconds: float = 0.0
    #: XLA attribution, credited by telemetry.kernels at the jit-kernel
    #: cache boundary while this operator's add_input/get_output runs:
    #: a kernel call that grew the jit executable cache was a COMPILE
    #: (cache-miss trace), anything else is dispatch/execute
    compile_ns: int = 0
    execute_ns: int = 0
    #: wall ns this operator reported is_blocked() while the driver
    #: wanted to move a batch through it (profiled runs only)
    blocked_ns: int = 0
    #: batch payload bytes moved through this operator (profiled runs
    #: only — batch_bytes reads array metadata, no device sync)
    input_bytes: int = 0
    output_bytes: int = 0
    #: operator-state spill (memory revocation) counters
    spilled_batches: int = 0
    spilled_bytes: int = 0
    #: cache-hierarchy counters (page-source hits/misses on scans,
    #: fragment replays/recordings) — rendered by EXPLAIN ANALYZE
    cache_hits: int = 0
    cache_misses: int = 0
    #: row counters armed for THIS operator: always under profile,
    #: and selectively for history-recorded operators on plain runs
    #: (DriverContext.count_rows_ops) — the accumulation stays a
    #: device-side async add either way, one host sync at drain
    count_rows: bool = False
    input_rows_dev: Any = None
    output_rows_dev: Any = None

    def materialize(self) -> None:
        """One host sync per counter, at drain time."""
        if self.input_rows_dev is not None:
            self.input_rows = int(self.input_rows_dev)
        if self.output_rows_dev is not None:
            self.output_rows = int(self.output_rows_dev)

    def snapshot(self) -> Dict[str, Any]:
        """Plain-dict copy of the scalar counters. Built explicitly —
        dataclasses.asdict would deep-copy the live *_dev device
        arrays (a device allocation each), and nulling them around the
        walk would be a mutate-under-read hazard for any live-status
        sampler."""
        self.materialize()
        return {
            "input_batches": self.input_batches,
            "input_rows": self.input_rows,
            "output_batches": self.output_batches,
            "output_rows": self.output_rows,
            "busy_seconds": self.busy_seconds,
            "compile_ns": self.compile_ns,
            "execute_ns": self.execute_ns,
            "blocked_ns": self.blocked_ns,
            "input_bytes": self.input_bytes,
            "output_bytes": self.output_bytes,
            "spilled_batches": self.spilled_batches,
            "spilled_bytes": self.spilled_bytes,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            # distinguishes a MEASURED zero from never-counted: the
            # history recorder must not record 0 rows for an operator
            # whose counters were simply disarmed
            "rows_counted": self.count_rows,
        }


@dataclasses.dataclass
class DriverContext:
    """Execution context shared by the operators of one driver."""
    session: Any = None
    memory: Any = None  # MemoryContext, wired in execution/memory.py
    #: profiled execution (EXPLAIN ANALYZE): count rows per operator and
    #: time each output with a device barrier
    profile: bool = False
    #: sync-free error protocol: operators append (read_flag, make_exc)
    #: pairs; the drive loop fetches every flag in ONE host sync after
    #: all drivers finish and raises the first tripped one. Keeps
    #: per-batch hot paths free of device->host reads (the join
    #: capacity / group limit pattern).
    deferred_checks: List[Any] = dataclasses.field(default_factory=list)
    #: operator ids whose row counters the history recorder wants even
    #: on unprofiled runs (presto_tpu/history.interesting_ops); None =
    #: profile-only counting, the pre-history behavior
    count_rows_ops: Any = None


def run_deferred_checks(dctx: "DriverContext") -> None:
    """Fetch every deferred device flag in ONE host sync and raise the
    first tripped error (called by drive loops after all drivers
    finish, before results are trusted)."""
    flags, excs = [], []
    for check in dctx.deferred_checks:
        flag, make_exc = check()
        if flag is not None:
            flags.append(flag)
            excs.append(make_exc)
    if not flags:
        return
    import jax
    from presto_tpu.telemetry import ledger as _ledger
    # device_get, not stack: task flags may live on different devices
    # of a mesh; one gather call still fetches them together. The
    # gather blocks on every dispatch the flags depend on — that wall
    # is the device finishing, not drive-loop self time.
    with _ledger.span("device_wait"):
        tripped = jax.device_get(flags)
    for hit, make_exc in zip(tripped, excs):
        if bool(hit):
            raise make_exc()


class OperatorContext:
    def __init__(self, operator_id: int, name: str,
                 driver_context: DriverContext):
        self.operator_id = operator_id
        self.name = name
        self.driver_context = driver_context
        self.stats = OperatorStats()
        self.stats.count_rows = driver_context.profile or (
            driver_context.count_rows_ops is not None
            and operator_id in driver_context.count_rows_ops)
        # pool tag must be unique per operator INSTANCE: operator ids
        # restart per planner, and mesh tasks/lifespan generations all
        # share one query pool
        self.tag = f"{name}#{operator_id}@{id(self):x}"

    # -- memory accounting (reference: OperatorContext's local memory
    # context chaining up to the query MemoryPool) --------------------

    def reserve_batch(self, batch: Batch) -> None:
        pool = self.driver_context.memory
        if pool is not None:
            from presto_tpu.execution.memory import batch_bytes
            pool.reserve(self.tag, batch_bytes(batch))

    def reserve_bytes(self, nbytes: int) -> None:
        """Device state that is no Batch (a join's direct table)."""
        pool = self.driver_context.memory
        if pool is not None:
            pool.reserve(self.tag, nbytes)

    def release_all(self) -> None:
        pool = self.driver_context.memory
        if pool is not None:
            pool.free_all(self.tag)

    # -- spill (memory revocation) helpers ----------------------------

    def register_revocable(self, spill) -> None:
        """Expose this operator's spill callback to the pool. `spill`
        returns bytes freed (and must free its own reservations)."""
        pool = self.driver_context.memory
        if pool is not None:
            pool.register_revocable(self.tag, spill)

    def unregister_revocable(self) -> None:
        pool = self.driver_context.memory
        if pool is not None:
            pool.unregister_revocable(self.tag)

    def count_spill(self, batches: int, nbytes: int) -> None:
        self.stats.spilled_batches += batches
        self.stats.spilled_bytes += nbytes


class Operator(abc.ABC):
    """One stage of a pipeline. Contract (Operator.java:20):

    - `needs_input()` true iff `add_input` may be called
    - `add_input(batch)` accepts one batch (only when needs_input)
    - `get_output()` returns a batch or None (no output ready)
    - `finish()` signals no more input will arrive
    - `is_finished()` true when no more output will be produced
    - `is_blocked()` returns False or a reason string (driver yields)
    """

    #: (counter of input rows, counter of output rows) that a drained
    #: statement adds this operator's armed row counts to
    #: (telemetry/stats.py:count_streamed_rows); None: no such series
    row_series: Optional[Tuple[str, str]] = None

    def __init__(self, ctx: OperatorContext):
        self.ctx = ctx

    @abc.abstractmethod
    def needs_input(self) -> bool: ...

    @abc.abstractmethod
    def add_input(self, batch: Batch) -> None: ...

    @abc.abstractmethod
    def get_output(self) -> Optional[Batch]: ...

    @abc.abstractmethod
    def finish(self) -> None: ...

    @abc.abstractmethod
    def is_finished(self) -> bool: ...

    def is_blocked(self):
        return False

    def close(self) -> None:
        pass

    # -- stats helpers ------------------------------------------------------

    def _count_in(self, batch: Batch) -> None:
        s = self.ctx.stats
        s.input_batches += 1
        if s.count_rows:
            import jax.numpy as jnp
            n = jnp.sum(batch.row_valid)
            s.input_rows_dev = n if s.input_rows_dev is None \
                else s.input_rows_dev + n
            if self.ctx.driver_context.profile:
                from presto_tpu.execution.memory import batch_bytes
                s.input_bytes += batch_bytes(batch)

    def _count_out(self, batch: Optional[Batch]) -> Optional[Batch]:
        if batch is not None:
            s = self.ctx.stats
            s.output_batches += 1
            if s.count_rows:
                import jax.numpy as jnp
                n = jnp.sum(batch.row_valid)
                s.output_rows_dev = n if s.output_rows_dev is None \
                    else s.output_rows_dev + n
                if self.ctx.driver_context.profile:
                    from presto_tpu.execution.memory import batch_bytes
                    s.output_bytes += batch_bytes(batch)
        return batch


class OperatorFactory(abc.ABC):
    """Creates one Operator per driver (reference: OperatorFactory in
    operator/ — factories are what LocalExecutionPlanner emits)."""

    def __init__(self, operator_id: int, name: str):
        self.operator_id = operator_id
        self.name = name

    @abc.abstractmethod
    def create(self, driver_context: DriverContext) -> Operator: ...

    def no_more_operators(self) -> None:
        """Called when every driver's operator has been created."""
