"""The Driver loop (reference: operator/Driver.java:68; hot loop
processInternal:371 — for each adjacent (current, next) pair, move one
batch current.getOutput() -> next.addInput()).

The host loop only moves device-array handles between operators; jax
dispatch is async, so the device pipeline stays busy while the host walks
the operator chain (SURVEY.md hard part #5).

Batch pump (docs/DATA_PLANE.md): when the fusion pass has reduced a
pipeline to `scan -> fused_kernel -> emit/fold`, the generic pair walk
is pure overhead — every pass re-checks every operator's blocked/
needs-input/finished state to move the one batch that was always going
to move. The pump fast path drives such a split in ONE loop with
double-buffered prefetch: split N+1's scan + host->device transfer
(the `prefetch` ledger frames) overlaps split N's fused kernel, which
JAX's async dispatch left running on the device. Semantics are
identical by construction — the same operator methods run in the same
per-operator order, the `operator.add_input` fault site still fires on
every hand-off, and quantum deadlines still checkpoint every split —
so pump-on and pump-off runs are byte-identical (tests/
test_batch_pump.py holds that oracle). Profiled runs (they block on
every batch by design), and any pipeline containing an operator the
pump cannot model (exchanges, merges, writers), keep the generic loop.

Every call of an operator's method, in either loop, goes through one
hand-off (`_Handoff`): the only place an operator is bound for kernel
attribution, timed, and named on the ledger and the profiler's
timeline. A `query_trace_enabled` statement therefore runs the loop
every other statement runs."""

from __future__ import annotations

import os
import time
from typing import List, Optional

from presto_tpu.execution import faults
from presto_tpu.operators.base import Operator
from presto_tpu.telemetry import kernels as _tk
from presto_tpu.telemetry import ledger as _ledger
from presto_tpu.telemetry import trace as _trace

#: process-wide batch-pump switch: the pump test battery flips it to
#: get the pair loop as its byte-identity reference; the env var sets
#: it for a subprocess
_PUMP_ON = os.environ.get("PRESTO_TPU_PUMP", "1") != "0"


def set_pump(on: bool) -> None:
    global _PUMP_ON
    _PUMP_ON = bool(on)


def pump_enabled() -> bool:
    return _PUMP_ON


class _Handoff:
    """One call of one operator's method (`get_output`, `add_input`,
    `finish`), the only place the driver binds an operator: kernel
    calls inside credit the operator's stats (telemetry/kernels.py),
    the call is the ledger frame `<category>/<operator kind>.<method>`
    (a host event on the profiler's timeline), the frame's elapsed
    time is the operator's busy time, and a per-query recorder, when
    one is current, gets the `op:<kind>.<method>` event. The binding
    cannot outlive the call: width-retry control flow
    (GroupLimitExceeded etc.) raises straight out of add_input, and a
    stale binding would credit kernel time to a dead operator."""

    __slots__ = ("ctx", "method", "category", "recorded", "_frame",
                 "_start_ns")

    def __init__(self, op: Operator, method: str,
                 category: str = "driver.step"):
        self.ctx = op.ctx
        self.method = method
        self.category = category
        #: cleared by a site whose call moved nothing (a get_output
        #: that returned None): the recorder holds hand-offs, not polls
        self.recorded = True

    def __enter__(self):
        ctx = self.ctx
        if _tk.ENABLED:
            _tk.set_current_op(ctx.stats)
        frame = self._frame = _ledger.span(
            self.category, detail=f"{ctx.name}.{self.method}"
        ).__enter__()
        # a thread without a ledger opens no frame: its own clock
        self._start_ns = frame.start_ns if frame is not None \
            else time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        frame = self._frame
        if frame is not None:
            frame.__exit__(*exc)
            dur = frame.elapsed_ns
        else:
            dur = time.perf_counter_ns() - self._start_ns
        ctx = self.ctx
        ctx.stats.busy_seconds += dur / 1e9
        if _tk.ENABLED:
            _tk.set_current_op(None)
        if self.recorded and _trace.ACTIVE:
            rec = _trace.current()
            if rec is not None:
                rec.add(f"op:{ctx.name}.{self.method}", "operator",
                        self._start_ns, dur)
        return False


def _pump_op_sets():
    """(sources, streamable) operator classes the pump may drive —
    resolved lazily to dodge import cycles. Streamable means the pump
    can preserve the pair loop's semantics from the operator's
    declared state alone: at most one output batch moves per
    add_input/get_output round, pending output is advertised through
    `needs_input` (the pump parks the batch and falls through), and
    blocking folds simply absorb until the generic loop drains them.
    Blocking on another driver is fine — the pump re-checks
    `is_blocked` before every split and parks exactly like the pair
    loop (a probe waiting on its build bridge pumps once the build
    publishes). What disqualifies a pipeline is an operator whose
    output cadence the pump cannot see (exchange sources/sinks, the
    k-way merge, writers with commit protocols)."""
    global _PUMP_SOURCES, _PUMP_STREAMABLE
    try:
        return _PUMP_SOURCES, _PUMP_STREAMABLE
    except NameError:
        pass
    from presto_tpu.operators.aggregation import (
        AggregationOperator, StreamingAggregationOperator,
    )
    from presto_tpu.operators.cache_ops import (
        FragmentRecordOperator, FragmentReplayOperator,
    )
    from presto_tpu.operators.core import (
        FilterProjectOperator, LimitOperator, OutputCollectorOperator,
        SourceOperator,
    )
    from presto_tpu.operators.fused_fragment import (
        FusedDistinctOperator, FusedTopNOperator,
    )
    from presto_tpu.operators.join_ops import (
        HashBuildOperator, LookupJoinOperator, SemiJoinOperator,
    )
    from presto_tpu.operators.sort_ops import (
        DistinctOperator, OrderByOperator, TopNOperator,
    )
    _PUMP_SOURCES = (SourceOperator, FragmentReplayOperator)
    # FilterProjectOperator covers fused chains too (a collapsed
    # FusedChainOperatorFactory creates one driving the chain kernel),
    # and LimitOperator covers FusedLimitOperator. The blocking folds
    # (agg, sort, topn, distinct, hash build) absorb input and emit
    # nothing until the generic loop drains them; the join probes
    # pipeline a bounded pending queue behind `needs_input`.
    _PUMP_STREAMABLE = (
        FilterProjectOperator, LimitOperator, FusedTopNOperator,
        FusedDistinctOperator, AggregationOperator,
        StreamingAggregationOperator, FragmentRecordOperator,
        OutputCollectorOperator, HashBuildOperator,
        LookupJoinOperator, SemiJoinOperator, OrderByOperator,
        TopNOperator, DistinctOperator,
    )
    return _PUMP_SOURCES, _PUMP_STREAMABLE


class Driver:
    #: quantum results (execution/task_executor.py): FINISHED = no
    #: more work ever; BLOCKED = an operator reports is_blocked(), the
    #: worker should park this driver instead of busy-spinning;
    #: PROGRESS = the quantum expired with work left; IDLE = nothing
    #: moved and nothing blocked (state machines may need another
    #: pass — finish propagation, deferred flushes)
    FINISHED = "finished"
    BLOCKED = "blocked"
    PROGRESS = "progress"
    IDLE = "idle"

    def __init__(self, operators: List[Operator]):
        assert operators, "driver needs at least one operator"
        self.operators = operators
        self._closed = False
        #: batch-pump state: None = eligibility undecided, False =
        #: ineligible pipeline shape, True = pumpable. `_prefetched`
        #: holds split N+1 pulled while split N's kernel runs;
        #: `_pump_drained` flips once the source is exhausted and the
        #: generic loop owns finish propagation + the fold drain.
        self._pump: Optional[bool] = None
        self._prefetched = None
        self._pump_drained = False
        self._pump_splits = 0
        #: passes over the chain, by whether one moved a batch (a pump
        #: split is a pass that moved; a pass that found an operator
        #: blocked, or nothing to do, is one that did not): added to
        #: presto_tpu_driver_passes_total in close(), whichever loop
        #: (executor quantum, pump, the mesh's process()) made them
        self._passes_moved = 0
        self._passes_idle = 0

    def is_finished(self) -> bool:
        return self._closed or self.operators[-1].is_finished()

    def blocked_reason(self) -> Optional[str]:
        """Name of the first blocked operator, or None. The executor
        parks a driver on any blocked operator — the serial loop's
        per-PAIR skip degenerates to the same thing one level up,
        because a blocked stage starves its neighbors within a few
        passes anyway."""
        for op in self.operators:
            if op.is_blocked():
                return op.ctx.name
        return None

    def process_quantum(self, quantum_s: float):
        """Run passes over the operator chain until `quantum_s` of
        wall clock elapses, the driver finishes, blocks, or stops
        moving. Returns (status, progressed): one of the class status
        constants plus whether ANY batch moved this quantum — the
        executor's progress/idle accounting and its wake-parked-
        siblings signal both key off `progressed`.

        blocked_ns stays correct across quantum suspensions: the
        open-window marks (`ctx._blocked_since`) live on the operator
        contexts and are wall-clock anchored, and a driver is owned by
        at most one worker at a time — parked wall time IS blocked
        wall time, exactly what the serial loop measured."""
        deadline = time.perf_counter() + quantum_s
        progressed = False
        if self._pump_ok():
            with _ledger.span("driver.step"):
                status, progressed = self._pump_quantum(deadline)
            if status is not None:
                return status, progressed
            # status None: the source drained (or the chain backed
            # up) mid-quantum — the generic loop below finishes the
            # job; splits already pumped still count as progress
        with _ledger.span("driver.step"):
            while True:
                if self.is_finished():
                    return self.FINISHED, progressed
                moved = self._process_once()
                progressed = progressed or moved
                if self.is_finished():
                    return self.FINISHED, progressed
                if not moved:
                    if self.blocked_reason() is not None:
                        return self.BLOCKED, progressed
                    return self.IDLE, progressed
                if time.perf_counter() >= deadline:
                    return self.PROGRESS, progressed

    # -- batch pump --------------------------------------------------------

    def _pump_ok(self) -> bool:
        """Pump this quantum? Cheap after the first call: eligibility
        is a cached shape property (profiled runs want device-
        inclusive per-operator timing and keep the pair loop: static
        per driver context, checked once); the per-quantum part is
        only the global switch and the drained flag."""
        if not _PUMP_ON or self._pump_drained or self._pump is False:
            return False
        if self._pump is None:
            self._pump = self._pump_eligible()
        return self._pump

    def _pump_eligible(self) -> bool:
        from presto_tpu.telemetry.metrics import METRICS
        ops = self.operators
        sources, streamable = _pump_op_sets()
        ok = (len(ops) >= 2
              and not ops[0].ctx.driver_context.profile
              and isinstance(ops[0], sources)
              and all(isinstance(op, streamable) for op in ops[1:]))
        METRICS.inc("presto_tpu_pump_drivers_total",
                    status="pump" if ok else "step")
        return ok

    def _pump_quantum(self, deadline: float):
        """Drive `scan -> fused_kernel -> emit/fold` splits until the
        quantum expires, an operator blocks, or the source drains.
        Returns (status, progressed); status None means fall through
        to the generic pair loop (drain/finish propagation, or a
        backed-up stage the pump won't model)."""
        ops = self.operators
        src = ops[0]
        progressed = False
        while True:
            if self.is_finished():
                return self.FINISHED, progressed
            for op in ops:
                if op.is_blocked():
                    self._passes_idle += 1
                    return self.BLOCKED, progressed
                if op is not src and op.is_finished():
                    # early termination (LIMIT hit mid-chain): the
                    # generic loop owns finish propagation
                    return None, progressed
            buf = self._prefetched
            self._prefetched = None
            if buf is None:
                buf = self._pump_pull()      # prime the double buffer
                if buf is None:
                    if not src.is_finished():
                        self._passes_idle += 1
                        return self.IDLE, progressed
                    self._pump_drained = True
                    return None, progressed
            if not all(op.needs_input() for op in ops[1:]):
                # a backed-up stage (e.g. a deferred-compact window at
                # depth): park the batch back in the buffer and let the
                # generic loop drain — the buffer is re-consumed first
                # thing next quantum, so no batch is lost or reordered
                self._prefetched = buf
                return None, progressed
            # split N: one add_input dispatches the whole fused chain
            # asynchronously — the host is back here while the device
            # still works ...
            self._pump_feed(buf)
            progressed = True
            self._pump_splits += 1
            self._passes_moved += 1
            # ... which is exactly when split N+1's scan + h2d runs
            # (the double buffer: device computes N, host readies N+1)
            if not src.is_finished():
                self._prefetched = self._pump_pull()
            if self._prefetched is None and src.is_finished():
                self._pump_drained = True
                return None, progressed
            if time.perf_counter() >= deadline:
                return self.PROGRESS, progressed

    def _pump_pull(self):
        """One source pull under the ledger's `prefetch` frame: the
        nested scan/h2d spans charge themselves, so `prefetch` is the
        overlap machinery's own self time."""
        src = self.operators[0]
        with _Handoff(src, "get_output", "prefetch") as h:
            batch = src.get_output()
            h.recorded = batch is not None
        return batch

    def _pump_feed(self, batch) -> None:
        """Move one prefetched batch through ops[1:], preserving the
        pair loop's per-hand-off contract: the `operator.add_input`
        fault site fires, kernel time binds to the consuming
        operator's stats, and busy_seconds accumulate."""
        ops = self.operators
        armed = faults.ARMED
        x = batch
        for op in ops[1:]:
            if armed:
                faults.fire("operator.add_input", op=op,
                            name=op.ctx.name)
            with _Handoff(op, "add_input"):
                op.add_input(x)
            if op is ops[-1]:
                break
            with _Handoff(op, "get_output") as h:
                x = op.get_output()
                h.recorded = x is not None
            if x is None:
                # absorbed by a fold (or pipelined inside a deferred-
                # compact window): nothing to move further downstream
                return
        self._drain_tail()

    def _drain_tail(self) -> bool:
        """The self-driving tail (a sink's flush): one get_output of
        the last operator, in both loops. True if it emitted."""
        tail = self.operators[-1]
        if tail.is_finished() or tail.is_blocked():
            return False
        with _Handoff(tail, "get_output") as h:
            out = tail.get_output()
            h.recorded = out is not None
        return out is not None

    def process(self, max_iterations: int = 1) -> bool:
        """Run up to `max_iterations` passes over the operator chain
        (the analog of Driver.processFor's time quantum). Returns True if
        any progress (batch moved / state advanced) was made."""
        progress = False
        for _ in range(max_iterations):
            moved = self._process_once()
            progress = progress or moved
            if self.is_finished():
                break
        return progress

    def _process_once(self) -> bool:
        """One pass of the pair walk, counted by whether it moved a
        batch (every caller's passes: the executor's quantum, the
        mesh's process(), run_to_completion)."""
        moved = self._process_once_inner()
        if moved:
            self._passes_moved += 1
        else:
            self._passes_idle += 1
        return moved

    def _process_once_inner(self) -> bool:
        ops = self.operators
        moved = False
        profile = ops[0].ctx.driver_context.profile
        # walk adjacent pairs, moving at most one batch per pair
        # (Driver.processInternal:371)
        for i in range(len(ops) - 1):
            current, nxt = ops[i], ops[i + 1]
            # a parked pump lookahead means the source is NOT done
            # yet from the pipeline's point of view, whatever its own
            # state machine says — the buffered batch must flow first
            cur_finished = current.is_finished() \
                and not (i == 0 and self._prefetched is not None)
            if current.is_blocked() or nxt.is_blocked():
                if profile:
                    self._note_blocked(current, nxt)
                continue
            if profile:
                self._note_blocked(current, nxt)  # closes open windows
            if nxt.needs_input() and not cur_finished:
                with _Handoff(current, "get_output") as h:
                    if i == 0 and self._prefetched is not None:
                        # a batch the pump prefetched but could not
                        # feed (backed-up stage at a quantum
                        # boundary): it MUST leave the buffer before
                        # the source is pulled again, or batches
                        # would reorder
                        batch = self._prefetched
                        self._prefetched = None
                    else:
                        batch = current.get_output()
                    if profile and batch is not None:
                        # device-inclusive timing: charge this
                        # operator for the async work its output
                        # depends on (profiled runs trade pipeline
                        # overlap for attribution, like the
                        # reference's EXPLAIN ANALYZE overhead)
                        import jax
                        jax.block_until_ready(batch)
                    h.recorded = batch is not None
                if batch is not None:
                    if faults.ARMED:
                        # fault site `operator.add_input`: the ONE
                        # choke point every batch hand-off crosses —
                        # chaos tests fail (or stall) any operator of
                        # any pipeline here without monkeypatching
                        faults.fire("operator.add_input", op=nxt,
                                    name=nxt.ctx.name)
                    with _Handoff(nxt, "add_input"):
                        nxt.add_input(batch)
                    moved = True
            # unwind finished prefix (Driver.java:438-447)
            if cur_finished:
                with _Handoff(nxt, "finish") as h:
                    h.recorded = False
                    nxt.finish()
        # drain the tail operator if it is a sink that self-drives
        return self._drain_tail() or moved

    @staticmethod
    def _note_blocked(current, nxt) -> None:
        """Profiled runs: accumulate wall time an operator spent
        blocking a hand-off (first blocked observation -> first
        subsequent unblocked one, tracked per OperatorContext)."""
        now = time.perf_counter()
        for op in (current, nxt):
            ctx = op.ctx
            if op.is_blocked():
                since = getattr(ctx, "_blocked_since", None)
                if since is None:
                    ctx._blocked_since = now
            else:
                since = getattr(ctx, "_blocked_since", None)
                if since is not None:
                    ctx.stats.blocked_ns += int((now - since) * 1e9)
                    ctx._blocked_since = None

    def run_to_completion(self, max_steps: int = 1_000_000) -> None:
        steps = 0
        while not self.is_finished():
            progress = self.process()
            steps += 1
            if steps > max_steps:
                # a wedged pipeline must be DIAGNOSABLE, not a bare
                # RuntimeError: the structured kind travels the query
                # failure taxonomy and the per-operator snapshot shows
                # WHERE the batches stopped (rows in vs out per stage)
                raise self._stall_error(max_steps)
            if not progress and not self.is_finished():
                blocked = [op.ctx.name for op in self.operators
                           if op.is_blocked()]
                if blocked:
                    # single-driver completion can't unblock cross-driver
                    # dependencies (e.g. a join bridge) — that's the task
                    # executor's job (round-robin over drivers)
                    raise RuntimeError(
                        f"driver deadlock: operators blocked {blocked}")
                # nothing blocked but no progress: let state machines
                # advance (e.g. finish propagation), bounded by max_steps
        self.close()

    def _stall_error(self, max_steps: int):
        """QueryError(kind="driver_stall") carrying the per-operator
        stats snapshot of the wedged pipeline."""
        from presto_tpu.runner.local import QueryError
        from presto_tpu.telemetry import snapshot_drivers
        snap = snapshot_drivers([self])[0]
        chain = " -> ".join(
            f"{s['name']}[{s['input_batches']} in/"
            f"{s['output_batches']} out]" for s in snap)
        err = QueryError(
            f"driver did not converge after {max_steps} steps "
            f"(livelock?): {chain}", kind="driver_stall")
        err.operator_stats = snap
        return err

    def close(self) -> None:
        if not self._closed:
            self._prefetched = None  # drop any in-flight lookahead
            from presto_tpu.telemetry.metrics import METRICS
            if self._pump_splits:
                METRICS.inc("presto_tpu_pump_splits_total",
                            self._pump_splits)
                self._pump_splits = 0
            if self._passes_moved or self._passes_idle:
                # both series, so that a share always has its base
                METRICS.inc("presto_tpu_driver_passes_total",
                            self._passes_moved, moved="yes")
                METRICS.inc("presto_tpu_driver_passes_total",
                            self._passes_idle, moved="no")
                self._passes_moved = self._passes_idle = 0
            now = time.perf_counter()
            for op in self.operators:
                # close any open blocked window: an operator still
                # blocked when the pipeline ends (LIMIT finished
                # upstream of a blocked exchange) must not report 0
                since = getattr(op.ctx, "_blocked_since", None)
                if since is not None:
                    op.ctx.stats.blocked_ns += int((now - since) * 1e9)
                    op.ctx._blocked_since = None
                op.close()
                op.ctx.release_all()
            self._closed = True
