"""Time-sliced multi-driver TaskExecutor (reference:
executor/TaskExecutor.java + executor/MultilevelSplitQueue.java).

Every statement used to drive its own serial round-robin loop on its
own thread: N HTTP clients of the single-node coordinator meant N
unbounded threads each monopolizing the GIL for a whole drive round,
so overload manifested as thread pile-ups and unbounded latency. This
executor inverts that: a FIXED worker pool interleaves every live
query's drivers in bounded time-sliced QUANTA —

  * a driver runs `Driver.process_quantum(quantum_s)` and then yields
    its worker, so a long scan cannot monopolize a slot;
  * quantum boundaries run the shared `check_lifecycle` checkpoint, so
    cancellation and per-query deadlines land MID-query (within one
    quantum), not at the next convenient host round;
  * a driver blocked on input (exchange page, join build) returns a
    "blocked" quantum result and PARKS instead of busy-spinning — its
    worker immediately serves someone else, and any progress by a
    sibling driver of the same task wakes it early;
  * a multilevel feedback queue demotes CPU-hungry tasks: accumulated
    scheduled time walks a task down the level ladder, and dequeue is
    weighted toward the young levels — short dashboard queries cut
    ahead of long scans (reference MultilevelSplitQueue semantics).

The executor is deliberately COOPERATIVE (quanta end at batch
hand-off granularity — a 16s XLA compile inside one hand-off is not
preemptible), and a task's drivers never run concurrently with
themselves: one driver is owned by at most one worker at a time, so
every Operator keeps its single-threaded contract.

Observability: every quantum counts into
`presto_tpu_executor_quanta_total{status}`, level demotions into
`presto_tpu_executor_demotions_total`, and live gauges (running
drivers, per-level queue depth, parked drivers, live tasks) are
sampled by /v1/metrics (telemetry/metrics.render_prometheus).
"""

from __future__ import annotations

import collections
import heapq
import itertools
import os
import threading
import time
import weakref
from typing import Callable, List, Optional

from presto_tpu import sanitize
from presto_tpu.operators.driver import Driver

#: accumulated-scheduled-time thresholds (seconds) at which a task's
#: drivers demote one priority level. The reference ladder is
#: {0, 1, 10, 60, 300}s against minutes-long warehouse queries;
#: rescaled here for an engine whose warm dashboard queries run in
#: hundreds of ms (a query past 30s of scheduled time is this
#: engine's "ETL" tier).
LEVEL_THRESHOLDS_S = (0.0, 0.2, 1.0, 5.0, 30.0)

#: how long a blocked / idle driver parks before being re-polled —
#: the executor analog of the serial drive loop's 2ms no-progress
#: sleep (progress by a sibling driver wakes a parked driver early)
POLL_INTERVAL_S = 0.002

#: default time slice (overridable per statement via the
#: `task_executor_quantum_ms` session property). The reference runs
#: 1s quanta against splits that live for minutes; warm queries here
#: finish whole in tens of ms, so the slice is sized to let a cheap
#: query finish in one-or-two quanta while bounding how long a cold
#: compile-heavy neighbor can hold a worker between checkpoints.
DEFAULT_QUANTUM_MS = 25.0


def _default_workers() -> int:
    env = os.environ.get("PRESTO_TPU_EXECUTOR_WORKERS")
    if env:
        try:
            n = int(env)
            if n > 0:
                return n
        except ValueError:
            pass
    # threads, not processes: the host side is GIL-bound glue, but
    # XLA dispatch/compile release the GIL, so extra workers buy
    # dispatch overlap even on few cores (reference: 2 x cores)
    return min(16, max(4, 2 * (os.cpu_count() or 1)))


class _DriverEntry:
    """One driver's scheduling state. Owned by exactly one worker
    while state == "running" (the executor's single-ownership
    invariant); all transitions happen under the executor lock."""

    __slots__ = ("driver", "task", "state", "level", "scheduled_ns",
                 "idx")

    def __init__(self, driver: Driver, task: "_TaskHandle",
                 idx: int = 0):
        self.driver = driver
        self.task = task
        self.state = "new"      # new|queued|running|parked|done
        self.level = 0
        self.scheduled_ns = 0
        self.idx = idx          # position within the task (fuzz trace)


class _TaskHandle:
    """Per-run_drivers() task: the drivers of ONE query (or fragment
    task), their shared lifecycle hooks, and the thread-local context
    captured from the submitting thread — kernel counters, the
    kernel-shape-bucket gate, the trace recorder — installed around
    every quantum so attribution lands exactly where the serial loop
    put it."""

    def __init__(self, label: str, quantum_s: float, cancel,
                 deadline: Optional[float], abort_check,
                 max_idle_s: float):
        from presto_tpu import batch as _batch
        from presto_tpu.telemetry import kernels as _tk
        from presto_tpu.telemetry import ledger as _ledger
        from presto_tpu.telemetry import trace as _trace
        self.label = label
        self.quantum_s = quantum_s
        self.cancel = cancel
        self.deadline = deadline
        self.abort_check = abort_check
        self.max_idle_s = max_idle_s
        self.entries: List[_DriverEntry] = []
        self.pending = 0        # drivers not yet done
        self.running = 0        # drivers currently owned by a worker
        self.failure: Optional[BaseException] = None
        self.done = threading.Event()
        self.scheduled_ns = 0
        self.last_progress = time.monotonic()
        #: submitting thread's per-query kernel counter dict (quanta
        #: merge their scratch counters into it under _merge_lock)
        self.counters = _tk.query_counters()
        self._merge_lock = sanitize.lock("executor.task_merge")
        self.shape_buckets = _batch.shape_buckets_override()
        self.recorder = _trace.current()
        #: the statement's attribution ledger (telemetry/ledger.py),
        #: re-installed around every quantum like the counters; the
        #: shared object is thread-safe, nesting state is per-thread
        self.ledger = _ledger.current()

    # -- thread-context install around one quantum ---------------------

    def bind(self):
        from presto_tpu import batch as _batch
        from presto_tpu.telemetry import kernels as _tk
        from presto_tpu.telemetry import ledger as _ledger
        from presto_tpu.telemetry import trace as _trace
        # a FRESH scratch counter dict per quantum: two workers of one
        # task must not race bare `+=` on a shared dict — each merges
        # its scratch under the task lock at unbind
        prev_q = _tk.begin_query()
        prev_sb = _batch.set_shape_buckets(self.shape_buckets)
        prev_rec = None
        if self.recorder is not None:
            prev_rec = _trace.activate(self.recorder)
        prev_led = _ledger.install(self.ledger)
        return prev_q, prev_sb, prev_rec, prev_led

    def unbind(self, token) -> None:
        from presto_tpu import batch as _batch
        from presto_tpu.telemetry import kernels as _tk
        from presto_tpu.telemetry import ledger as _ledger
        from presto_tpu.telemetry import trace as _trace
        prev_q, prev_sb, prev_rec, prev_led = token
        scratch = _tk.end_query(prev_q)
        _batch.set_shape_buckets(prev_sb)
        _ledger.uninstall(prev_led)
        if self.recorder is not None:
            _trace.deactivate(prev_rec)
        if self.counters is not None and scratch:
            with self._merge_lock:
                for k, v in scratch.items():
                    self.counters[k] = self.counters.get(k, 0) + v


class TaskExecutor:
    """The worker pool + multilevel feedback queue. One per process
    (get_task_executor); every statement's drive loop submits its
    drivers and blocks on the task's completion."""

    def __init__(self, workers: Optional[int] = None,
                 quantum_ms: float = DEFAULT_QUANTUM_MS,
                 level_thresholds_s=LEVEL_THRESHOLDS_S,
                 poll_interval_s: float = POLL_INTERVAL_S):
        self.workers = int(workers) if workers else _default_workers()
        self.quantum_s = float(quantum_ms) / 1e3
        self.thresholds = tuple(float(t) for t in level_thresholds_s)
        self.n_levels = len(self.thresholds)
        self.poll_interval_s = float(poll_interval_s)
        self._cond = sanitize.condition("executor.pool")
        self._runnable = [collections.deque()
                          for _ in range(self.n_levels)]
        #: scheduled ns accounted per level; dequeue picks the
        #: non-empty level with the smallest level_ns/weight — young
        #: levels hold 2x the share of the level below them, so new
        #: queries always get through but old ones never starve
        self._level_ns = [0] * self.n_levels
        self._level_weight = [1 << (self.n_levels - 1 - i)
                              for i in range(self.n_levels)]
        self._parked: list = []   # heap of (wake_at, seq, entry)
        self._seq = itertools.count()
        self._threads: List[threading.Thread] = []
        self._stop = False
        self._running = 0
        self._tasks = 0
        self._quanta = 0
        self._demotions = 0
        #: tasks with at least one entry not fully drained — what the
        #: single-ownership auditor sweeps (pruned in
        #: _check_task_done_locked once every entry is done)
        self._live: set = set()
        sanitize.track("executor", self)

    # -- submission ----------------------------------------------------

    def run_drivers(self, drivers: List[Driver], cancel=None,
                    deadline: Optional[float] = None,
                    quantum_ms: Optional[float] = None,
                    abort_check: Optional[
                        Callable[[], Optional[BaseException]]] = None,
                    max_idle_s: float = 600.0,
                    label: str = "query") -> None:
        """Schedule `drivers` and block until every one finishes (or
        the first failure, re-raised here once no worker still holds a
        driver of this task). Same contract as the serial loop: the
        caller owns deferred checks and close()."""
        task = _TaskHandle(
            label,
            (float(quantum_ms) / 1e3) if quantum_ms else self.quantum_s,
            cancel, deadline, abort_check, max_idle_s)
        live = [d for d in drivers if not d.is_finished()]
        if not live:
            return
        t0_ns = time.perf_counter_ns()
        with self._cond:
            self._ensure_started_locked()
            self._tasks += 1
            self._live.add(task)
            for d in live:
                e = _DriverEntry(d, task, idx=len(task.entries))
                task.entries.append(e)
                task.pending += 1
            for e in task.entries:
                self._offer_locked(e)
            self._cond.notify_all()
        try:
            task.done.wait()
        finally:
            with self._cond:
                self._tasks -= 1
                scheduled_ns = task.scheduled_ns
            # ledger: the SCHEDULING GAP — wall this task spent
            # runnable-but-unscheduled or parked, i.e. submit wall not
            # covered by any quantum — charges to `driver` (executor
            # overhead), and the quantum-covered remainder is ABSORBED
            # from the submitting thread's enclosing frame: the quanta
            # charge that wall themselves on worker threads, so the
            # outer statement span must not also count the wait as
            # its own self time. Quanta overlapping on a multi-core
            # pool can make scheduled > wall; the gap clamps at 0 and
            # finish()'s parallel normalization owns the overhang.
            from presto_tpu.telemetry import ledger as _ledger
            wait_ns = time.perf_counter_ns() - t0_ns
            gap = max(0, wait_ns - scheduled_ns)
            _ledger.add("driver.quantum", gap)
            _ledger.absorb(wait_ns - gap)
        if task.failure is not None:
            raise task.failure

    # -- worker loop ---------------------------------------------------

    def _ensure_started_locked(self) -> None:
        if self._threads or self._stop:
            return
        for i in range(self.workers):
            # the stop signal must not strongly pin the executor (the
            # leak auditor's owner-collected check relies on the owner
            # actually being collectable)
            t = sanitize.thread(
                target=self._worker_loop,
                name=f"presto-tpu-executor-{i}",
                daemon=True, owner=self,
                stop_signal=lambda ref=weakref.ref(self):
                    ref() is not None and ref()._stop,
                purpose="executor-worker")
            t.start()
            self._threads.append(t)

    def shutdown(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        for t in self._threads:
            t.join(timeout=2)

    def _worker_loop(self) -> None:
        while True:
            with self._cond:
                entry = None
                while entry is None:
                    if self._stop:
                        return
                    now = time.monotonic()
                    self._promote_due_locked(now)
                    entry = self._poll_locked()
                    if entry is None:
                        self._cond.wait(self._next_wait_locked(now))
                entry.state = "running"
                entry.task.running += 1
                self._running += 1
            # _run_quantum owns the release: ownership hand-back and
            # the entry's next-state transition happen in ONE critical
            # section, so the single-ownership auditor never observes
            # a half-released driver (a parked entry still counted as
            # running, or vice versa)
            self._run_quantum(entry)

    def _next_wait_locked(self, now: float) -> float:
        if self._parked:
            return max(0.0005, min(1.0, self._parked[0][0] - now))
        return 1.0

    def _promote_due_locked(self, now: float) -> None:
        while self._parked and self._parked[0][0] <= now:
            _, _, e = heapq.heappop(self._parked)
            if e.state == "parked":   # else woken early / done: stale
                self._offer_locked(e)

    def _offer_locked(self, entry: _DriverEntry) -> None:
        lvl = self._level_of(entry.task.scheduled_ns)
        if lvl > entry.level:
            self._demotions += 1
            from presto_tpu.telemetry.metrics import METRICS
            METRICS.inc("presto_tpu_executor_demotions_total",
                        level=str(lvl))
            from presto_tpu.telemetry import flight as _flight
            if _flight.ENABLED:
                # flight recorder: demotions are exactly the "why was
                # my query deprioritized" post-mortem question
                _flight.record("demotion", lvl, entry.task.label)
        entry.level = lvl
        entry.state = "queued"
        self._runnable[lvl].append(entry)
        self._cond.notify()

    def _level_of(self, scheduled_ns: int) -> int:
        s = scheduled_ns / 1e9
        lvl = 0
        for i, t in enumerate(self.thresholds):
            if s >= t:
                lvl = i
        return lvl

    def _poll_locked(self) -> Optional[_DriverEntry]:
        best = None
        for lvl in range(self.n_levels):
            if not self._runnable[lvl]:
                continue
            score = self._level_ns[lvl] / self._level_weight[lvl]
            if best is None or score < best[0]:
                best = (score, lvl)
        if best is None:
            return None
        lvl = best[1]
        # catch-up (reference: MultilevelSplitQueue's
        # computeLevelMinimum): a level that sat idle must not replay
        # its accrued-time deficit as absolute priority — a freshly
        # demoted ETL entry landing on an empty level 4 would
        # otherwise starve level 0 until tens of seconds of deficit
        # burned off. Raise the chosen level's accrued time to the
        # lowest OTHER non-empty level's share normalized into this
        # level's scale; the weights then govern the split of traffic
        # from now on (young levels 2x per step), not history.
        others = [self._level_ns[i] * self._level_weight[lvl]
                  // self._level_weight[i]
                  for i in range(self.n_levels)
                  if i != lvl and self._runnable[i]]
        if others:
            self._level_ns[lvl] = max(self._level_ns[lvl],
                                      min(others))
        q = self._runnable[lvl]
        fz = sanitize.FUZZ  # snapshot: a concurrent unfuzz must not
        if fz is not None and len(q) > 1:  # None out mid-use
            # schedule fuzz: the level choice (fairness) stays, but
            # WHICH equal-priority entry runs next is seeded-random
            q.rotate(-fz.pick(len(q)))
        return q.popleft()

    def _park_locked(self, entry: _DriverEntry, delay: float) -> None:
        fz = sanitize.FUZZ
        if fz is not None:
            # schedule fuzz: jitter the park deadline so blocked
            # drivers re-poll early/late, racing sibling progress
            delay = fz.park_jitter(delay)
        entry.state = "parked"
        heapq.heappush(self._parked,
                       (time.monotonic() + delay, next(self._seq),
                        entry))
        # wake one waiter so the pool's wait timeout re-derives from
        # the (possibly nearer) new park deadline
        self._cond.notify()

    def _note_progress_locked(self, task: _TaskHandle) -> None:
        task.last_progress = time.monotonic()
        # progress may be exactly what a blocked sibling waits for
        # (join build feeding a parked probe): wake the task's parked
        # drivers now instead of at their poll deadline
        for e in task.entries:
            if e.state == "parked":
                self._offer_locked(e)

    def _finish_entry_locked(self, entry: _DriverEntry) -> None:
        if entry.state != "done":
            entry.state = "done"
            entry.task.pending -= 1
        self._check_task_done_locked(entry.task)

    def _check_task_done_locked(self, task: _TaskHandle) -> None:
        """The task completes when every driver finished — or when it
        failed and no worker still holds one of its drivers (the
        submitter must not tear down operator state a sibling quantum
        is still touching)."""
        if task.pending <= 0 and task.running == 0:
            # fully drained (a failed task's queued entries finish
            # through the fail-fast path): drop it from the audit set
            self._live.discard(task)
        if task.done.is_set():
            return
        if task.pending <= 0 and task.running == 0:
            task.done.set()
        elif task.failure is not None and task.running == 0:
            task.done.set()

    def _release_locked(self, entry: _DriverEntry) -> None:
        """Hand the worker's ownership of `entry` back to the pool
        accounting. Must share a critical section with the entry's
        next-state transition — the single-ownership invariant audit
        relies on 'state == running' and 'counted in task.running'
        flipping atomically."""
        self._running -= 1
        entry.task.running -= 1

    def _run_quantum(self, entry: _DriverEntry) -> None:
        from presto_tpu.telemetry.metrics import METRICS
        task = entry.task
        if task.failure is not None or task.done.is_set():
            # fail-fast drain: a failed task's queued drivers never
            # run another quantum
            with self._cond:
                self._release_locked(entry)
                self._finish_entry_locked(entry)
            return
        err: Optional[BaseException] = None
        status = Driver.IDLE
        progressed = False
        quantum_s = task.quantum_s
        fz = sanitize.FUZZ  # snapshot: survives a concurrent unfuzz
        if fz is not None:
            # schedule fuzz: forced preemption — a seeded shrink of
            # the slice moves every cooperative yield point earlier
            quantum_s *= fz.quantum_scale()
        t0 = time.perf_counter_ns()
        try:
            token = task.bind()
            try:
                # the whole quantum charges to the ledger's
                # `driver.quantum` category by SELF time: kernel/scan/
                # exchange/serde work inside it subtracts via the
                # nesting discipline, and the Driver's own stepping
                # opens a nested `driver.step` frame — what remains
                # here is exactly the executor's quantum bookkeeping
                from presto_tpu.telemetry import ledger as _ledger
                with _ledger.span(
                        "driver.quantum", detail="executor",
                        query_id=getattr(task.ledger, "query_id", "")):
                    from presto_tpu.execution import faults
                    if faults.ARMED:
                        # fault site `executor.quantum`: every
                        # scheduled time slice crosses here — chaos
                        # tests fail any query mid-execution without
                        # monkeypatching
                        faults.fire("executor.quantum",
                                    task=task.label,
                                    level=entry.level)
                    if sanitize.ARMED:
                        # quantum-boundary checkpoint: a violated
                        # executor invariant fails the owning query
                        # cleanly through the task-failure path
                        sanitize.audit_executor(self)
                    from presto_tpu.runner.local import (
                        check_lifecycle,
                    )
                    check_lifecycle(task.cancel, task.deadline)
                    if task.abort_check is not None:
                        exc = task.abort_check()
                        if exc is not None:
                            raise exc
                    status, progressed = \
                        entry.driver.process_quantum(quantum_s)
            finally:
                task.unbind(token)
        except BaseException as e:  # noqa: BLE001 — task-scoped fail
            err = e
        dur = time.perf_counter_ns() - t0
        with self._cond:
            self._release_locked(entry)
            self._quanta += 1
            entry.scheduled_ns += dur
            task.scheduled_ns += dur
            self._level_ns[entry.level] += dur
            if err is not None:
                if task.failure is None:
                    task.failure = err
                self._finish_entry_locked(entry)
                self._cond.notify_all()
                outcome = "failed"
            else:
                if progressed:
                    self._note_progress_locked(task)
                if status == Driver.FINISHED:
                    self._finish_entry_locked(entry)
                    outcome = "finished"
                elif not progressed and self._idle_exceeded(task):
                    from presto_tpu.runner.local import QueryError
                    task.failure = QueryError(
                        f"query made no progress for "
                        f"{task.max_idle_s:.0f}s (deadlock?)")
                    self._finish_entry_locked(entry)
                    self._cond.notify_all()
                    outcome = "stalled"
                elif status == Driver.BLOCKED:
                    self._park_locked(entry, self.poll_interval_s)
                    outcome = "blocked"
                elif status == Driver.PROGRESS:
                    self._offer_locked(entry)
                    outcome = "progress"
                else:  # IDLE: state machines need another pass soon
                    self._park_locked(entry, self.poll_interval_s)
                    outcome = "idle"
            self._check_task_done_locked(task)
            if fz is not None:
                # under the pool lock: the trace order IS the
                # schedule order (the determinism oracle)
                fz.note(task.label, entry.idx, outcome)
        METRICS.inc("presto_tpu_executor_quanta_total", status=outcome)

    @staticmethod
    def _idle_exceeded(task: _TaskHandle) -> bool:
        return (time.monotonic() - task.last_progress) \
            > task.max_idle_s

    # -- observability -------------------------------------------------

    def snapshot(self) -> dict:
        """Live gauges for /v1/metrics (running drivers, per-level
        queue depth, parked drivers, live tasks) plus the monotonic
        quanta/demotion counters."""
        with self._cond:
            return {
                "workers": self.workers,
                "running_drivers": self._running,
                "queued_drivers": [len(q) for q in self._runnable],
                "parked_drivers": sum(
                    1 for _, _, e in self._parked
                    if e.state == "parked"),
                "tasks": self._tasks,
                "quanta": self._quanta,
                "demotions": self._demotions,
                "level_scheduled_ns": list(self._level_ns),
            }


#: THE process-wide executor (like the cache-manager singleton): every
#: runner/coordinator/worker task of this process time-shares one pool
_DEFAULT: Optional[TaskExecutor] = None
_DEFAULT_LOCK = sanitize.lock("executor.singleton")


def get_task_executor(create: bool = True
                      ) -> Optional[TaskExecutor]:
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None and create:
            _DEFAULT = TaskExecutor()
        return _DEFAULT


def set_task_executor(executor: Optional[TaskExecutor]
                      ) -> Optional[TaskExecutor]:
    """Install a custom-configured executor as the process default
    (tests and benches shrink pools / thresholds); returns the
    previous one so callers can restore it."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        prev = _DEFAULT
        _DEFAULT = executor
        return prev


def executor_for_session(properties) -> Optional[TaskExecutor]:
    """The executor a statement's drive loops should use, or None when
    the session opted out (`task_executor_enabled = false` keeps the
    serial round-robin loop)."""
    from presto_tpu.session_properties import get_property
    if not bool(get_property(properties, "task_executor_enabled")):
        return None
    return get_task_executor()
