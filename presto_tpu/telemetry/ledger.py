"""Wall-clock attribution ledger: a per-query, NON-OVERLAPPING
decomposition of wall time into named categories (reference analog:
the CPU/scheduled/blocked wall split of Presto's QueryStats, extended
with the TPU engine's own cost taxonomy — scan datagen, h2d/d2h,
XLA compile, async kernel dispatch vs device wait, serde, exchange
transport, spool I/O, retry backoff).

Why it exists: the engine's headline perf numbers kept being INFERRED
by subtraction ("2.18s wall vs 360ms attributed kernel time, so ~85%
is host glue") because kernel attribution only covered the kernel-
cache boundary. This ledger makes every millisecond attributable, with
a machine-checked coverage invariant:

    wall == Σ categories + unattributed        (exactly, by
                                                construction — see
                                                :meth:`QueryLedger.finish`)

and the residual ``unattributed`` surfaced per query (EXPLAIN ANALYZE,
``system.runtime.queries.unattributed_ms``, the
``presto_tpu_ledger_unattributed_ratio`` Prometheus histogram) so a
regression in COVERAGE is itself observable.

Mechanics — self-time accounting with per-thread nesting:

  * One :class:`QueryLedger` per statement, installed on the executing
    thread (and re-installed on every executor worker quantum via
    ``_TaskHandle.bind``, like the kernel counters), so any layer the
    query passes through can charge time without parameter threading.
  * :func:`span` frames keep a per-thread stack; a frame charges its
    SELF time (elapsed minus time charged to nested frames/leaves on
    the same thread), so categories can never double-count within a
    thread. Leaf charges (:func:`add`) subtract from the enclosing
    frame the same way.
  * Worker-thread time (executor quanta) charges into the shared
    ledger under its small lock; the submitting thread deliberately
    does NOT span its own ``task.done.wait`` (the quanta cover that
    wall), and the executor charges the scheduling GAP — wall not
    covered by any quantum — to ``driver`` (executor overhead).

  * A frame may carry a DETAIL (``span(category, detail)``): its self
    time is charged to `category` exactly as a plain frame's, and
    also kept per (category, detail) on the ledger (``details_ms`` in
    the document, ``presto_tpu_ledger_detail_ns_total`` once a
    statement). A detail names WHO inside the category spent the
    time (the operator method of a hand-off, which of the three
    ``driver.quantum`` frames, the phase of an exchange wave); it
    never moves time between categories, so the invariant below is
    untouched. What stays on the bare category is the self time of
    its plain frames and its leaf charges.

Zero overhead when no ledger is installed: every site is a thread-
local load + branch (the ``faults.ARMED`` discipline, per-thread).

Category taxonomy (docs/OBSERVABILITY.md):

    queued        admission-queue wait (resource groups / coordinator)
    planning      parse + analyze + optimize + local planning + plan-
                  cache lookups (host-side expr compile included)
    scan          connector page-source next(): datagen, file decode
    h2d           host->device placement (device_put)
    d2d           chip->chip placement: a scan or exchange-source
                  batch copied to its task's device from another
                  chip of the mesh (parallel/mesh.place; a batch
                  already there charges nothing)
    compile       kernel calls that paid an XLA trace+compile
    dispatch      host wall issuing already-compiled kernels (async
                  dispatch — the device may still be working when the
                  call returns)
    device_wait   host blocked on device results at drain points
                  (block_until_ready / deferred-flag fetch) — the
                  dispatch-then-wait slack that used to hide in
                  "execute"
    d2h           device->host transfers (device_get)
    serde         batch <-> bytes encode/decode for the exchange wire
    exchange      exchange transport (HTTP push wall, net of serde
                  and backoff nested inside it)
    exchange.all_to_all
                  mesh shuffle waves: assembling the sharded global
                  arrays, dispatching the shard_map all_to_all
                  program, and the one per-wave host sync on the
                  received-row counts (parallel/shuffle.py — the ICI
                  tier of the exchange, kept apart from the DCN
                  `exchange` HTTP wall; docs/SHARDING.md). Details
                  `assemble`, `dispatch`, `sync`, `slice`: a wave's
                  four phases (the last cuts each consumer's batch
                  out of the outputs); the bare category keeps the
                  pre-wave compaction and the wave program's own
                  call (a leaf charge)
    spool         spool I/O: task-output spool put/read-back, lifespan
                  spool disk pages
    retry_backoff transport-retry backoff sleeps
    prefetch      the batch pump's lookahead frames: pulling split
                  N+1's scan + h2d while split N's kernel runs on the
                  device (operators/driver.py; nested scan/h2d spans
                  subtract, so this is the overlap machinery's own
                  self time). Detail `<source kind>.get_output`
    driver.step   per-operator stepping (host Python moving batches).
                  Detail `<operator kind>.<method>`: one hand-off's
                  self time, the operator's own Python net of
                  kernels, scans, transfers and drains; the bare
                  category is the loops' own self time (polling
                  is_blocked / needs_input / is_finished, deadlines)
    driver.reassembly
                  batch/result reassembly: stats snapshotting, history
                  recording, coordinator-side row materialization
    driver.quantum
                  executor quantum bookkeeping + scheduling gaps +
                  statement-level drive framing (the catch-all that
                  keeps the invariant honest). Details `statement`
                  (the root frame of a statement or coordinator
                  attempt), `executor` (a worker's quantum
                  bookkeeping), `mesh_round` (the mesh drive loop's
                  round: lifecycle checks, deferred spawns, lifespan
                  advances); the bare category is the executor's
                  scheduling gap (a leaf charge)

The legacy monolithic ``driver`` category was split into the three
``driver.*`` sub-categories above (PR 16) so a drive-loop regression is
attributable per cause; pre-split documents (and ad-hoc charges) still
render and still count toward the coverage invariant —
:meth:`QueryLedger.finish` carries any charged category, listed or not.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Any, Dict, Optional, Tuple

from jax.profiler import TraceAnnotation

from presto_tpu import sanitize

#: the full category set, in rendering order
CATEGORIES: Tuple[str, ...] = (
    "queued", "planning", "scan", "h2d", "d2d", "compile", "dispatch",
    "device_wait", "d2h", "serde", "exchange", "exchange.all_to_all",
    "spool", "retry_backoff", "prefetch", "driver.step",
    "driver.reassembly", "driver.quantum",
)

#: the drive-loop sub-categories (docs/OBSERVABILITY.md): their sum is
#: the comparable figure for the pre-split monolithic `driver` number
DRIVER_CATEGORIES: Tuple[str, ...] = (
    "driver.step", "driver.reassembly", "driver.quantum",
)

_TL = threading.local()
_IDS = itertools.count(1)


class QueryLedger:
    """Per-query category accumulator (ns). Thread-safe: executor
    worker threads and the submitting thread charge concurrently."""

    __slots__ = ("_lock", "query_id", "ns", "detail_ns", "device_ns",
                 "finished")

    def __init__(self, query_id: str = ""):
        self._lock = sanitize.lock("telemetry.ledger")
        #: the request identifier the statement's frames carry on the
        #: profiler's timeline (the root frame and every executor
        #: quantum): the server's query id where one reached the
        #: runner, else a process-unique one
        self.query_id = query_id or f"ledger-{next(_IDS)}"
        self.ns: Dict[str, int] = {c: 0 for c in CATEGORIES}
        #: (category, detail) -> ns: the self time of the detailed
        #: frames, a part of `ns[category]` and never beside it
        self.detail_ns: Dict[Tuple[str, str], int] = {}
        #: device index -> {category -> ns}: the shard-aware second
        #: axis (mesh drives wrap each task's quantum in device_scope,
        #: so kernel/driver charges land on the device doing the work)
        self.device_ns: Dict[int, Dict[str, int]] = {}
        self.finished: Optional[Dict[str, Any]] = None

    def charge(self, category: str, dur_ns: int,
               device: Optional[int] = None,
               detail: Optional[str] = None) -> None:
        if dur_ns <= 0:
            return
        with self._lock:
            self.ns[category] = self.ns.get(category, 0) + dur_ns
            if detail is not None:
                key = (category, detail)
                self.detail_ns[key] = \
                    self.detail_ns.get(key, 0) + dur_ns
            if device is not None:
                per = self.device_ns.setdefault(device, {})
                per[category] = per.get(category, 0) + dur_ns

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self.ns)

    def attributed_ns(self) -> int:
        with self._lock:
            return sum(self.ns.values())

    def finish(self, wall_ns: int) -> Dict[str, Any]:
        """Close the ledger against the query's measured wall and
        return the attribution document. The coverage invariant holds
        by construction: ``wall_ms == Σ categories_ms +
        unattributed_ms`` exactly (unattributed is the residual).

        Parallel overlap: a query whose drivers run thread-time on
        several executor workers AT ONCE (or whose concurrent kernel
        calls both book a shared compile window — telemetry/kernels'
        deliberate blocked-on-compile-lock accounting) can accumulate
        MORE thread-time than wall. Per-category proportions are still
        exact, so the document normalizes them onto the wall
        (``parallel_scale`` < 1 records the factor and the raw sum),
        keeping the invariant true instead of serving a negative
        residual."""
        with self._lock:
            snap = dict(self.ns)
            details = dict(self.detail_ns)
            dev_snap = {d: dict(per)
                        for d, per in self.device_ns.items()}
        attributed = sum(snap.values())
        scale = None
        if attributed > wall_ns > 0:
            scale = wall_ns / attributed
            snap = {c: int(v * scale) for c, v in snap.items()}
            attributed = sum(snap.values())
            details = {k: int(v * scale) for k, v in details.items()}
            dev_snap = {d: {c: int(v * scale) for c, v in per.items()}
                        for d, per in dev_snap.items()}
        unattributed = wall_ns - attributed
        # every charged category travels, listed or not: an ad-hoc key
        # (a legacy `driver` charge, a future category) counted toward
        # `attributed`, so dropping it here would break the invariant
        order = list(CATEGORIES) \
            + sorted(k for k in snap if k not in CATEGORIES)
        doc: Dict[str, Any] = {
            "wall_ms": round(wall_ns / 1e6, 3),
            "categories_ms": {
                c: round(snap.get(c, 0) / 1e6, 3)
                for c in order if snap.get(c, 0) > 0},
            "unattributed_ms": round(unattributed / 1e6, 3),
            "unattributed_frac": round(unattributed / wall_ns, 4)
            if wall_ns > 0 else 0.0,
        }
        if scale is not None:
            doc["parallel_scale"] = round(scale, 4)
        if details:
            # who inside a category: a PART of categories_ms[c] (the
            # detailed frames' self time), normalized with it
            per_cat: Dict[str, Dict[str, float]] = {}
            for (c, d), v in sorted(details.items()):
                per_cat.setdefault(c, {})[d] = round(v / 1e6, 3)
            doc["details_ms"] = per_cat
        if dev_snap:
            # the shard-aware breakdown: same categories, one column
            # per mesh device that charged anything (normalized by the
            # same parallel_scale, so per-device proportions stay
            # comparable to the wall-true top-level figures)
            doc["per_device"] = {
                str(d): {
                    c: round(per.get(c, 0) / 1e6, 3)
                    for c in order if per.get(c, 0) > 0}
                for d, per in sorted(dev_snap.items())}
        self.finished = doc
        return doc


def verify_coverage(doc: Dict[str, Any],
                    tolerance_ms: float = 0.01) -> None:
    """THE machine check of the coverage invariant over a finished
    attribution document: Σ categories + unattributed must equal wall
    (rounding tolerance only). Raises AssertionError naming the
    drift."""
    total = sum(doc.get("categories_ms", {}).values()) \
        + doc.get("unattributed_ms", 0.0)
    drift = abs(total - doc.get("wall_ms", 0.0))
    # per-category rounding can stack: one tolerance per category
    budget = tolerance_ms * (len(doc.get("categories_ms", {})) + 2)
    assert drift <= budget, (
        f"ledger coverage invariant violated: categories+unattributed "
        f"= {total:.3f}ms vs wall {doc.get('wall_ms')}ms "
        f"(drift {drift:.3f}ms)")


def publish(doc: Dict[str, Any]) -> None:
    """Add a finished attribution document to the process counters:
    every category to presto_tpu_ledger_ns_total, every detail to
    presto_tpu_ledger_detail_ns_total, the residual to its counter
    and its ratio histogram. Once a statement (or coordinator
    attempt), where its ledger closes: no frame touches METRICS."""
    from presto_tpu.telemetry.metrics import METRICS
    for c, ms in doc["categories_ms"].items():
        METRICS.inc("presto_tpu_ledger_ns_total", ms * 1e6, category=c)
    for c, per in doc.get("details_ms", {}).items():
        for d, ms in per.items():
            METRICS.inc("presto_tpu_ledger_detail_ns_total", ms * 1e6,
                        category=c, detail=d)
    METRICS.inc("presto_tpu_ledger_unattributed_ns_total",
                max(0.0, doc["unattributed_ms"]) * 1e6)
    METRICS.observe("presto_tpu_ledger_unattributed_ratio",
                    max(0.0, doc["unattributed_frac"]))


# ---------------------------------------------------------------------------
# thread-local install + nesting


def install(ledger: Optional[QueryLedger]):
    """Make `ledger` THIS thread's current ledger with a fresh nesting
    stack; returns the previous (ledger, stack) token for uninstall.
    Executor quanta install the task's shared ledger per quantum (the
    kernel-counter pattern)."""
    prev = (getattr(_TL, "ledger", None), getattr(_TL, "stack", None))
    _TL.ledger = ledger
    _TL.stack = [] if ledger is not None else None
    return prev


def uninstall(token) -> None:
    _TL.ledger, _TL.stack = token


def current() -> Optional[QueryLedger]:
    return getattr(_TL, "ledger", None)


class _Frame:
    """One open span of a thread that has a ledger: `start_ns`, the
    time nested frames and leaf charges took (`nested_ns`), and, once
    closed, `elapsed_ns` (the hand-off reads the operator's busy time
    off it instead of a clock pair of its own)."""

    __slots__ = ("led", "category", "detail", "start_ns", "nested_ns",
                 "elapsed_ns", "_event")

    def __init__(self, led, category, detail, meta):
        self.led = led
        self.category = category
        self.detail = detail
        self.nested_ns = 0
        self.elapsed_ns = 0
        name = f"ledger:{category}" if detail is None \
            else f"ledger:{category}/{detail}"
        self._event = TraceAnnotation(name, **meta)

    def __enter__(self):
        _TL.stack.append(self)
        self.start_ns = time.perf_counter_ns()
        self._event.__enter__()
        return self

    def __exit__(self, *exc):
        self._event.__exit__(*exc)
        stack = _TL.stack
        stack.pop()
        dur = self.elapsed_ns = time.perf_counter_ns() - self.start_ns
        self.led.charge(self.category, dur - self.nested_ns,
                        device=getattr(_TL, "device", None),
                        detail=self.detail)
        if stack:
            stack[-1].nested_ns += dur
        return False


_NO_FRAME = contextlib.nullcontext()


def span(category: str, detail: Optional[str] = None, **meta):
    """Charge this frame's SELF time (elapsed minus nested charges on
    this thread) to `category`, and with a `detail` also to the
    ledger's (category, detail) table: the same nanoseconds, named
    finer. A no-op (zero clock reads; `with` binds None) when the
    thread has no current ledger. With one, the frame is also a host
    event on jax.profiler's timeline, the clock the device trace
    shares: `ledger:<category>`, or `ledger:<category>/<detail>`, with
    `meta` as the event's metadata (about 0.5 us a frame, attached or
    not: docs/OBSERVABILITY.md, "The device timeline")."""
    led = getattr(_TL, "ledger", None)
    if led is None:
        return _NO_FRAME
    return _Frame(led, category, detail, meta)


@contextlib.contextmanager
def device_scope(device: Optional[int]):
    """Attribute charges made on this thread inside the scope to mesh
    device `device` (the ledger's second axis — see
    QueryLedger.device_ns). The mesh drive loop wraps each task's
    driver quantum so kernel dispatch/compile and driver self-time
    land on the device doing the work; `None` runs the scope
    unattributed (single-task fragments, collective waves that belong
    to the whole mesh)."""
    prev = getattr(_TL, "device", None)
    _TL.device = device
    try:
        yield
    finally:
        _TL.device = prev


def add(category: str, dur_ns: int) -> None:
    """Leaf charge of externally-measured time (e.g. a kernel call's
    wall from telemetry.kernels): counts toward `category` and
    subtracts from the enclosing span frame on this thread so the
    frame's self time cannot double-count it."""
    led = getattr(_TL, "ledger", None)
    if led is None:
        return
    led.charge(category, dur_ns, device=getattr(_TL, "device", None))
    stack = _TL.stack
    if stack:
        stack[-1].nested_ns += dur_ns


def absorb(dur_ns: int) -> None:
    """Mark `dur_ns` of the enclosing span frame as EXTERNALLY
    accounted without charging any category on this thread — the
    executor's run_drivers wait uses this: the waited wall is
    represented by the quanta charging on worker threads, so the
    submitting thread's enclosing frame must not count it as its own
    self time (that would double-book the same wall)."""
    if dur_ns <= 0:
        return
    stack = getattr(_TL, "stack", None)
    if stack:
        stack[-1].nested_ns += dur_ns


@contextlib.contextmanager
def kernel_scope(category: str):
    """Attribute warm kernel DISPATCH wall inside the scope to
    `category` instead of the generic \"dispatch\" bucket — the
    exchange wave uses this so the collective all_to_all program's
    steady-state wall is visible as its own line rather than blending
    into every other kernel's dispatch. Compile wall stays under
    \"compile\": one-time tracing cost is not the collective's
    steady-state."""
    prev = getattr(_TL, "kernel_category", None)
    _TL.kernel_category = category
    try:
        yield
    finally:
        _TL.kernel_category = prev


def add_kernel(dur_ns: int, compiled: bool) -> None:
    """The telemetry.kernels hook: a compiling call is COMPILE wall, a
    warm call is host DISPATCH wall (async — device-side completion is
    measured separately as device_wait at drain points; see the
    async-dispatch undercount note in docs/OBSERVABILITY.md). Warm
    dispatch honors any enclosing `kernel_scope` redirect."""
    if compiled:
        add("compile", dur_ns)
    else:
        add(getattr(_TL, "kernel_category", None) or "dispatch",
            dur_ns)
