"""Always-on flight recorder: a fixed-size per-process ring buffer of
lifecycle events, so a query that fails, sheds, stalls, or dies under
load leaves a post-mortem WITHOUT anyone having pre-armed tracing
(reference analog: an aircraft FDR; engineering analog: the kernel's
ftrace ring / Presto's query-level event log, collapsed to one cheap
in-memory ring).

Design contract (the trace.ACTIVE / faults.ARMED gate discipline,
inverted — this one ships ENABLED):

  * recording is a cheap append of a PRE-ENCODED tuple
    ``(t_ns, kind, a, b, c)`` under one leaf lock — no dict churn, no
    string formatting on the hot path. Events are LIFECYCLE-granular
    (per query / per shed / per retry / per membership change / per
    demotion / per compile), never per batch, so "always on" costs
    noise (the serving bench measures and reports the warm-QPS delta;
    budget <= 5%).
  * the ring is fixed-size (``RING_SIZE`` tuples); old events fall
    off. ``snapshot()`` is the only reader and copies under the lock.
  * on query failure/deadline/stall the recent window is snapshotted
    into the error payload (``exc.flight_events`` ->
    the coordinator's FAILED response + ``GET /v1/query/{id}``), and
    the live ring is dumpable on every node via ``GET /v1/flight`` and
    ``tools/query_doctor.py``.

Event kinds (the a/b/c slots are kind-specific, pre-encoded by the
call site):

    query       (state, kind_or_user, sql_head)   lifecycle edges
    span        (edge, name, detail)              traced-span edges
    compile     (kernel, ms, reason)              XLA compiles (reason
                                                  xla_unnamed: a program
                                                  no kernel family named,
                                                  by jax's fun_name)
    shed        (kind, group, "")                 admission sheds
    retry       (tier, target, detail)            transport/task/query
    demotion    (level, label, "")                executor MLFQ
    membership  (state, worker, detail)           heartbeat transitions
    fault       (site, "", "")                    injected faults fired
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from presto_tpu import sanitize

#: master gate: False strips recording to one attribute load + branch
#: per event site (the serving bench's overhead A/B flips this)
ENABLED = True

#: ring capacity in events; at lifecycle granularity this is minutes
#: of history on a busy coordinator, in ~a few hundred KiB
RING_SIZE = 4096

_LOCK = sanitize.lock("telemetry.flight")
_RING: "deque[Tuple[int, str, Any, Any, Any]]" = deque(maxlen=RING_SIZE)
_DROPPED = 0
_SAMPLED_OUT = 0
_TOTAL = 0

#: per-kind sampling lever (the overhead satellite): kind -> keep
#: 1-in-n. Empty by default — every event kept. Operators facing a
#: hot event class (a retry storm flooding `retry`, per-compile
#: events during a cold fleet prewarm) dial it down WITHOUT losing
#: the class entirely; skipped events are counted
#: (presto_tpu_flight_dropped_total{reason="sampled"}) so the ring
#: never silently under-reports
_SAMPLE_EVERY: Dict[str, int] = {}
_SAMPLE_SEEN: Dict[str, int] = {}


def set_sampling(rates: Optional[Dict[str, int]]) -> Dict[str, int]:
    """Install per-kind keep-1-in-n rates (None/{} clears; n <= 1
    entries are dropped — they mean 'keep everything'). Returns the
    previous rates so benches/tests can restore."""
    global _SAMPLE_EVERY
    with _LOCK:
        prev = dict(_SAMPLE_EVERY)
        _SAMPLE_EVERY = {k: int(n) for k, n in (rates or {}).items()
                         if int(n) > 1}
        _SAMPLE_SEEN.clear()
    return prev


def record(kind: str, a: Any = "", b: Any = "", c: Any = "") -> None:
    """Append one pre-encoded event. Callers gate on ``flight.ENABLED``
    themselves only when building a/b/c is not free; the call itself
    re-checks so an un-gated site is still correct."""
    if not ENABLED:
        return
    global _DROPPED, _SAMPLED_OUT, _TOTAL
    ev = (time.perf_counter_ns(), kind, a, b, c)
    dropped = sampled = False
    with _LOCK:
        _TOTAL += 1
        n = _SAMPLE_EVERY.get(kind)
        if n is not None:
            seen = _SAMPLE_SEEN.get(kind, 0)
            _SAMPLE_SEEN[kind] = seen + 1
            if seen % n:
                _SAMPLED_OUT += 1
                sampled = True
        if not sampled:
            if len(_RING) == RING_SIZE:
                _DROPPED += 1
                dropped = True
            _RING.append(ev)
    # counter incs OUTSIDE the ring lock (METRICS has its own) and
    # only on the loss paths — the common keep path pays nothing new
    if dropped or sampled:
        from presto_tpu.telemetry.metrics import METRICS
        METRICS.inc("presto_tpu_flight_dropped_total",
                    reason="sampled" if sampled else "ring_full")


def snapshot(limit: Optional[int] = None
             ) -> List[Tuple[int, str, Any, Any, Any]]:
    """The most recent `limit` events (all, when None), oldest
    first."""
    with _LOCK:
        evs = list(_RING)
    if limit is not None and len(evs) > limit:
        evs = evs[-limit:]
    return evs


def snapshot_dicts(limit: Optional[int] = None) -> List[Dict[str, Any]]:
    """JSON-facing view: the /v1/flight body and the error-payload
    window. Timestamps become ms-before-now so readers need no
    perf_counter epoch."""
    now = time.perf_counter_ns()
    return [{"age_ms": round((now - t) / 1e6, 1), "kind": kind,
             "a": a, "b": b, "c": c}
            for t, kind, a, b, c in snapshot(limit)]


def attach_failure(exc: BaseException, limit: int = 64) -> None:
    """Ride the recent window on a failing query's exception — the
    post-mortem travels with the error to whatever surface reports it
    (coordinator FAILED payload, client, logs)."""
    try:
        exc.flight_events = snapshot_dicts(limit)
    except Exception:  # noqa: BLE001 — slotted exception types etc.
        pass


def stats() -> Dict[str, int]:
    with _LOCK:
        return {"size": len(_RING), "capacity": RING_SIZE,
                "total": _TOTAL, "dropped": _DROPPED,
                "sampled_out": _SAMPLED_OUT,
                "sampling": dict(_SAMPLE_EVERY)}


def reset() -> None:
    """Test hygiene only: empty the ring (sampling rates persist —
    they are configuration, not state)."""
    global _DROPPED, _SAMPLED_OUT, _TOTAL
    with _LOCK:
        _RING.clear()
        _SAMPLE_SEEN.clear()
        _DROPPED = 0
        _SAMPLED_OUT = 0
        _TOTAL = 0
