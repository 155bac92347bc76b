"""XLA compile-vs-execute attribution at the jit-kernel cache
boundary (the telemetry counterpart of the engine's kernel LRUs:
operators/core._FP_KERNEL_CACHE, operators/aggregation's step/finalize
caches, operators/join_ops._PROBE_KERNEL_CACHE).

jax compiles lazily — a jitted callable traces+compiles on its first
call per input signature, and that call BLOCKS the host for the whole
compile while ordinary calls return after the (async) dispatch. So the
split falls out of two cheap observations per call:

  * did the jit executable cache grow? (``PjitFunction._cache_size``)
    -> this call paid a compile; its wall time is COMPILE ns
  * otherwise -> the wall time is dispatch/EXECUTE ns

which is exactly "cache-miss trace = compile, hit = execute only" at
the engine's own kernel-cache boundary: a kernel served from the LRU
has a warm jit cache, so its calls are pure execute.

Attribution targets, all optional per call:
  * the CURRENT OPERATOR's OperatorStats (set by the Driver loop
    around add_input/get_output — operators/driver.py), feeding
    EXPLAIN ANALYZE and the stats tree
  * the CURRENT QUERY's counter dict (set by the runner around one
    statement), feeding system.runtime.queries.compile_ms
  * the process-wide Prometheus counters (/v1/metrics)

``ENABLED`` is the zero-overhead gate (the faults.ARMED pattern): when
False the instrumented wrapper is a single branch + tail call.

The same boundary names the DEVICE's time (docs/OBSERVABILITY.md, "The
device timeline"): `jit` below is the one place a device program is
jitted and named after its kernel family, so a device trace groups by
family; the wrapper's call is a `kernel:<family>` host span on
jax.profiler's clock; and one jax.monitoring listener counts every XLA
compile by family, the eager jnp ops no wrapper sees included.

Concurrency: compile detection is a heuristic over SHARED jit caches,
hardened for the two-cold-queries race. Every in-flight call registers
in the wrapper's active set under the state lock; the call that
ACCOUNTS a cache-size growth marks every other in-flight call of the
same wrapper, and a marked call classifies its wall as compile even
when its own before/after samples straddle no growth (the
misattribution this closes: caller B compiles, the cache grows, caller
A — blocked on jax's compile lock the whole time — samples `before`
AFTER the growth and used to book its compile-blocked wall as
execute). The residual imprecision is in the SAFE direction: a
concurrent call that overlapped a compile window without blocking
books compile ns it didn't strictly pay — time adjacent to a compile
is compile cost for attribution purposes, and warm (steady-state)
phases never compile, so their execute numbers are untouched. Per-call
exactness would need a per-call compile signal jax does not expose."""

from __future__ import annotations

import functools
import re
import threading
import time
import weakref
from typing import Dict, Optional

import jax
from jax.profiler import TraceAnnotation

from presto_tpu import sanitize
from presto_tpu.telemetry.metrics import METRICS
from presto_tpu.telemetry import flight as _flight
from presto_tpu.telemetry import ledger as _ledger
from presto_tpu.telemetry import trace as _trace

#: device name -> kernel family, filled by `jit` below: the one map
#: from what a device trace carries (the XLA module name) back to the
#: KernelContract family the counters are labelled with
_DEVICE_NAMES: Dict[str, str] = {}

#: the forms a device name arrives in: `jit_<name>(<fingerprint>)` on
#: the profiler's "XLA Modules" line, `jit_<name>` once reduced,
#: `PjitFunction(<name>)` on a host thread, `jit(<name>)` in
#: jax.monitoring's `fun_name`
_MODULE_FORMS = re.compile(
    r"^(?:PjitFunction\((?P<host>.*)\)|jit\((?P<event>.*)\)"
    r"|jit_(?P<module>.*?)(?:\(\d+\))?)$")


def jit(fn, family: str, part: Optional[str] = None, **jit_kwargs):
    """THE place a device program gets its name: `jax.jit` of `fn`
    under the device name `<family>` or `<family>_<part>` (one family,
    several programs). The name becomes the XLA module `jit_<name>` in
    a device trace and `PjitFunction(<name>)` on the host's lane, so
    device time groups by kernel family with no lookup table kept
    beside the code. `fn` itself keeps its Python name (impl bodies
    compose into other traces under theirs). Lint rule TS007 refuses a
    `jax.jit` that bypasses this function."""
    name = family if part is None else f"{family}_{part}"
    known = _DEVICE_NAMES.setdefault(name, family)
    if known != family:
        raise ValueError(f"device name {name!r} already belongs to "
                         f"family {known!r}, not {family!r}")

    @functools.wraps(fn)
    def named(*args, **kwargs):
        return fn(*args, **kwargs)
    named.__name__ = named.__qualname__ = name
    return jax.jit(named, **jit_kwargs)


def device_names() -> Dict[str, str]:
    """device name -> family, as registered so far."""
    return dict(_DEVICE_NAMES)


def device_name_of(module: str) -> str:
    """The device name inside any of the forms a trace or a compile
    event carries it in (`module` itself when it is none of them)."""
    m = _MODULE_FORMS.match(module)
    if m is None:
        return module
    return next(g for g in m.groups() if g is not None)


def family_of_module(module: str) -> Optional[str]:
    """`jit_join_build_sorted(123)` -> `join_build`; None for a
    program no kernel family named (an eager jnp op)."""
    return _DEVICE_NAMES.get(device_name_of(module))


#: master gate for kernel timing. On by default: the per-call cost is
#: two clock reads + a cache-size poll (~hundreds of ns) under batch-
#: granular dispatches (~tens of us). Set False to strip even that.
ENABLED = True

_TL = threading.local()

#: live instrumented wrappers, for reset_retrace_state (weak: kernels
#: evicted from the engine LRUs must stay collectable)
_WRAPPERS: "weakref.WeakSet" = weakref.WeakSet()

#: armed-only input-signature tracking (the runtime half of the
#: kernel contract checker's retrace prediction, tools/kernelcheck):
#: when on, every kernel call records its family's distinct input
#: signatures (pytree structure + leaf shapes/dtypes + static
#: values). The kernel contracts guarantee one compile per signature,
#: so len(signatures) is the PREDICTED compile count — compared
#: against the live kernel_retrace_total deltas by
#: analysis/runtime.cross_check; live > predicted is a violation
#: (an undeclared retrace source: value-baking, dtype drift). Off by
#: default: the per-call tree_flatten is not free.
SIGNATURE_TRACKING = False
_SIGNATURES: Dict[str, set] = {}
_SIG_LOCK = sanitize.lock("telemetry.kernel_signatures")


def arm_signature_tracking(on: bool = True) -> None:
    """Toggle signature tracking (clears collected signatures)."""
    global SIGNATURE_TRACKING
    with _SIG_LOCK:
        _SIGNATURES.clear()
    SIGNATURE_TRACKING = bool(on)


def signature_report() -> Dict[str, int]:
    """family -> distinct input signatures observed since arming
    (the predicted compile count under the kernel contracts)."""
    with _SIG_LOCK:
        return {k: len(v) for k, v in sorted(_SIGNATURES.items())}


def _record_signature(name: str, args, kwargs) -> None:
    try:
        import jax
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
        parts = [str(treedef)]
        for leaf in leaves:
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is not None and dtype is not None:
                parts.append(f"{dtype}{tuple(shape)}")
            else:
                # non-array leaves are static-ish values (capacities,
                # verify modes); their VALUES key compiles. Python
                # scalars that ride as traced operands (LIMIT n) make
                # the prediction conservative (predicted >= live),
                # which the cross-check's direction tolerates.
                parts.append(repr(leaf)[:80])
        sig = "|".join(parts)
    except Exception:  # noqa: BLE001 — tracking is advisory
        return
    with _SIG_LOCK:
        _SIGNATURES.setdefault(name, set()).add(sig)


def reset_retrace_state() -> None:
    """Forget which kernels have traced: after a kernel-cache wipe
    (execution/compile_cache.clear_kernel_caches — the restart
    simulation) the next compile of each kernel IS a first trace
    again, and must classify as reason="new_kernel", not "shape"."""
    for w in list(_WRAPPERS):
        st = w._retrace_state
        with st["lock"]:
            st["traced"] = False
            st["accounted"] = 0


def set_current_op(stats) -> None:
    """Bind the operator whose add_input/get_output is running on this
    thread (Driver loop); kernel calls credit compile/execute ns to
    it. Pass None to clear."""
    _TL.op = stats


def begin_query() -> Dict[str, int]:
    """Install a fresh per-query kernel counter dict on this thread
    and return it (the runner stows it in the query's history entry).
    Returns the PREVIOUS dict via end_query's argument contract."""
    prev = getattr(_TL, "query", None)
    counters = {"compile_ns": 0, "execute_ns": 0, "compiles": 0,
                "kernel_calls": 0, "expr_compile_ns": 0}
    _TL.query = counters
    return prev


def end_query(prev=None) -> Optional[Dict[str, int]]:
    out = getattr(_TL, "query", None)
    _TL.query = prev
    return out


def query_counters() -> Optional[Dict[str, int]]:
    return getattr(_TL, "query", None)


def _cache_sizes(jits) -> int:
    total = 0
    for j in jits:
        try:
            total += j._cache_size()
        except Exception:  # noqa: BLE001 — introspection is optional
            return -1
    return total


def record(name: str, dur_ns: int, compiled: bool,
           reason: Optional[str] = None) -> None:
    """Credit one kernel call to the current operator, the current
    query, and the process counters. `reason` classifies a compile for
    the retrace counter: "new_kernel" (this kernel object's FIRST
    trace — a genuinely new program) vs "shape" (an already-traced
    kernel re-traced for a new input signature: the bucketing gap the
    kernel_shape_buckets property exists to close)."""
    op = getattr(_TL, "op", None)
    if op is not None:
        if compiled:
            op.compile_ns += dur_ns
        else:
            op.execute_ns += dur_ns
    # attribution ledger: compile wall vs async DISPATCH wall (device
    # completion is measured at drain points as device_wait —
    # telemetry/ledger.py); flight recorder keeps compile edges
    _ledger.add_kernel(dur_ns, compiled)
    if compiled and _flight.ENABLED:
        _flight.record("compile", name, round(dur_ns / 1e6, 1),
                       reason or "")
    q = getattr(_TL, "query", None)
    if q is not None:
        q["kernel_calls"] += 1
        if compiled:
            q["compiles"] += 1
            q["compile_ns"] += dur_ns
        else:
            q["execute_ns"] += dur_ns
    METRICS.inc("presto_tpu_kernel_calls_total", kernel=name)
    if compiled:
        METRICS.inc("presto_tpu_kernel_compiles_total", kernel=name)
        METRICS.inc("presto_tpu_kernel_compile_ns_total", dur_ns,
                    kernel=name)
        # reason None = this growth event was already booked by a
        # concurrent racer (see instrument_kernel): the compile TIME
        # still counts (blocking on jax's compile lock is compile
        # cost) but the retrace counter charges each trace once
        if reason is not None:
            METRICS.inc("presto_tpu_kernel_retrace_total",
                        kernel=name, reason=reason)
    else:
        METRICS.inc("presto_tpu_kernel_execute_ns_total", dur_ns,
                    kernel=name)


_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def _on_jax_duration(event: str, duration_secs: float,
                     fun_name: str = "", **_) -> None:
    """The complete compile count: every program XLA builds (or loads
    from the persistent cache) in this process, whether or not a
    kernel family's wrapper was around it. The cache-size poll above
    sees instrumented kernels only; an eager jnp op on the driver
    path is its own device program and lands here as `(unnamed)`,
    with its `fun_name` in the flight ring."""
    if event != _BACKEND_COMPILE:
        return
    family = family_of_module(fun_name)
    if family is None:
        family = "(unnamed)"
        _flight.record("compile", fun_name,
                       round(duration_secs * 1e3, 1), "xla_unnamed")
    METRICS.inc("presto_tpu_xla_compiles_total", family=family)
    METRICS.inc("presto_tpu_xla_compile_seconds_total", duration_secs,
                family=family)


# jax calls it on a compile and never on a warm dispatch
jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def record_expr_compile(dur_ns: int) -> None:
    """Host-side expression-closure building time (expr/compile.py) —
    the non-XLA share of plan->kernel cost."""
    q = getattr(_TL, "query", None)
    if q is not None:
        q["expr_compile_ns"] += dur_ns
    METRICS.inc("presto_tpu_expr_compile_ns_total", dur_ns)


def instrument_kernel(kernel, name: str, jits=None):
    """Wrap `kernel` so every call is timed and classified compile vs
    execute. `jits` lists the jitted callables whose executable caches
    to poll (default: `kernel` itself when it is a jit; a host-side
    wrapper around several jits passes them explicitly). The wrapper
    is what the engine's kernel LRUs should store — the jit cache
    state travels with it, so an LRU hit keeps reporting execute-only.
    """
    if jits is None:
        jits = [kernel] if hasattr(kernel, "_cache_size") else []
    jits = [j for j in jits if hasattr(j, "_cache_size")]
    # retrace classification state: once this kernel object has
    # compiled, any LATER compile is a re-trace for a new input
    # signature ("shape") — the thing shape bucketing eliminates.
    # `accounted` is the largest jit-cache size whose growth the
    # retrace counter has already charged: two threads racing ONE
    # first trace both observe the cache grow, but only the first to
    # take the lock books it — the loser passes reason=None (compile
    # time still recorded, no phantom "shape" retrace).
    # `active` holds every in-flight call (token -> overlapped-a-
    # compile flag): the accounting call marks the others, so a call
    # whose `before` sample landed AFTER a concurrent compile's cache
    # growth still classifies its (compile-lock-blocked) wall as
    # compile — see the module docstring's concurrency contract
    state = {"traced": False, "accounted": 0,
             "lock": sanitize.lock("telemetry.kernel_state"),
             "active": {}}
    # the call's span on jax.profiler's timeline: a kernel object's
    # first call always compiles; a later retrace for a new shape
    # cannot be told before the call and stays a `kernel:` span, with
    # jax's own compile events inside it
    warm_span, cold_span = f"kernel:{name}", f"compile:{name}"

    def wrapped(*args, **kwargs):
        if not ENABLED:
            return kernel(*args, **kwargs)
        if SIGNATURE_TRACKING:
            _record_signature(name, args, kwargs)
        tok = object()
        with state["lock"]:
            state["active"][tok] = False
        before = _cache_sizes(jits)
        t0 = time.perf_counter_ns()
        try:
            with TraceAnnotation(
                    warm_span if state["traced"] else cold_span):
                out = kernel(*args, **kwargs)
        except BaseException:
            with state["lock"]:
                state["active"].pop(tok, None)
            raise
        dur = time.perf_counter_ns() - t0
        after = _cache_sizes(jits)
        reason = None
        with state["lock"]:
            overlapped = state["active"].pop(tok, False)
            compiled = (before >= 0 and after > before) or overlapped
            if compiled and after > state["accounted"]:
                reason = "shape" if state["traced"] else "new_kernel"
                state["traced"] = True
                state["accounted"] = after
                for k in state["active"]:
                    state["active"][k] = True
        record(name, dur, compiled, reason)
        if _trace.ACTIVE:
            rec = _trace.current()
            if rec is not None:
                rec.add(f"kernel:{name}",
                        "compile" if compiled else "execute",
                        t0, dur)
        return out

    wrapped.__wrapped__ = kernel
    wrapped._kernel_name = name
    wrapped._retrace_state = state
    _WRAPPERS.add(wrapped)
    return wrapped
