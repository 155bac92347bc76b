"""Compile-wall management: persistent XLA compilation cache + AOT
kernel prewarm (the reproduction's answer to the reference's
per-query bytecode generation cost, presto-bytecode + sql/gen —
except XLA compiles are ~seconds, so they MUST amortize across
queries, splits, AND process restarts).

Three layers, from cheapest to deepest:

1. **Engine kernel LRUs** (operators/core._FP_KERNEL_CACHE, the agg
   step/finalize caches, operators/join_ops._PROBE_KERNEL_CACHE):
   per-process, keyed on expression fingerprints. A hit skips even
   the jax trace. Shape bucketing (batch.pad_for_kernel) keeps their
   inner jit caches small.
2. **jax in-memory jit caches**: per-process, keyed on traced input
   signatures. A miss costs a trace + XLA compile.
3. **Persistent compilation cache** (this module): on-disk, keyed on
   the traced HLO. A jit miss that hits the disk cache pays the trace
   (~ms) but loads the compiled executable instead of re-running XLA
   (~seconds) — this is what survives a process restart.

``prewarm`` replays representative statements at server start so the
trace layer re-populates from the disk layer BEFORE traffic arrives:
restart-warm serving then performs ZERO fresh compiles (the
attribution counters prove it — see tests/test_compile_cache.py and
docs/COMPILATION.md)."""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional, Sequence

from presto_tpu import sanitize

#: JAX's own variable: when set, JAX reads it at import and this
#: module sets NO directory in code (the one rule, docs/COMPILATION.md)
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
#: optional ';'-separated warmup SQL (or @/path/to/file with one
#: statement per non-comment line) run at coordinator start
ENV_PREWARM_SQL = "PRESTO_TPU_PREWARM_SQL"
#: where the cache lives when nothing places it from outside: inside
#: the checkout (git-ignored), at a FIXED path — the path is part of
#: the cache key, so a directory that moves never hits
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_LOCK = sanitize.lock("compile_cache.config")
_CONFIGURED_DIR: Optional[str] = None


def _persist_everything() -> None:
    """Zero jax's persistence thresholds so even small kernels persist
    (restart-warm must re-load EVERYTHING cheaply, and the serving mix
    is mostly sub-second kernels after bucketing)."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def configure_compilation_cache(cache_dir: Optional[str]) -> None:
    """Point jax's persistent compilation cache at `cache_dir`
    (created if missing); None disables it. Process-global by nature
    — jax holds ONE cache dir — so this is a config surface, not a
    session property. Idempotent; a directory that cannot be made or
    a backend that refuses the setting raises."""
    global _CONFIGURED_DIR
    with _LOCK:
        if cache_dir == _CONFIGURED_DIR:
            return
        import jax
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc,
        )
        if cache_dir is not None:
            os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        _persist_everything()
        # jax memoizes a DISABLED cache at the first compile; any
        # compile before this call (module-import jits, an earlier
        # query) would otherwise leave the new dir silently unused
        _cc.reset_cache()
        _CONFIGURED_DIR = cache_dir


def configured_cache_dir() -> Optional[str]:
    """The directory this module set in code (None when jax was left
    to JAX_COMPILATION_CACHE_DIR, or when no cache is on)."""
    return _CONFIGURED_DIR


def configure(cache_dir: Optional[str] = None) -> None:
    """Called when a LocalRunner/Coordinator is built. An explicit
    `compilation_cache_dir=` wins. Without one, the rule:
    JAX_COMPILATION_CACHE_DIR, when
    set, is JAX's to honor and no directory is set in code; otherwise
    the cache is `<checkout>/.jax_cache`. A CPU backend does not
    default into the cache: XLA:CPU's persistent entries are AOT
    executables stamped with synthetic machine features that fail the
    loader's host check on reload (error spam, no speedup), and CPU
    compiles are cheap anyway. Asks for the backend, so it runs at
    construction time, never while the package is imported."""
    if cache_dir is not None:
        configure_compilation_cache(cache_dir)
        return
    if os.environ.get(ENV_CACHE_DIR):
        _persist_everything()
        return
    if _CONFIGURED_DIR is not None:
        return  # an explicit override made earlier stays
    import jax
    if jax.default_backend() == "cpu":
        return
    configure_compilation_cache(DEFAULT_CACHE_DIR)


def clear_kernel_caches() -> None:
    """Drop every in-process compiled-kernel cache: the engine kernel
    LRUs AND jax's in-memory jit caches. This is the process-restart
    simulation (tests/test_compile_cache.py): afterwards the
    only warm layer left is the persistent on-disk cache."""
    from presto_tpu.operators import (
        aggregation, core, fused_fragment, join_ops,
    )
    core._FP_KERNEL_CACHE.clear()
    aggregation._AGG_STEP_CACHE.clear()
    aggregation._AGG_FIN_CACHE.clear()
    join_ops._PROBE_KERNEL_CACHE.clear()
    fused_fragment.clear_fused_kernel_cache()
    import jax
    jax.clear_caches()
    # post-wipe compiles are FIRST traces again — the retrace counter
    # must not misclassify them as shape re-traces
    from presto_tpu.telemetry import kernels as _tk
    _tk.reset_retrace_state()


def parse_prewarm_sql(spec: Optional[str]) -> List[str]:
    """';'-separated SQL, or '@path' to a file of one statement per
    non-empty, non-'--' line."""
    if not spec:
        return []
    if spec.startswith("@"):
        with open(spec[1:]) as f:
            lines = f.read().splitlines()
        return [ln.strip().rstrip(";") for ln in lines
                if ln.strip() and not ln.strip().startswith("--")]
    return [s.strip() for s in spec.split(";") if s.strip()]


def prewarm(runner, statements: Sequence[str],
            user: str = "prewarm") -> Dict[str, Any]:
    """Replay `statements` through the runner so every kernel they
    need is traced (and, with a persistent cache configured, loaded
    from disk instead of recompiled). Failures are recorded, not
    raised — a server must come up even if one warmup statement rots.
    Returns {statements, failed, seconds, compiles, compile_ms,
    disk_cache_dir}."""
    from presto_tpu.telemetry.metrics import METRICS
    t0 = time.perf_counter()
    compiles0 = METRICS.total("presto_tpu_kernel_compiles_total")
    compile_ns0 = METRICS.total("presto_tpu_kernel_compile_ns_total")
    failed: List[str] = []
    for sql in statements:
        try:
            runner.execute_as(sql, user)
            METRICS.inc("presto_tpu_prewarm_statements_total",
                        status="ok")
        except Exception as e:  # noqa: BLE001 — prewarm is best-effort
            failed.append(f"{sql[:80]}: {type(e).__name__}: {e}")
            METRICS.inc("presto_tpu_prewarm_statements_total",
                        status="failed")
    return {
        "statements": len(statements),
        "failed": failed,
        "seconds": round(time.perf_counter() - t0, 3),
        "compiles": int(
            METRICS.total("presto_tpu_kernel_compiles_total")
            - compiles0),
        "compile_ms": round(
            (METRICS.total("presto_tpu_kernel_compile_ns_total")
             - compile_ns0) / 1e6, 1),
        "disk_cache_dir": _CONFIGURED_DIR
        or os.environ.get(ENV_CACHE_DIR) or None,
    }


def prewarm_tables(runner, catalog: Optional[str] = None,
                   schema: Optional[str] = None,
                   caps: Sequence[int] = (4096,)) -> int:
    """Schema-driven family prewarm: for every table of the given
    catalog.schema (defaults: the runner session's), compile the
    GENERIC operator kernels — compact, sort-by-first-column, limit —
    against that table's column layout at the bucketed capacities.
    Statement-driven ``prewarm`` covers query-specific expression
    kernels; this covers the shared families a first ad-hoc query
    would otherwise compile inline. Returns the number of (table,
    cap) combinations warmed."""
    from presto_tpu.batch import empty_batch
    from presto_tpu.ops import sort as sort_kernels
    from presto_tpu import batch as batch_mod
    catalog = catalog or runner.session.catalog
    schema = schema or runner.session.schema
    conn = runner.catalogs.connector(catalog)
    warmed = 0
    for tname in conn.metadata.list_tables(schema):
        from presto_tpu.connectors.spi import TableHandle
        try:
            ts = conn.metadata.get_table_schema(
                TableHandle(catalog, schema, tname))
        except KeyError:
            continue
        schema_cols = [p for c in ts.columns for p in c.physical()]
        if not schema_cols:
            continue
        for cap in caps:
            import jax.numpy as jnp
            b = empty_batch(schema_cols, cap)
            batch_mod._compact(b)
            first = schema_cols[0][0]
            sort_kernels.sort_batch(b, (first,), (False,), (False,))
            # match LimitOperator's real signature: already_emitted is
            # a STRONG int64 device scalar there — a python 0 would
            # warm a weak-typed trace no real query ever hits
            sort_kernels.limit_batch(b, 1, jnp.asarray(0, jnp.int64))
            warmed += 1
    return warmed
