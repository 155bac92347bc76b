"""Stats snapshotting + rendering shared by EXPLAIN ANALYZE, the
/v1/task status RPC, and the /v1/query/{id} stats tree (reference:
operator/OperatorStats.java rolled up through TaskStats/StageStats
into QueryStats, and planPrinter's EXPLAIN ANALYZE rendering).

Snapshots are PLAIN DICTS: they must serialize over the task-status
RPC, outlive their operators without pinning device buffers, and land
in system.runtime.operator_stats rows unchanged."""

from __future__ import annotations

from typing import Any, Dict, List, Optional


def snapshot_drivers(drivers, pool=None) -> List[List[Dict[str, Any]]]:
    """Materialize per-operator stats into JSON-able dicts, one list
    per pipeline, WITHOUT retaining operators (which would pin their
    buffered device batches)."""
    peaks = pool.peak_by_tag if pool is not None else {}
    out = []
    for pi, d in enumerate(drivers):
        ops = []
        for op in d.operators:
            ctx = op.ctx
            ctx.stats.materialize()
            s = ctx.stats.snapshot()
            s.update(pipeline=pi, operator_id=ctx.operator_id,
                     name=ctx.name, tag=ctx.tag,
                     peak_bytes=peaks.get(ctx.tag, 0))
            ops.append(s)
        out.append(ops)
    return out


def count_streamed_rows(drivers) -> None:
    """Add a drained statement's operator rows to the process's
    counters (`Operator.count_drained`: the streaming aggregation's
    and the semi join's `row_series`, the lookup join's probe rows by
    layout). Call it after snapshot_drivers, which has materialized
    the stats: it reads no device value of its own. Only operators
    whose row counters were armed (profile, or the history recorder's
    interesting_ops) count."""
    for d in drivers:
        for op in d.operators:
            if op.ctx.stats.count_rows:
                op.count_drained(op.ctx.stats)


def _ms(ns: int) -> float:
    return ns / 1e6


def operator_line(s: Dict[str, Any]) -> str:
    """One EXPLAIN ANALYZE stats line. The leading `name [id=N]  rows:
    A -> B  batches: ...  busy: ...ms` shape is LOAD-BEARING (tests
    and downstream tooling grep it); the compile/execute/cache columns
    append after it."""
    mem = s.get("peak_bytes", 0)
    mem_s = f"  peak mem: {mem / 1e6:.1f}MB" if mem else ""
    spill_s = (f"  spilled: {s['spilled_batches']} batches/"
               f"{s['spilled_bytes'] / 1e6:.1f}MB"
               if s.get("spilled_batches") else "")
    cache_s = (f"  cache: {s.get('cache_hits', 0)} hits/"
               f"{s.get('cache_misses', 0)} misses"
               if s.get("cache_hits") or s.get("cache_misses") else "")
    ker_s = ""
    if s.get("compile_ns") or s.get("execute_ns"):
        ker_s = (f"  compile: {_ms(s.get('compile_ns', 0)):.1f}ms"
                 f"  execute: {_ms(s.get('execute_ns', 0)):.1f}ms")
    blocked_s = (f"  blocked: {_ms(s['blocked_ns']):.1f}ms"
                 if s.get("blocked_ns") else "")
    return (f"  {s['name']} [id={s['operator_id']}]  "
            f"rows: {s.get('input_rows', 0):,} -> "
            f"{s.get('output_rows', 0):,}  "
            f"batches: {s.get('input_batches', 0)} -> "
            f"{s.get('output_batches', 0)}  "
            f"busy: {s.get('busy_seconds', 0.0) * 1e3:.1f}ms"
            f"{ker_s}{blocked_s}{mem_s}{spill_s}{cache_s}")


def render_operator_stats(pipelines: List[List[Dict[str, Any]]],
                          wall: float, pool=None) -> str:
    """Per-operator execution stats text (the EXPLAIN ANALYZE body and
    the distributed profile's per-task sections)."""
    peaks = pool.peak_by_tag if pool is not None else {}
    lines = []
    busy_total = 0.0
    compile_total = 0
    execute_total = 0
    for pi, ops in enumerate(pipelines):
        lines.append(f"Pipeline {pi}:")
        for s in reversed(ops):
            busy_total += s.get("busy_seconds", 0.0)
            compile_total += s.get("compile_ns", 0)
            execute_total += s.get("execute_ns", 0)
            if not s.get("peak_bytes") and peaks:
                s = {**s,
                     "peak_bytes": peaks.get(s.get("tag"), 0)}
            lines.append(operator_line(s))
    lines.append(f"wall: {wall * 1e3:.1f}ms, "
                 f"operator busy sum: {busy_total * 1e3:.1f}ms")
    lines.append(f"kernel time: compile {_ms(compile_total):.1f}ms + "
                 f"execute {_ms(execute_total):.1f}ms = "
                 f"{_ms(compile_total + execute_total):.1f}ms")
    if pool is not None and pool.peak:
        lines.append(f"peak reserved device memory: "
                     f"{pool.peak / 1e6:.1f}MB")
    return "\n".join(lines)


def rollup(pipelines: List[List[Dict[str, Any]]]) -> Dict[str, Any]:
    """Task-level totals over one snapshot (TaskStats analog)."""
    out = {"busy_ms": 0.0, "compile_ms": 0.0, "execute_ms": 0.0,
           "blocked_ms": 0.0, "input_rows": 0, "output_rows": 0,
           "input_batches": 0, "output_batches": 0,
           "cache_hits": 0, "cache_misses": 0, "peak_bytes": 0}
    for ops in pipelines:
        for s in ops:
            out["busy_ms"] += s.get("busy_seconds", 0.0) * 1e3
            out["compile_ms"] += _ms(s.get("compile_ns", 0))
            out["execute_ms"] += _ms(s.get("execute_ns", 0))
            out["blocked_ms"] += _ms(s.get("blocked_ns", 0))
            for k in ("input_rows", "output_rows", "input_batches",
                      "output_batches", "cache_hits", "cache_misses"):
                out[k] += s.get(k, 0)
            out["peak_bytes"] = max(out["peak_bytes"],
                                    s.get("peak_bytes", 0))
    for k in ("busy_ms", "compile_ms", "execute_ms", "blocked_ms"):
        out[k] = round(out[k], 3)
    return out


def render_ledger(doc: Dict[str, Any]) -> str:
    """EXPLAIN ANALYZE's wall-attribution section: one line per
    ledger category + the explicit unattributed residual, with the
    coverage invariant (Σ == wall) visible in the text itself."""
    wall = doc.get("wall_ms", 0.0)
    lines = ["wall attribution (telemetry/ledger.py, "
             "sum + unattributed == wall):"]
    details = doc.get("details_ms", {})
    for c, ms in doc.get("categories_ms", {}).items():
        pct = (100.0 * ms / wall) if wall > 0 else 0.0
        lines.append(f"  {c:<20} {ms:>10.1f}ms  {pct:5.1f}%")
        # who inside the category: parts of the line above (those
        # that round to a tenth of a millisecond at least)
        for d, part in sorted(details.get(c, {}).items(),
                              key=lambda kv: -kv[1]):
            if part >= 0.05:
                lines.append(f"    /{d:<28} {part:>10.1f}ms")
    unattr = doc.get("unattributed_ms", 0.0)
    pct = (100.0 * unattr / wall) if wall > 0 else 0.0
    lines.append(f"  {'unattributed':<20} {unattr:>10.1f}ms  "
                 f"{pct:5.1f}%")
    lines.append(f"  {'wall':<20} {wall:>10.1f}ms")
    per_device = doc.get("per_device")
    if per_device:
        lines.append("per-device attribution (mesh tasks; "
                     "docs/SHARDING.md):")
        for dev, cats in per_device.items():
            total = sum(cats.values())
            top = sorted(cats.items(), key=lambda kv: -kv[1])[:4]
            detail = "  ".join(f"{c}={ms:.1f}ms" for c, ms in top)
            lines.append(f"  device {dev:<3} {total:>10.1f}ms  "
                         f"{detail}")
    return "\n".join(lines)


def build_query_stats(wall_ms: float, queued_ms: float = 0.0,
                      kernel: Optional[Dict[str, int]] = None,
                      tasks: Optional[List[Dict[str, Any]]] = None,
                      rows_out: Optional[int] = None,
                      state: Optional[str] = None,
                      error_kind: Optional[str] = None
                      ) -> Dict[str, Any]:
    """The QueryStats tree served by GET /v1/query/{id}, shipped to
    event listeners, and projected into system.runtime.queries.
    `kernel` is the per-query counter dict from telemetry.kernels;
    `tasks` is [{"task_id", "worker", "pipelines": [[op dicts]]}]."""
    kernel = kernel or {}
    stats: Dict[str, Any] = {
        "wall_ms": round(wall_ms, 3),
        "queued_ms": round(queued_ms, 3),
        "compile_ms": round(_ms(kernel.get("compile_ns", 0)), 3),
        "execute_ms": round(_ms(kernel.get("execute_ns", 0)), 3),
        "expr_compile_ms": round(
            _ms(kernel.get("expr_compile_ns", 0)), 3),
        "kernel_calls": kernel.get("kernel_calls", 0),
        "kernel_compiles": kernel.get("compiles", 0),
    }
    if state is not None:
        stats["state"] = state
    if error_kind is not None:
        stats["error_kind"] = error_kind
    if rows_out is not None:
        stats["rows_out"] = rows_out
    if tasks is not None:
        stats["tasks"] = [
            {**t, "totals": rollup(t.get("pipelines", []))}
            for t in tasks]
    return stats
