"""Process-wide metrics registry, rendered in the Prometheus text
exposition format (reference analog: presto-main's JMX metrics /
/v1/jmx, re-expressed as the de-facto scrape format so any collector
can consume GET /v1/metrics on the coordinator and every worker).

Counters are monotonic and cheap (one small lock per inc — the sites
are batch/page/query granular, never per row); gauges are sampled live
at render time from their owning subsystems (cache manager, memory
pools), so the scrape always reflects current state without the
subsystems having to push."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from presto_tpu import sanitize

_Key = Tuple[str, Tuple[Tuple[str, str], ...]]


class MetricsRegistry:
    def __init__(self):
        self._lock = sanitize.lock("telemetry.metrics")
        self._counters: Dict[_Key, float] = {}
        self._help: Dict[str, str] = {}
        #: histogram families: name -> bucket upper bounds; series:
        #: key -> {"buckets": [count per bound], "sum", "count"}
        self._hist_bounds: Dict[str, Tuple[float, ...]] = {}
        self._hists: Dict[_Key, Dict[str, object]] = {}

    def describe(self, name: str, help_text: str) -> None:
        self._help.setdefault(name, help_text)

    def describe_histogram(self, name: str, help_text: str,
                           buckets) -> None:
        """Declare a histogram family (Prometheus TYPE histogram:
        cumulative _bucket{le=...} + _sum + _count series)."""
        self._help.setdefault(name, help_text)
        self._hist_bounds.setdefault(
            name, tuple(float(b) for b in buckets))

    def observe(self, name: str, value: float, **labels) -> None:
        bounds = self._hist_bounds[name]  # must be declared
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = {
                    "buckets": [0] * len(bounds),
                    "sum": 0.0, "count": 0}
            for i, b in enumerate(bounds):
                if value <= b:
                    h["buckets"][i] += 1
            h["sum"] += float(value)
            h["count"] += 1

    def histogram_snapshot(self, name: str) -> Dict[str, object]:
        """Merged view over every label combination of one histogram
        family — the bench/test assertion surface."""
        bounds = self._hist_bounds.get(name, ())
        out = {"buckets": [0] * len(bounds), "sum": 0.0, "count": 0,
               "bounds": list(bounds)}
        with self._lock:
            for (n, _), h in self._hists.items():
                if n != name:
                    continue
                for i, v in enumerate(h["buckets"]):
                    out["buckets"][i] += v
                out["sum"] += h["sum"]
                out["count"] += h["count"]
        return out

    def inc(self, name: str, value: float = 1.0, **labels) -> None:
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            self._counters[key] = self._counters.get(key, 0.0) + value

    def get(self, name: str, **labels) -> float:
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            return self._counters.get(key, 0.0)

    def total(self, name: str) -> float:
        """Sum over every label combination of `name`."""
        with self._lock:
            return sum(v for (n, _), v in self._counters.items()
                       if n == name)

    def by_label(self, name: str, label: str) -> Dict[str, float]:
        """{label value -> summed count} for one counter family."""
        out: Dict[str, float] = {}
        with self._lock:
            for (n, labels), v in self._counters.items():
                if n != name:
                    continue
                lv = dict(labels).get(label, "")
                out[lv] = out.get(lv, 0.0) + v
        return out

    def delta_by_label(self, name: str, label: str,
                       before: Dict[str, float]) -> Dict[str, int]:
        """Positive per-label-value growth since a by_label snapshot
        (chip_smoke.py's compiles per family, the tests' deltas)."""
        now = self.by_label(name, label)
        return {k: int(v - before.get(k, 0))
                for k, v in sorted(now.items())
                if v - before.get(k, 0) > 0}

    def snapshot(self) -> Dict[str, float]:
        """{name{label="v",...}: value} — tests and bench deltas."""
        with self._lock:
            out = {}
            for (name, labels), v in sorted(self._counters.items()):
                out[_series(name, labels)] = v
            return out

    def render(self, extra=None) -> str:
        """Prometheus text format. `extra` is an optional list of
        (name, type, help, [(labels_dict, value)]) gauge families
        sampled by the caller at scrape time."""
        lines = []
        with self._lock:
            families: Dict[str, list] = {}
            for (name, labels), v in sorted(self._counters.items()):
                families.setdefault(name, []).append((labels, v))
        for name, series in families.items():
            lines.append(f"# HELP {name} "
                         f"{self._help.get(name, name)}")
            lines.append(f"# TYPE {name} counter")
            for labels, v in series:
                lines.append(f"{_series(name, labels)} {_num(v)}")
        with self._lock:
            hfamilies: Dict[str, list] = {}
            for (name, labels), h in sorted(self._hists.items()):
                hfamilies.setdefault(name, []).append(
                    (labels, list(h["buckets"]), h["sum"],
                     h["count"]))
        for name, series in hfamilies.items():
            bounds = self._hist_bounds[name]
            lines.append(f"# HELP {name} "
                         f"{self._help.get(name, name)}")
            lines.append(f"# TYPE {name} histogram")
            for labels, buckets, total, count in series:
                for b, v in zip(bounds, buckets):
                    le = tuple(sorted(dict(labels,
                                           le=_num(b)).items()))
                    lines.append(
                        f"{_series(name + '_bucket', le)} {v}")
                inf = tuple(sorted(dict(labels, le="+Inf").items()))
                lines.append(
                    f"{_series(name + '_bucket', inf)} {count}")
                lines.append(
                    f"{_series(name + '_sum', labels)} {_num(total)}")
                lines.append(
                    f"{_series(name + '_count', labels)} {count}")
        for name, typ, help_text, series in (extra or ()):
            lines.append(f"# HELP {name} {help_text}")
            lines.append(f"# TYPE {name} {typ}")
            for labels, v in series:
                lines.append(
                    f"{_series(name, tuple(sorted(labels.items())))}"
                    f" {_num(v)}")
        return "\n".join(lines) + "\n"

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._hists.clear()


def _series(name: str, labels) -> str:
    if not labels:
        return name
    inner = ",".join(f'{k}="{_escape(str(v))}"' for k, v in labels)
    return f"{name}{{{inner}}}"


def _escape(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def _num(v: float) -> str:
    return str(int(v)) if float(v).is_integer() else repr(float(v))


#: THE process-wide registry (one per node process, like the cache
#: manager singleton)
METRICS = MetricsRegistry()

# -- well-known series (described up front so a scrape before first
# increment still explains them) --------------------------------------
METRICS.describe("presto_tpu_queries_total",
                 "Queries by terminal state (and error kind)")
METRICS.describe("presto_tpu_kernel_calls_total",
                 "Instrumented jit-kernel invocations")
METRICS.describe("presto_tpu_kernel_compiles_total",
                 "Kernel calls that triggered an XLA compile")
METRICS.describe("presto_tpu_kernel_compile_ns_total",
                 "Wall ns spent in calls that compiled (trace+XLA)")
METRICS.describe("presto_tpu_kernel_execute_ns_total",
                 "Wall ns spent dispatching already-compiled kernels")
METRICS.describe("presto_tpu_kernel_retrace_total",
                 "Kernel compiles by reason: new_kernel = first trace "
                 "of a program, shape = an existing kernel re-traced "
                 "for a new input signature (the retrace source "
                 "kernel_shape_buckets bounds)")
METRICS.describe("presto_tpu_xla_compiles_total",
                 "Programs XLA built or loaded from the persistent "
                 "cache, by kernel family (jax.monitoring's backend "
                 "compile event; family looked up from the program's "
                 "device name, `(unnamed)` for an eager jnp op that "
                 "no kernel family jitted)")
METRICS.describe("presto_tpu_xla_compile_seconds_total",
                 "Seconds of the backend compile events counted by "
                 "presto_tpu_xla_compiles_total, by kernel family")
METRICS.describe("presto_tpu_join_builds_total",
                 "Join build sides indexed at HashBuildOperator.finish, "
                 "by layout: direct = one unique integer key addressed "
                 "by key - min, sorted = the sorted-hash layout (spilled "
                 "builds' per-part tables are not counted)")
METRICS.describe("presto_tpu_join_direct_fallback_total",
                 "Builds that kept the sorted layout, by the first "
                 "reason the direct one was ruled out: join_type (the "
                 "consumer reads only the sorted layout: FULL, semi/"
                 "anti), multi_key, dtype (not "
                 "an integer), spread (max - min + 1 over 8 x capacity "
                 "or 2^27), duplicate (two live rows share a key)")
METRICS.describe("presto_tpu_join_build_rows_total",
                 "Live rows of the build sides indexed at "
                 "HashBuildOperator.finish, by layout (the count its one "
                 "fetch brings: NULL keys included, they occupy the "
                 "merged batch)")
METRICS.describe("presto_tpu_join_build_lanes_total",
                 "Capacity of the merged build batch each finish "
                 "indexed, by layout: the ladder rung the live rows "
                 "land on. rows / lanes is the share of the rung that "
                 "is live")
METRICS.describe("presto_tpu_join_build_batches_total",
                 "Input batches each finish concatenated into its "
                 "merged build batch, by layout (0 for an empty build)")
METRICS.describe("presto_tpu_join_build_packed_lanes_total",
                 "Capacity of the merged build batch each finish PACKED "
                 "(live rows moved to the front: Batch.concat), by "
                 "layout; grows by 0 for a build whose input lanes "
                 "already fit the rung and stay in arrival order "
                 "(Batch.concat_lanes). packed / lanes is the share of "
                 "build lanes a pack passed over")
METRICS.describe("presto_tpu_join_direct_table_slots_total",
                 "Length of the slot_of table of each direct build: "
                 "the key spread rounded up to a power of two, 4 bytes "
                 "a slot")
METRICS.describe("presto_tpu_join_build_finish_ns_total",
                 "Host wall ns inside HashBuildOperator.finish, first "
                 "line to the bridge's hand-over (concat, dynamic "
                 "filters, the table, their syncs; spilled builds too). "
                 "The same frame is the host span join_build:finish on "
                 "jax.profiler's timeline; it charges no ledger category")
METRICS.describe("presto_tpu_join_probe_lanes_total",
                 "Lanes of the lookup join's probe batches by stage: "
                 "searched = lanes the candidate search ran over, "
                 "materialized = lanes at which probe and build "
                 "columns were gathered. An aligned probe above "
                 "COMPACT_FLOOR gathers at the bucket of its live "
                 "count; any other at its output capacity. Static "
                 "shapes, counted on the host")
METRICS.describe("presto_tpu_agg_stream_rows_total",
                 "Live rows into the streaming aggregations of drained "
                 "statements (the operator's input, before a fused "
                 "upstream filter). Added at the statement's drain from "
                 "the operator's armed row counter: profile, or the "
                 "history recorder's interesting_ops")
METRICS.describe("presto_tpu_agg_stream_groups_total",
                 "Groups the streaming aggregations of drained "
                 "statements emitted (the operator's output rows; "
                 "counted as presto_tpu_agg_stream_rows_total is)")
METRICS.describe("presto_tpu_semi_join_probe_rows_total",
                 "Live rows the semi joins of drained statements "
                 "probed (the operator's input rows; counted as "
                 "presto_tpu_agg_stream_rows_total is)")
METRICS.describe("presto_tpu_semi_join_matched_rows_total",
                 "Rows the semi joins of drained statements kept: "
                 "probe rows with a match, or for NOT IN / NOT EXISTS "
                 "those without one (the operator's output rows)")
METRICS.describe("presto_tpu_semi_join_sinks_total",
                 "Joins an IN/EXISTS semi join was moved below at "
                 "planning (optimizer._sink_semi_join), one per join "
                 "passed: TPC-H Q18's semi join passes two and lands "
                 "on the lineitem scan")
METRICS.describe("presto_tpu_protocol_ns_total",
                 "Client-protocol ns on the coordinator by phase: "
                 "accept = POST /v1/statement in to response out, "
                 "result_wait = a finished answer waiting for the "
                 "client's poll (done to tail page handed out), "
                 "encode = json.dumps of result pages that carry data")
METRICS.describe("presto_tpu_prewarm_statements_total",
                 "AOT prewarm statements by status")
METRICS.describe("presto_tpu_expr_compile_ns_total",
                 "Host ns building expression closures (expr/compile)")
METRICS.describe("presto_tpu_exchange_pages_total",
                 "Exchange pages by direction (push/recv/pop)")
METRICS.describe("presto_tpu_exchange_bytes_total",
                 "Exchange payload bytes by direction")
METRICS.describe("presto_tpu_transport_retries_total",
                 "Transport-level retry attempts (backoff tier)")
METRICS.describe("presto_tpu_backoff_sleep_ns_total",
                 "ns slept in transport retry backoff")
METRICS.describe("presto_tpu_transfer_bytes_total",
                 "Transfer bytes by direction: d2h at exchange "
                 "device_get; h2d where a scan or exchange-source "
                 "batch is placed on its task's device from the host; "
                 "d2d where it is copied there from another chip (a "
                 "warm mesh scan makes neither: its page-cache entry "
                 "lives on the chip that reads it); exchange_d2d "
                 "(exchange_h2d) where the mesh exchange copies a "
                 "batch, a bucket index or a wave's shard onto its "
                 "consumer's chip")
METRICS.describe("presto_tpu_executor_quanta_total",
                 "TaskExecutor time slices by outcome (finished/"
                 "progress/blocked/idle/failed/stalled)")
METRICS.describe("presto_tpu_executor_demotions_total",
                 "Drivers demoted to a lower multilevel-feedback-"
                 "queue priority level by accumulated scheduled time")
METRICS.describe("presto_tpu_admission_total",
                 "Resource-group admission decisions (run/queued/"
                 "rejected/queue_full) by group")
METRICS.describe("presto_tpu_admission_sheds_total",
                 "Queries shed by admission control, by kind "
                 "(rejected/queue_full/queue_expired) and group")
METRICS.describe("presto_tpu_tasks_total",
                 "Fault-tolerant scheduler tasks by status "
                 "(dispatched/finished/failed/retried/reused) and "
                 "attempt number — retried counts rescheduled "
                 "attempts, reused counts committed tasks whose "
                 "spooled output survived a worker loss")
METRICS.describe("presto_tpu_heartbeat_probes_total",
                 "Membership heartbeat probes by status (ok/failed)")
METRICS.describe("presto_tpu_membership_transitions_total",
                 "Worker membership transitions by destination state "
                 "(suspected/removed/active/readmitted)")
METRICS.describe("presto_tpu_spool_pages_total",
                 "Task-output spool pages accepted, by tier "
                 "(mem/disk)")
METRICS.describe("presto_tpu_spool_bytes_total",
                 "Task-output spool payload bytes accepted")
METRICS.describe("presto_tpu_fleet_memory_sheds_total",
                 "Queries shed by the fleet memory enforcer "
                 "(cluster-wide reservation gate at dispatch)")
METRICS.describe("presto_tpu_ledger_ns_total",
                 "Wall-attribution ledger ns by category "
                 "(telemetry/ledger.py: queued/planning/scan/h2d/"
                 "d2d/compile/dispatch/device_wait/d2h/serde/exchange/"
                 "spool/retry_backoff/prefetch/driver.*), summed "
                 "over finished queries")
METRICS.describe("presto_tpu_ledger_detail_ns_total",
                 "Self-time ns of the ledger's detailed frames by "
                 "category and detail, a part of that category's "
                 "presto_tpu_ledger_ns_total: driver.step by "
                 "<operator kind>.<method> (a hand-off's own Python), "
                 "prefetch by <source kind>.get_output, "
                 "driver.quantum by statement/executor/mesh_round, "
                 "exchange.all_to_all by assemble/dispatch/sync/slice; "
                 "added once a finished query (ledger.publish)")
METRICS.describe("presto_tpu_driver_passes_total",
                 "Passes of a Driver over its operator chain by "
                 "whether one moved a batch (moved=yes: a pair walk "
                 "that moved, a pump split; moved=no: a walk or a "
                 "pump poll that found an operator blocked or "
                 "nothing to do), whichever loop made them; added "
                 "when the driver closes")
METRICS.describe("presto_tpu_serde_bytes_total",
                 "Page-serde codec bytes by stage (encode/decode) "
                 "and kind: raw = uncompressed payload, framed = "
                 "the LZ4/zlib codec frame on the wire "
                 "(native/codec.py; docs/DATA_PLANE.md)")
METRICS.describe("presto_tpu_pump_drivers_total",
                 "Driver pipelines by drive mode: pump = the batch-"
                 "pump fast path (scan -> fused kernel -> emit/fold "
                 "with double-buffered prefetch), step = the generic "
                 "pair loop (operators/driver.py)")
METRICS.describe("presto_tpu_pump_splits_total",
                 "Source splits driven through the batch pump "
                 "(one prefetch + one fused dispatch each)")
METRICS.describe("presto_tpu_exchange_all_to_all_waves_total",
                 "Collective exchange waves: one fused bucketize + "
                 "jax.lax.all_to_all dispatch across the whole mesh "
                 "(parallel/shuffle.wave_repartition; "
                 "docs/SHARDING.md)")
METRICS.describe("presto_tpu_exchange_all_to_all_rows_total",
                 "Live rows delivered by collective exchange waves "
                 "(dead lanes are routed to the dropped bucket "
                 "in-trace and never cross the interconnect)")
METRICS.describe("presto_tpu_exchange_all_to_all_bytes_total",
                 "Estimated wire bytes of collective exchange waves: "
                 "live rows x packed row width (data + validity "
                 "bytes) of the post-wave schema")
METRICS.describe("presto_tpu_mesh_queries_total",
                 "Queries completed by the mesh (distributed) "
                 "runner, by status")
METRICS.describe("presto_tpu_mesh_lock_wait_ns_total",
                 "ns statements waited for their mesh: one "
                 "statement's collectives run at a time on a mesh "
                 "(runner/mesh.py), the wait is the ledger's `queued`")
METRICS.describe("presto_tpu_mesh_retries_total",
                 "Mesh query re-executions by escalation kind "
                 "(max_groups/join_expansion/history_fusion/"
                 "lifespans — runner/mesh.py retry ladder)")
METRICS.describe("presto_tpu_ledger_unattributed_ns_total",
                 "Wall ns the attribution ledger could NOT assign to "
                 "a category (the coverage residual; the histogram "
                 "presto_tpu_ledger_unattributed_ratio tracks its "
                 "per-query fraction)")
METRICS.describe_histogram(
    "presto_tpu_ledger_unattributed_ratio",
    "Per-query fraction of wall the attribution ledger left "
    "unattributed (coverage regressions shift this right)",
    buckets=(0.01, 0.02, 0.05, 0.10, 0.20, 0.50, 1.0))
METRICS.describe("presto_tpu_flight_dropped_total",
                 "Flight-recorder events not retained, by reason: "
                 "ring_full (oldest event overwritten at capacity) "
                 "vs sampled (skipped by the per-kind sampling "
                 "lever)")


def render_prometheus() -> str:
    """METRICS counters + live gauges from the cache hierarchy and its
    memory pool — the GET /v1/metrics body."""
    extra = []
    try:
        from presto_tpu.cache import get_cache_manager
        mgr = get_cache_manager(create=False)
    except Exception:  # noqa: BLE001 — metrics must always render
        mgr = None
    if mgr is not None:
        rows = mgr.snapshot_rows()
        for metric, idx in (("hits", 1), ("misses", 2),
                            ("evictions", 3)):
            extra.append((
                f"presto_tpu_cache_{metric}_total", "counter",
                f"Cache {metric} by level",
                [({"level": r[0]}, r[idx]) for r in rows]))
        extra.append((
            "presto_tpu_cache_entries", "gauge",
            "Live cache entries by level",
            [({"level": r[0]}, r[4]) for r in rows]))
        extra.append((
            "presto_tpu_cache_bytes", "gauge",
            "Cached batch bytes by level",
            [({"level": r[0]}, r[5]) for r in rows]))
        extra.append((
            "presto_tpu_memory_pool_reserved_bytes", "gauge",
            "Reserved bytes of the shared cache memory pool",
            [({"pool": "cache"}, mgr.pool.reserved)]))
        if mgr.pool.budget is not None:
            extra.append((
                "presto_tpu_memory_pool_budget_bytes", "gauge",
                "Byte budget of the shared cache memory pool",
                [({"pool": "cache"}, mgr.pool.budget)]))
    # time-sliced executor gauges (execution/task_executor.py):
    # sampled live, zero series until the first statement runs on it
    try:
        from presto_tpu.execution.task_executor import (
            get_task_executor,
        )
        ex = get_task_executor(create=False)
    except Exception:  # noqa: BLE001 — metrics must always render
        ex = None
    if ex is not None:
        snap = ex.snapshot()
        extra.append((
            "presto_tpu_executor_running_drivers", "gauge",
            "Drivers currently owned by an executor worker",
            [({}, snap["running_drivers"])]))
        extra.append((
            "presto_tpu_executor_queued_drivers", "gauge",
            "Runnable drivers waiting per multilevel-queue level",
            [({"level": str(i)}, n)
             for i, n in enumerate(snap["queued_drivers"])]))
        extra.append((
            "presto_tpu_executor_parked_drivers", "gauge",
            "Drivers parked blocked/idle awaiting input",
            [({}, snap["parked_drivers"])]))
        extra.append((
            "presto_tpu_executor_tasks", "gauge",
            "Live tasks (queries/fragments) on the executor",
            [({}, snap["tasks"])]))
    # fleet control-plane gauges: live membership states per
    # heartbeat monitor and the task-output spool's footprint
    try:
        monitors = sanitize.tracked("heartbeat_monitor")
    except Exception:  # noqa: BLE001
        monitors = []
    if monitors:
        counts: Dict[str, float] = {}
        tasks_running = []
        exec_queued = []
        reserved = []
        for m in monitors:
            for state, n in m.counts().items():
                counts[state] = counts.get(state, 0) + n
            try:
                rows = m.snapshot()
            except Exception:  # noqa: BLE001
                rows = []
            for w in rows:
                load = w.get("load") or {}
                mem = w.get("memory") or {}
                lbl = {"worker": w["url"]}
                tasks_running.append(
                    (lbl, load.get("tasks_running", 0)))
                exec_queued.append(
                    (lbl, load.get("executor_queued", 0)))
                reserved.append(
                    (lbl, mem.get("reserved_bytes", 0)))
        extra.append((
            "presto_tpu_workers", "gauge",
            "Fleet members by membership state",
            [({"state": s}, n) for s, n in sorted(counts.items())]))
        # per-worker load feedback (the placement inputs), scraped
        # from the heartbeat's last successful probe — the Prometheus
        # face of system.runtime.nodes
        if tasks_running:
            extra.append((
                "presto_tpu_worker_tasks_running", "gauge",
                "Running fragment tasks per worker (heartbeat "
                "report)", tasks_running))
            extra.append((
                "presto_tpu_worker_executor_queued", "gauge",
                "Executor queue depth per worker (heartbeat report)",
                exec_queued))
            extra.append((
                "presto_tpu_worker_reserved_bytes", "gauge",
                "Reserved memory bytes per worker (heartbeat "
                "report)", reserved))
    try:
        spools = sanitize.tracked("task_spool")
    except Exception:  # noqa: BLE001
        spools = []
    if spools:
        stats = [s.stats() for s in spools]
        extra.append((
            "presto_tpu_spool_bytes", "gauge",
            "Memory-tier bytes held by task-output spools",
            [({}, sum(s["bytes"] for s in stats))]))
        extra.append((
            "presto_tpu_spool_committed_tasks", "gauge",
            "Committed (replayable) tasks across task-output spools",
            [({}, sum(s["committed_tasks"] for s in stats))]))
    # per-group admission gauges (running + queue depth) across every
    # live ResourceGroupManager of this process
    try:
        from presto_tpu.execution.resource_groups import (
            sample_group_gauges,
        )
        running, queued = sample_group_gauges()
    except Exception:  # noqa: BLE001
        running = queued = []
    if running:
        extra.append((
            "presto_tpu_resource_group_running", "gauge",
            "Running queries per resource group", running))
        extra.append((
            "presto_tpu_resource_group_queued", "gauge",
            "Queued queries per resource group", queued))
    return METRICS.render(extra)
