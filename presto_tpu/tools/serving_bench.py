"""Concurrent serving benchmark: N clients through the coordinator on
a repeated dashboard-style TPC-H mix, cold vs warm (reference: the
serving posture of both Presto papers — repeat dashboard traffic is
the workload the cache hierarchy exists for; the existing tools/
benchmark.py measures single-query latency, this measures QPS and tail
latency under concurrency).

Topology: one single-node Coordinator (shared LocalRunner + the
process-wide plan/fragment/page cache hierarchy) behind the real HTTP
client protocol; N StatementClient threads.

Protocol:
  cold  — caches cleared; the mix's queries run once, spread across
          the clients (first-arrival latency, jit compile included —
          that IS the cold serving experience)
  warm  — every client runs the full mix `warm_rounds` times
  off   — (optional) the mix once more against a coordinator with
          every cache level disabled, for the equivalence oracle
  chaos — (--chaos) the mix again with the deterministic fault
          registry armed at a FIXED seed (periodic injected faults at
          operator and cache seams): reports availability + an error
          taxonomy alongside QPS, and every query that SUCCEEDS under
          chaos must still be byte-identical to the warm phase —
          faults may cost availability, never correctness.
  overload — (--overload) offered load > capacity: every client
          hammers the mix against a coordinator whose admission caps
          are deliberately far below the client count. Overload must
          be ABSORBED as structured rejected/queue_full sheds (never
          collapse): the phase reports shed counts by kind, per-user
          p50/p99 (the per-user fair-queueing story), queue-depth
          peaks sampled live from the resource groups + executor,
          and the availability of ADMITTED queries — which must stay
          ~1.0 while sheds soak up the excess. Successes must remain
          byte-identical to warm.
  worker-churn — (--worker-churn) the fleet-robustness story: a
          MULTI-WORKER coordinator (fault-tolerant task retries over
          spooled exchanges, fixed task partitions) serves the mix
          while one worker per window is SIGKILLed and respawned on
          its old port. Admitted availability must stay 1.0 — the
          task-retry + elastic tiers absorb every death — and every
          success must stay byte-identical to a pre-churn baseline
          on the SAME topology; tasks retried vs reused and
          membership transitions ride the report.
  restart-warm — (--restart-warm) the process-restart story: kernel
          LRUs + jax jit caches wiped (everything a coordinator
          reboot loses), caches cleared, then a NEW coordinator comes
          up with the mix as its AOT prewarm list against the
          persistent XLA compilation cache populated by the earlier
          phases. The measured phase must perform ZERO fresh compiles
          (fresh_compiles, from the attribution counters) and land
          within ~1.2x of warm QPS.

Every phase checksums each query's result rows; the run fails loudly
if warm results are not byte-identical to cold and to caches-off (or
if any chaos-phase success diverges).

Usage:
    python -m presto_tpu.tools.serving_bench --clients 4 \
        --schema sf0_1 --mix q1,q3,q6,q13 --warm-rounds 3 \
        --chaos --out BENCH_SERVING_r08.json
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

#: default dashboard mix: an aggregation-heavy repeat workload (scan+
#: agg q1/q6, a 3-way join q3, a join+group q13) — the shape a BI
#: dashboard refresh sends at a serving cluster
DEFAULT_MIX = ("q1", "q3", "q6", "q13")

#: the fixed-seed chaos recipe: a transient operator fault roughly
#: every ~150 batch hand-offs (fails the unlucky query with a clean
#: structured error) and a cache-insert fault every 3rd put (absorbed
#: as a rejection by contract) — deterministic via the spec's seeds
DEFAULT_CHAOS_SPEC = ("operator.add_input:every:150:7;"
                      "cache.put:every:3:11")


def _percentile(xs: Sequence[float], p: float) -> float:
    if not xs:
        return 0.0
    s = sorted(xs)
    i = min(int(round(p * (len(s) - 1))), len(s) - 1)
    return s[i]


def _checksum(rows: List[list]) -> str:
    """ORDER-SENSITIVE row digest: the byte-identity oracle must see a
    replay that returns right values in the wrong order (the mix's
    queries all end in ORDER BY, so order is part of the answer)."""
    h = hashlib.blake2b(digest_size=16)
    for r in rows:
        h.update(repr(r).encode())
    return h.hexdigest()


def _harvest_ledgers(coord, known_ids: set,
                     names_by_sql: Dict[str, str]) -> Optional[dict]:
    """Aggregate the attribution-ledger docs of every query this phase
    FINISHED on `coord` (ids not in `known_ids`): summed categories,
    the per-query residual distribution (the acceptance bar: every
    query's unattributed < 10% of wall), and a per-mix-query
    breakdown — the machine-readable where-the-glue-goes evidence."""
    per_query: Dict[str, dict] = {}
    total_cats: Dict[str, float] = {}
    wall = unattr = 0.0
    max_frac = 0.0
    over_10 = 0
    n = 0
    for qid, q in list(coord.queries.items()):
        if qid in known_ids or q.state != "FINISHED":
            continue
        led = (q.stats or {}).get("ledger")
        if not led:
            continue
        n += 1
        name = names_by_sql.get(q.sql, q.sql[:24])
        frac = max(0.0, float(led.get("unattributed_frac") or 0.0))
        max_frac = max(max_frac, frac)
        if frac >= 0.10:
            over_10 += 1
        wall += led.get("wall_ms", 0.0)
        unattr += led.get("unattributed_ms", 0.0)
        agg = per_query.setdefault(name, {
            "queries": 0, "wall_ms": 0.0, "unattributed_ms": 0.0,
            "unattributed_frac_max": 0.0, "categories_ms": {}})
        agg["queries"] += 1
        agg["wall_ms"] = round(agg["wall_ms"]
                               + led.get("wall_ms", 0.0), 3)
        agg["unattributed_ms"] = round(
            agg["unattributed_ms"] + led.get("unattributed_ms", 0.0),
            3)
        agg["unattributed_frac_max"] = max(
            agg["unattributed_frac_max"], frac)
        for c, ms in led.get("categories_ms", {}).items():
            agg["categories_ms"][c] = round(
                agg["categories_ms"].get(c, 0.0) + ms, 3)
            total_cats[c] = round(total_cats.get(c, 0.0) + ms, 3)
    if n == 0:
        return None
    return {
        "queries": n,
        "wall_ms": round(wall, 3),
        "categories_ms": dict(sorted(total_cats.items())),
        "unattributed_ms": round(unattr, 3),
        "unattributed_frac_max": round(max_frac, 4),
        "queries_over_10pct": over_10,
        "per_query": {k: {**v, "categories_ms": dict(sorted(
            v["categories_ms"].items()))}
            for k, v in sorted(per_query.items())},
    }


def _serde_delta(metrics, before: Dict[Tuple[str, str], float]) -> dict:
    """This phase's exchange/spool serde traffic from the monotonic
    `presto_tpu_serde_bytes_total` counters: raw vs framed bytes per
    direction plus the achieved compression ratio (framed/raw; < 1.0
    means the codec shrank the wire). Phases run sequentially, so the
    before/after delta is exactly this phase's traffic."""
    out = {}
    for s in ("encode", "decode"):
        raw = int(metrics.get("presto_tpu_serde_bytes_total",
                              stage=s, kind="raw")
                  - before[(s, "raw")])
        framed = int(metrics.get("presto_tpu_serde_bytes_total",
                                 stage=s, kind="framed")
                     - before[(s, "framed")])
        out[s] = {"raw_bytes": raw, "framed_bytes": framed,
                  "ratio": round(framed / raw, 4) if raw else None}
    return out


def _doctor_verdict(warm_stats: dict,
                    expected: Optional[str]) -> Optional[dict]:
    """query_doctor's verdict over the warm (serving-mix) phase's
    aggregated ledger — where does the steady-state wall go. With
    `expected` set (--assert-verdict) a mismatched verdict FAILS the
    bench: the CI gate that keeps the serving mix kernel-dominated."""
    from presto_tpu.tools.query_doctor import diagnose
    led = (warm_stats or {}).get("ledger")
    if not led:
        if expected:
            raise RuntimeError(
                "--assert-verdict: warm phase produced no "
                "attribution ledger to diagnose")
        return None
    d = diagnose(led)
    if expected and d["verdict"] != expected:
        raise RuntimeError(
            f"--assert-verdict {expected}: warm serving-mix verdict "
            f"is {d['verdict']} (shares: "
            + json.dumps(d["shares_frac"]) + ")")
    return d


def _run_critical_path_phase(coord, work: List[Tuple[str, str]],
                             tolerance: float = 0.05) -> dict:
    """Each mix query once, traced, through the live coordinator: the
    blocking-chain extraction must produce a critical path whose
    segments sum to wall within `tolerance` for EVERY query (the
    machine-checked contract of telemetry/critical_path.py), and the
    per-query category decomposition rides the capture so a round's
    "where did warm latency go" is answerable from the JSON alone."""
    from presto_tpu.server.coordinator import StatementClient
    from presto_tpu.telemetry import critical_path as _cp
    runner = coord._runner()
    prev = runner.session.properties.get("query_trace_enabled")
    runner.session.properties["query_trace_enabled"] = True
    per_query: Dict[str, dict] = {}
    failures: List[str] = []
    try:
        c = StatementClient(coord.url, user="bench-cp",
                            source="serving_bench")
        for name, sql in work:
            known = set(coord.queries)
            c.execute(sql, timeout=600.0)
            qid = next((i for i in coord.queries
                        if i not in known), None)
            doc = ((coord.queries[qid].stats or {})
                   .get("critical_path")) if qid else None
            if not doc:
                failures.append(f"{name}: traced query produced no "
                                f"critical-path doc")
                continue
            ok, detail = _cp.verify(doc, tolerance)
            if not ok:
                failures.append(f"{name}: {detail}")
            cats = doc.get("categories_ms") or {}
            per_query[name] = {
                "wall_ms": doc.get("wall_ms"),
                "coverage": doc.get("coverage"),
                "verified": ok,
                "categories_ms": dict(list(cats.items())[:6]),
                "summary": _cp.render(doc).splitlines()[0],
            }
    finally:
        if prev is None:
            runner.session.properties.pop("query_trace_enabled",
                                          None)
        else:
            runner.session.properties["query_trace_enabled"] = prev
    out = {"tolerance": tolerance, "queries": per_query,
           "failures": failures, "verified_all": not failures}
    if failures:
        # the sum-to-wall invariant is the whole point of the
        # extraction — a query it fails on is a bench failure
        raise RuntimeError("critical-path phase failed: "
                           + json.dumps(out, indent=1))
    return out


def _run_phase(url: str, assignments: List[List[Tuple[str, str]]],
               tolerant: bool = False, timeout_s: float = 600.0,
               coord=None) -> Tuple[dict, Dict[str, set]]:
    """Run each client's (name, sql) list on its own thread through
    the HTTP client protocol. Returns (phase stats, {query name ->
    set of checksums over EVERY SUCCESSFUL execution} — a single
    transient bad read anywhere in the phase widens the set and fails
    the oracle).

    Default mode treats any query failure as fatal (the bench is
    broken). `tolerant` is the CHAOS mode: per-query failures are
    expected, recorded into an error taxonomy, and reported as
    availability — the per-query client timeout bounds every fault
    mode, so a chaos phase can lose availability but never hang."""
    from presto_tpu.server.coordinator import StatementClient
    latencies: List[float] = []
    checks: Dict[str, set] = {}
    errors: List[str] = []
    taxonomy: Dict[str, int] = {}
    lock = threading.Lock()
    # count only clients with work: an empty assignment spawns no
    # thread, and a barrier party that never arrives would hang the
    # whole bench (e.g. --clients 5 with the default 4-query mix)
    assignments = [w for w in assignments if w]
    start = threading.Barrier(len(assignments) + 1)

    def client(idx: int, work: List[Tuple[str, str]]) -> None:
        c = StatementClient(url, user=f"bench-{idx}",
                            source="serving_bench")
        start.wait()
        for name, sql in work:
            t0 = time.perf_counter()
            try:
                _, data = c.execute(sql, timeout=timeout_s)
            except Exception as e:  # noqa: BLE001 — recorded
                kind = getattr(e, "kind", None) \
                    or str(e).split(":", 1)[0].strip() \
                    or type(e).__name__
                with lock:
                    errors.append(f"{name}: {type(e).__name__}: {e}")
                    taxonomy[kind] = taxonomy.get(kind, 0) + 1
                if tolerant:
                    continue
                return
            dt = time.perf_counter() - t0
            with lock:
                latencies.append(dt)
                checks.setdefault(name, set()).add(_checksum(data))

    # per-phase XLA attribution: the process-wide kernel counters are
    # monotonic and phases run sequentially, so before/after deltas
    # are exactly this phase's compile-vs-execute split — including
    # DISTINCT COMPILES PER KERNEL FAMILY, the compile-amortization
    # trajectory metric (a phase that re-uses every kernel shows {})
    from presto_tpu.telemetry.metrics import METRICS
    known_ids = set(coord.queries) if coord is not None else set()
    names_by_sql = {sql: name
                    for work in assignments for name, sql in work}
    # per-phase serde/compression attribution: raw (uncompressed
    # payload) vs framed (LZ4/zlib codec frame) bytes per direction —
    # the before-vs-after-compression evidence of the exchange plane
    serde0 = {(s, k): METRICS.get("presto_tpu_serde_bytes_total",
                                  stage=s, kind=k)
              for s in ("encode", "decode") for k in ("raw", "framed")}
    compile0 = METRICS.total("presto_tpu_kernel_compile_ns_total")
    execute0 = METRICS.total("presto_tpu_kernel_execute_ns_total")
    fam0 = METRICS.by_label("presto_tpu_kernel_compiles_total",
                            "kernel")
    fuse0 = METRICS.by_label("presto_tpu_fused_fragments_total",
                             "status")
    threads = [threading.Thread(target=client, args=(i, work))
               for i, work in enumerate(assignments)]
    for t in threads:
        t.start()
    start.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors and not tolerant:
        raise RuntimeError("serving bench query failed: "
                           + "; ".join(errors))
    distinct = METRICS.delta_by_label(
        "presto_tpu_kernel_compiles_total", "kernel", fam0)
    n = len(latencies)
    stats = {
        "queries": n,
        "wall_s": round(wall, 3),
        "qps": round(n / wall, 3) if wall > 0 else None,
        "p50_ms": round(_percentile(latencies, 0.50) * 1e3, 1),
        "p95_ms": round(_percentile(latencies, 0.95) * 1e3, 1),
        "p99_ms": round(_percentile(latencies, 0.99) * 1e3, 1),
        "max_ms": round(max(latencies) * 1e3, 1) if latencies
        else 0.0,
        "kernel_compile_ms": round(
            (METRICS.total("presto_tpu_kernel_compile_ns_total")
             - compile0) / 1e6, 1),
        "kernel_execute_ms": round(
            (METRICS.total("presto_tpu_kernel_execute_ns_total")
             - execute0) / 1e6, 1),
        "distinct_compiles": distinct,
        "fresh_compiles": int(sum(distinct.values())),
        # whole-fragment fusion coverage of the phase (planner pass
        # counters; plan-cache hits re-run the pass per execution, so
        # every query of the phase contributes)
        "fused_fragments": METRICS.delta_by_label(
            "presto_tpu_fused_fragments_total", "status", fuse0),
        "serde_bytes": _serde_delta(METRICS, serde0),
    }
    if coord is not None:
        # wall-attribution ledger rollup of THIS phase's queries —
        # categories summed, per-query residuals (the coverage bar)
        stats["ledger"] = _harvest_ledgers(coord, known_ids,
                                           names_by_sql)
    if tolerant:
        total = n + len(errors)
        stats.update({
            "queries": total,
            "succeeded": n,
            "failed": len(errors),
            "availability": round(n / total, 4) if total else None,
            "errors": dict(sorted(taxonomy.items())),
        })
    return stats, checks


#: shed kinds — admission refused the work; everything else that
#: fails was ADMITTED and counts against availability
#: (cluster_memory = the fleet memory enforcer's dispatch gate)
SHED_KINDS = ("rejected", "queue_full", "cluster_memory")


def _run_overload_phase(url: str, resource_groups, clients: int,
                        work: List[Tuple[str, str]], rounds: int,
                        timeout_s: float = 180.0) -> Tuple[dict,
                                                           Dict[str,
                                                                set]]:
    """Offered load > capacity through the real HTTP protocol: every
    client loops the mix `rounds` times with no pacing. Sheds are
    EXPECTED; admitted queries must succeed. Returns (stats,
    {query name -> checksums of successes}) like _run_phase, plus
    per-user latency percentiles and live queue-depth peaks (sampled
    from the resource groups and the executor at ~25ms)."""
    from presto_tpu.server.coordinator import StatementClient
    from presto_tpu.telemetry.metrics import METRICS
    lock = threading.Lock()
    checks: Dict[str, set] = {}
    per_user: Dict[str, dict] = {}
    taxonomy: Dict[str, int] = {}
    assignments = [list(work) * rounds for _ in range(clients)]
    start = threading.Barrier(clients + 1)
    stop_sampler = threading.Event()
    depth_peaks = {"queued": 0, "running": 0,
                   "executor_queued": 0, "queued_last": 0}

    def sampler():
        from presto_tpu.execution.task_executor import (
            get_task_executor,
        )
        while not stop_sampler.wait(0.025):
            try:
                snap = resource_groups.snapshot()
                queued = max((r["queued"] for r in snap), default=0)
                running = max((r["running"] for r in snap),
                              default=0)
                depth_peaks["queued"] = max(depth_peaks["queued"],
                                            queued)
                depth_peaks["queued_last"] = queued
                depth_peaks["running"] = max(depth_peaks["running"],
                                             running)
                ex = get_task_executor(create=False)
                if ex is not None:
                    depth_peaks["executor_queued"] = max(
                        depth_peaks["executor_queued"],
                        sum(ex.snapshot()["queued_drivers"]))
            except Exception:  # noqa: BLE001 — sampling best-effort
                pass

    def client(idx: int, my_work: List[Tuple[str, str]]) -> None:
        user = f"bench-{idx}"
        c = StatementClient(url, user=user, source="serving_bench")
        mine = per_user.setdefault(user, {
            "latencies": [], "shed": 0, "failed": 0})
        start.wait()
        for name, sql in my_work:
            t0 = time.perf_counter()
            try:
                _, data = c.execute(sql, timeout=timeout_s)
            except Exception as e:  # noqa: BLE001 — recorded
                kind = getattr(e, "kind", None) \
                    or str(e).split(":", 1)[0].strip() \
                    or type(e).__name__
                with lock:
                    taxonomy[kind] = taxonomy.get(kind, 0) + 1
                    if kind in SHED_KINDS:
                        mine["shed"] += 1
                    else:
                        mine["failed"] += 1
                continue
            dt = time.perf_counter() - t0
            with lock:
                mine["latencies"].append(dt)
                checks.setdefault(name, set()).add(_checksum(data))

    quanta0 = METRICS.total("presto_tpu_executor_quanta_total")
    demo0 = METRICS.total("presto_tpu_executor_demotions_total")
    threads = [threading.Thread(target=client, args=(i, w))
               for i, w in enumerate(assignments)]
    sampler_t = threading.Thread(target=sampler, daemon=True)
    sampler_t.start()
    for t in threads:
        t.start()
    start.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    stop_sampler.set()
    sampler_t.join(timeout=2)
    lat_all: List[float] = []
    users_doc = {}
    for user, d in sorted(per_user.items()):
        xs = d["latencies"]
        lat_all.extend(xs)
        users_doc[user] = {
            "succeeded": len(xs),
            "shed": d["shed"],
            "failed": d["failed"],
            "p50_ms": round(_percentile(xs, 0.50) * 1e3, 1),
            "p99_ms": round(_percentile(xs, 0.99) * 1e3, 1),
        }
    offered = sum(len(w) for w in assignments)
    shed = sum(taxonomy.get(k, 0) for k in SHED_KINDS)
    admitted = offered - shed
    succeeded = len(lat_all)
    return {
        "offered": offered,
        "admitted": admitted,
        "succeeded": succeeded,
        "shed": shed,
        "sheds_by_kind": {k: taxonomy[k] for k in SHED_KINDS
                          if k in taxonomy},
        "errors": dict(sorted(taxonomy.items())),
        # the robustness headline: of the queries admission LET IN,
        # how many answered (sheds are absorbed overload, not
        # failures)
        "availability_admitted": round(succeeded / admitted, 4)
        if admitted else None,
        "wall_s": round(wall, 3),
        "qps": round(succeeded / wall, 3) if wall > 0 else None,
        "p50_ms": round(_percentile(lat_all, 0.50) * 1e3, 1),
        "p99_ms": round(_percentile(lat_all, 0.99) * 1e3, 1),
        "max_ms": round(max(lat_all) * 1e3, 1) if lat_all else 0.0,
        "per_user": users_doc,
        "queue_depth_peak": depth_peaks["queued"],
        "queue_depth_final": depth_peaks["queued_last"],
        "running_peak": depth_peaks["running"],
        "executor_queued_peak": depth_peaks["executor_queued"],
        "executor_quanta": int(METRICS.total(
            "presto_tpu_executor_quanta_total") - quanta0),
        "executor_demotions": int(METRICS.total(
            "presto_tpu_executor_demotions_total") - demo0),
    }, checks


def _spawn_churn_worker(port: int = 0):
    """One worker subprocess for the churn phase (same spawn shape as
    tests/test_distributed.py). `port` > 0 re-binds a respawned
    worker to its predecessor's address so the coordinator's
    membership view re-admits it in place."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env = {**os.environ, "PYTHONPATH": root}
    proc = subprocess.Popen(
        [sys.executable, "-m", "presto_tpu.server.node",
         "--port", str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True)
    url = json.loads(proc.stdout.readline())["url"]
    return proc, url


def _run_worker_churn_phase(schema: str, work: List[Tuple[str, str]],
                            clients: int, rounds: int,
                            n_workers: int, kills: int,
                            period_s: float, host: str,
                            timeline_out: Optional[str]
                            = None) -> dict:
    """Fault-tolerant fleet serving under worker CHURN: a
    multi-worker coordinator (task_retries on, fixed task_partitions
    so results stay byte-identical across membership changes) serves
    the mix while a churn thread SIGKILLs one worker per window and
    respawns it on the same port. Reports admitted availability
    (must stay 1.0 — the task-retry + elastic tiers absorb every
    death), tasks retried vs reused from the scheduler counters,
    membership transitions, and the byte-identity oracle against a
    pre-churn baseline on the SAME topology (a single-node baseline
    would differ in float summation order)."""
    import signal as _signal
    from presto_tpu.server.coordinator import Coordinator
    from presto_tpu.server.node import http_get
    from presto_tpu.telemetry.metrics import METRICS
    if _backend() != "cpu":
        # one process per chip: this parent has run queries and holds
        # the device, so a worker subprocess that needs it would fail
        # or hang. The HTTP worker fleet is a CPU-only topology.
        raise RuntimeError(
            "the worker-churn phase starts worker subprocesses and "
            f"runs on the CPU backend only (this is {_backend()})")
    workers = [list(_spawn_churn_worker()) for _ in range(n_workers)]
    urls = [w[1] for w in workers]
    coord = Coordinator(
        urls, "tpch", schema, host=host, port=0,
        max_concurrent_queries=max(clients, 2),
        properties={"task_retries": 2,
                    "task_partitions": 2 * n_workers,
                    "query_retries": 2,
                    # every churn query is traced: workers ship their
                    # spans with task status and the scheduler merges
                    # one fleet timeline per query — the retried-
                    # attempt evidence the timeline file carries
                    "query_trace_enabled": True},
        heartbeat_interval_s=0.25)
    stop_churn = threading.Event()
    churn_log = {"kills": 0, "respawns": 0, "errors": []}

    def churn():
        for k in range(kills):
            # between kills: wait for the previous respawn to be
            # RE-ADMITTED by the heartbeat — the churn story is one
            # loss at a time, not a cascading double failure
            deadline = time.monotonic() + max(period_s * 10, 30)
            while time.monotonic() < deadline \
                    and not stop_churn.is_set():
                if coord.membership.counts().get("active", 0) \
                        == len(workers):
                    break
                time.sleep(0.05)
            # synchronize with live traffic: the kill must land while
            # the measured phase has a query in flight (the baseline
            # phase finished before this thread started, so any
            # RUNNING query here is measured-phase work)
            deadline = time.monotonic() + max(period_s * 10, 30)
            while time.monotonic() < deadline \
                    and not stop_churn.is_set():
                if any(q.state == "RUNNING"
                       for q in list(coord.queries.values())
                       if q.done_at is None):
                    break
                time.sleep(0.02)
            if stop_churn.is_set():
                return
            i = k % len(workers)
            proc, url = workers[i]
            port = int(url.rsplit(":", 1)[1])
            try:
                proc.send_signal(_signal.SIGKILL)
                proc.wait(timeout=10)
                churn_log["kills"] += 1
            except Exception as e:  # noqa: BLE001 — recorded
                churn_log["errors"].append(repr(e))
                continue
            # the respawn is unconditional: a window that outlives
            # the phase must still restore the fleet (the teardown
            # SIGTERMs it like any other member)
            stop_churn.wait(period_s / 2)
            try:
                nproc, nurl = _spawn_churn_worker(port)
                workers[i][0] = nproc
                churn_log["respawns"] += 1
                deadline = time.monotonic() + 30
                while time.monotonic() < deadline:
                    try:
                        if json.loads(http_get(
                                f"{nurl}/v1/info", timeout=2)
                                ).get("state") == "active":
                            break
                    except Exception:  # noqa: BLE001 — still booting
                        time.sleep(0.1)
            except Exception as e:  # noqa: BLE001 — recorded
                churn_log["errors"].append(repr(e))

    try:
        coord.start()
        coord.check_workers()
        # pre-churn baseline on the SAME distributed topology: the
        # byte-identity oracle for every success under churn
        _, base_checks = _run_phase(coord.url, [list(work)],
                                    timeout_s=300.0)
        tasks0 = METRICS.by_label("presto_tpu_tasks_total", "status")
        trans0 = METRICS.by_label(
            "presto_tpu_membership_transitions_total", "to")
        churn_t = threading.Thread(target=churn, daemon=True)
        churn_t.start()
        stats, checks = _run_phase(
            coord.url, [list(work) * rounds for _ in range(clients)],
            tolerant=True, timeout_s=300.0)
        stop_churn.set()
        churn_t.join(timeout=60)
        # merged fleet timeline: pick the traced query whose timeline
        # shows the MOST task attempts (a worker died under it —
        # retried lanes + both workers' pids in one Perfetto doc)
        timeline_doc = None
        best = (-1, None)
        for q in list(coord.queries.values()):
            if not q.trace:
                continue
            pids = {e.get("pid") for e in q.trace
                    if isinstance(e.get("pid"), int)}
            attempts = len({e["name"] for e in q.trace
                            if isinstance(e.get("name"), str)
                            and e["name"].startswith("task ")
                            and " attempt " in e["name"]})
            score = attempts * 10 + len(pids)
            if score > best[0]:
                best = (score, (q, pids, attempts))
        if best[1] is not None:
            q, pids, attempts = best[1]
            timeline_doc = {
                "query_id": q.id,
                "sql": q.sql[:120],
                "events": len(q.trace),
                "pids": sorted(p for p in pids
                               if isinstance(p, int)),
                "task_attempt_lanes": attempts,
                "file": timeline_out,
            }
            if timeline_out:
                with open(timeline_out, "w") as f:
                    json.dump({
                        "displayTimeUnit": "ms",
                        "otherData": {"query_id": q.id,
                                      "sql": q.sql[:200],
                                      "phase": "worker_churn"},
                        "traceEvents": q.trace,
                    }, f)
    finally:
        stop_churn.set()
        coord.stop()
        for proc, _url in workers:
            try:
                proc.send_signal(_signal.SIGTERM)
                proc.wait(timeout=10)
            except Exception:  # noqa: BLE001 — last resort
                try:
                    proc.kill()
                except Exception:  # noqa: BLE001
                    pass
    shed = sum(v for k, v in stats.get("errors", {}).items()
               if k in SHED_KINDS)
    admitted = stats["queries"] - shed
    consistent = all(
        len(sums) == 1 and sums == base_checks.get(name)
        for name, sums in checks.items())
    doc = {
        "workers": n_workers,
        "clients": clients,
        "rounds": rounds,
        "churn": churn_log,
        "offered": stats["queries"],
        "succeeded": stats["succeeded"],
        "failed": stats["failed"],
        "shed": shed,
        "errors": stats.get("errors", {}),
        # the robustness headline: of the queries admission let in,
        # how many answered despite workers dying under them
        "availability_admitted": round(
            stats["succeeded"] / admitted, 4) if admitted else None,
        "wall_s": stats["wall_s"],
        "qps": stats["qps"],
        "p50_ms": stats["p50_ms"],
        "p99_ms": stats["p99_ms"],
        "tasks": METRICS.delta_by_label(
            "presto_tpu_tasks_total", "status", tasks0),
        "membership_transitions": METRICS.delta_by_label(
            "presto_tpu_membership_transitions_total", "to", trans0),
        "timeline": timeline_doc,
        "successes_match_baseline": consistent,
    }
    if not consistent:
        raise RuntimeError(
            "worker-churn successes diverged from the pre-churn "
            "baseline: " + json.dumps(doc, indent=1))
    return doc


def _rows_match(a, b) -> bool:
    """Mesh-vs-local result identity: exact for non-floats, suite
    tolerance for floats (the mesh's partial->final aggregation
    reassociates float sums)."""
    import math
    ra = sorted(a.rows(), key=str)
    rb = sorted(b.rows(), key=str)
    if len(ra) != len(rb):
        return False
    for x, y in zip(ra, rb):
        if len(x) != len(y):
            return False
        for u, v in zip(x, y):
            if isinstance(u, float) or isinstance(v, float):
                if not (u == v or math.isclose(
                        float(u), float(v),
                        rel_tol=1e-6, abs_tol=1e-6)):
                    return False
            elif u != v:
                return False
    return True


def _run_mesh_phase(schema: str, sqls: Dict[str, str],
                    rounds: int = 2) -> dict:
    """The --mesh phase: the serving mix executed on the sharded
    MeshRunner (shard_map fragments + all_to_all waves) vs the
    single-device LocalRunner, in process — this phase measures the
    ENGINE's mesh scaling, not the HTTP coordinator. Reports warm
    per-query latency both ways, the geomean ratio, per-device wall
    attribution summed over the mix, exchange bytes/row, and the
    fused_fragments counters the sharded planner produced.

    Honesty note (carried into the doc): on the CPU test mesh the
    "devices" are XLA virtual devices inside ONE process sharing the
    GIL and the host's cores — the ratio here is a correctness-and-
    attribution exercise, not an ICI scaling claim."""
    import math
    import time as _time

    import jax

    from presto_tpu.runner import MeshRunner
    from presto_tpu.runner.local import LocalRunner
    from presto_tpu.telemetry.metrics import METRICS

    ndev = len(jax.devices())
    w = 1
    while w * 2 <= min(8, ndev):
        w *= 2
    if w < 2:
        return {"skipped": f"{ndev} device(s) visible — the mesh "
                           "phase needs >=2 (on CPU set XLA_FLAGS="
                           "--xla_force_host_platform_device_count"
                           "=8)"}
    local = LocalRunner("tpch", schema)
    mesh = MeshRunner("tpch", schema, n_workers=w)
    ex_names = ("waves", "rows", "bytes")
    ex_before = {k: METRICS.total(
        f"presto_tpu_exchange_all_to_all_{k}_total")
        for k in ex_names}
    fused_before = METRICS.by_label(
        "presto_tpu_fused_fragments_total", "status")

    def warm_best(r, sql):
        times, res = [], None
        for _ in range(rounds + 1):  # round 0 compiles
            t0 = _time.perf_counter()
            res = r.execute(sql)
            times.append((_time.perf_counter() - t0) * 1e3)
        return min(times[1:]), res

    per_query = {}
    per_device: Dict[str, float] = {}
    ratios = []
    identical = True
    for name, sql in sqls.items():
        local_ms, lres = warm_best(local, sql)
        mesh_ms, mres = warm_best(mesh, sql)
        led = mres.query_stats.get("ledger") or {}
        for dev, cats in (led.get("per_device") or {}).items():
            per_device[dev] = per_device.get(dev, 0.0) \
                + sum(cats.values())
        ok = _rows_match(lres, mres)
        identical = identical and ok
        ratio = (local_ms / mesh_ms) if mesh_ms else None
        per_query[name] = {
            "local_warm_ms": round(local_ms, 1),
            "mesh_warm_ms": round(mesh_ms, 1),
            "mesh_vs_local": round(ratio, 3) if ratio else None,
            "identical": ok,
        }
        if ratio:
            ratios.append(ratio)
    ex = {k: int(METRICS.total(
        f"presto_tpu_exchange_all_to_all_{k}_total") - ex_before[k])
        for k in ex_names}
    doc = {
        "n_devices": w,
        "rounds": rounds,
        "geomean_mesh_vs_local": round(math.exp(
            sum(math.log(r) for r in ratios) / len(ratios)), 3)
        if ratios else None,
        "caveat": "CPU virtual-device mesh in one GIL-bound process "
                  "— attribution/correctness figure, not an ICI "
                  "scaling claim",
        "queries": per_query,
        "results_identical": identical,
        "per_device_ms": {d: round(ms, 1) for d, ms in
                          sorted(per_device.items())},
        "exchange": {
            "all_to_all_waves": ex["waves"],
            "all_to_all_rows": ex["rows"],
            "all_to_all_bytes": ex["bytes"],
            "bytes_per_row": round(ex["bytes"] / ex["rows"], 2)
            if ex["rows"] else None,
        },
        "fused_fragments": METRICS.delta_by_label(
            "presto_tpu_fused_fragments_total", "status",
            fused_before),
    }
    if not identical:
        raise RuntimeError(
            "mesh phase diverged from single-device results: "
            + json.dumps(doc, indent=1))
    return doc


def _load_mix(mix: Sequence[str]) -> Dict[str, str]:
    from presto_tpu.tools.verifier import load_suite
    suite = load_suite("tpch")
    missing = [m for m in mix if m not in suite]
    if missing:
        raise ValueError(f"unknown mix queries {missing}")
    return {m: suite[m] for m in mix}


def run_serving_bench(clients: int = 4, schema: str = "sf0_1",
                      mix: Sequence[str] = DEFAULT_MIX,
                      warm_rounds: int = 3,
                      flight_ab_rounds: int = 3,
                      verify_off: bool = True,
                      chaos: bool = False,
                      chaos_rounds: int = 2,
                      chaos_spec: str = DEFAULT_CHAOS_SPEC,
                      restart_warm: bool = False,
                      cache_dir: Optional[str] = None,
                      fusion_report: bool = False,
                      overload: bool = False,
                      overload_rounds: int = 2,
                      overload_concurrency: Optional[int] = None,
                      sanitize_phase: bool = False,
                      history_phase: bool = False,
                      worker_churn: bool = False,
                      churn_workers: int = 2,
                      churn_rounds: int = 2,
                      churn_kills: int = 1,
                      churn_period_s: float = 3.0,
                      timeline_out: Optional[str] = None,
                      assert_verdict: Optional[str] = None,
                      host: str = "127.0.0.1",
                      mesh_phase: bool = False,
                      mesh_rounds: int = 2) -> dict:
    """`cache_dir` is an explicit persistent-compilation-cache
    override; without it the coordinators follow the one rule of
    execution/compile_cache.configure
    (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache off the
    CPU). A --restart-warm run therefore needs --cache-dir on a CPU
    backend, which does not default into the cache."""
    from presto_tpu.cache import get_cache_manager
    from presto_tpu.execution import compile_cache
    from presto_tpu.server.coordinator import Coordinator
    sqls = _load_mix(mix)
    work = list(sqls.items())

    if cache_dir:
        # the cold/warm phases populate this persistent cache; the
        # restart-warm phase re-traces against it after the wipe
        compile_cache.configure_compilation_cache(cache_dir)

    mgr = get_cache_manager()
    mgr.clear()
    coord = Coordinator([], "tpch", schema, host=host, port=0,
                        max_concurrent_queries=clients,
                        single_node=True)
    coord.start()
    chaos_doc = None
    try:
        # cold: each query exactly once, spread over the clients
        cold_assign = [work[i::clients] for i in range(clients)]
        cold, cold_checks = _run_phase(coord.url, cold_assign,
                                       coord=coord)
        # warm: every client hammers the full mix
        warm_assign = [list(work) * warm_rounds
                       for _ in range(clients)]
        warm, warm_checks = _run_phase(coord.url, warm_assign,
                                       coord=coord)
        # serving-mix diagnosis (and the --assert-verdict CI gate)
        # over the warm phase's aggregated attribution ledger
        doctor = _doctor_verdict(warm, assert_verdict)
        # critical-path phase: each mix query once, traced, with the
        # blocking-chain sum-to-wall invariant machine-checked
        critical = _run_critical_path_phase(coord, work)
        # flight-recorder overhead A/B: ALTERNATING warm rounds with
        # recording on/off, medians compared (single adjacent rounds
        # on a loaded 1-core box are dominated by run-to-run noise —
        # alternation + median isolates the recorder's own cost).
        # Always-on must cost <= ~5% warm QPS, measured not asserted.
        import statistics
        from presto_tpu.telemetry import flight as _flight
        one_round = [list(work) for _ in range(clients)]
        on_qps: List[float] = []
        off_qps: List[float] = []
        flight_checks: Dict[str, set] = {}
        flight_off_checks: Dict[str, set] = {}
        try:
            for _ in range(max(1, flight_ab_rounds)):
                _flight.ENABLED = True
                s_on, c_on = _run_phase(coord.url, one_round)
                on_qps.append(s_on["qps"])
                for k, v in c_on.items():
                    flight_checks.setdefault(k, set()).update(v)
                _flight.ENABLED = False
                s_off, c_off = _run_phase(coord.url, one_round)
                off_qps.append(s_off["qps"])
                for k, v in c_off.items():
                    flight_off_checks.setdefault(k, set()).update(v)
        finally:
            _flight.ENABLED = True
        med_on = statistics.median(on_qps)
        med_off = statistics.median(off_qps)
        flight_doc = {
            "qps_flight_on": med_on,
            "qps_flight_off": med_off,
            "qps_rounds_on": on_qps,
            "qps_rounds_off": off_qps,
            "overhead_frac": round(1.0 - med_on / med_off, 4)
            if med_off else None,
            "ring": _flight.stats(),
        }
        if chaos:
            # chaos: the SAME coordinator (warm caches, live resource
            # groups) under seeded periodic faults
            from presto_tpu.execution import faults
            faults.disarm()
            for kw in faults.parse_spec(chaos_spec):
                faults.arm(**kw)
            try:
                chaos_assign = [list(work) * chaos_rounds
                                for _ in range(clients)]
                chaos_stats, chaos_checks = _run_phase(
                    coord.url, chaos_assign, tolerant=True,
                    timeout_s=120.0)
            finally:
                faults.disarm()
            # correctness oracle: every SUCCESS under chaos must be
            # byte-identical to the warm phase's answer
            consistent = all(
                len(sums) == 1 and sums == warm_checks.get(name)
                for name, sums in chaos_checks.items())
            chaos_doc = {
                "spec": chaos_spec,
                "rounds": chaos_rounds,
                **chaos_stats,
                "successes_match_warm": consistent,
            }
            if not consistent:
                raise RuntimeError(
                    "chaos-phase successes diverged from warm "
                    "results: " + json.dumps(chaos_doc, indent=1))
    finally:
        coord.stop()

    overload_doc = None
    if overload:
        # a FRESH coordinator with admission caps far below the
        # client count (warm process-wide caches ride along): the
        # offered load must be absorbed as structured sheds while
        # admitted queries keep answering byte-identically
        cap = overload_concurrency or max(2, clients // 8)
        ov_coord = Coordinator(
            [], "tpch", schema, host=host, port=0,
            max_concurrent_queries=cap,
            max_queued_queries=cap * 2, single_node=True,
            properties={"admission_queue_timeout_ms": 30_000})
        ov_coord.start()
        try:
            ov_stats, ov_checks = _run_overload_phase(
                ov_coord.url, ov_coord.resource_groups, clients,
                work, overload_rounds)
        finally:
            ov_coord.stop()
        ov_consistent = all(
            len(sums) == 1 and sums == warm_checks.get(name)
            for name, sums in ov_checks.items())
        overload_doc = {
            "clients": clients,
            "rounds": overload_rounds,
            "max_concurrent": cap,
            "max_queued": cap * 2,
            **ov_stats,
            "successes_match_warm": ov_consistent,
        }
        if not ov_consistent:
            raise RuntimeError(
                "overload-phase successes diverged from warm "
                "results: " + json.dumps(overload_doc, indent=1))

    sanitize_doc = None
    if sanitize_phase:
        # the warm mix once more with the concurrency sanitizer fully
        # armed on a FRESH coordinator + executor (both built under
        # the sanitizer so their locks are order-tracked): reports
        # violations and the armed-vs-disarmed wall delta alongside
        # QPS, so future fleet/mesh benches carry sanitizer status
        from presto_tpu import sanitize as _san
        from presto_tpu.tools.sanitize import _drain, _fresh_executor
        was_armed = _san.ARMED  # an env-armed run must stay armed
        _san.arm()
        restore_executor = _fresh_executor()
        try:
            san_coord = Coordinator(
                [], "tpch", schema, host=host, port=0,
                max_concurrent_queries=clients, single_node=True)
            san_coord.start()
            try:
                san_stats, san_checks = _run_phase(
                    san_coord.url,
                    [list(work) for _ in range(clients)])
                # settle: the last query's slot release races the
                # client's final poll — the quiescent audit needs
                # the ledger drained
                _drain(san_coord)
            finally:
                san_coord.stop()
            violations = [str(v) for v in _san.audit(
                raise_=False, coordinator_check=True)]
            edges = len(_san.lock_order_edges())
        finally:
            restore_executor()
            if not was_armed:
                _san.disarm()
        san_consistent = all(
            len(sums) == 1 and sums == warm_checks.get(name)
            for name, sums in san_checks.items())
        sanitize_doc = {
            **san_stats,
            "violations": violations,
            "violation_count": len(violations),
            "lock_order_edges": edges,
            "armed_vs_warm_qps": round(
                san_stats["qps"] / warm["qps"], 3)
            if warm.get("qps") and san_stats.get("qps") else None,
            "successes_match_warm": san_consistent,
        }
        if violations or not san_consistent:
            raise RuntimeError(
                "sanitize phase failed (violations or divergence): "
                + json.dumps(sanitize_doc, indent=1))

    def _consistent(*phases: Dict[str, set]) -> bool:
        """One checksum per query per phase, identical across phases
        — every repetition of every phase participates."""
        for name in {n for p in phases for n in p}:
            union = set()
            for p in phases:
                sums = p.get(name)
                if not sums or len(sums) != 1:
                    return False
                union |= sums
            if len(union) != 1:
                return False
        return True

    identical = _consistent(cold_checks, warm_checks, flight_checks,
                            flight_off_checks)
    off = None
    if verify_off:
        off_coord = Coordinator(
            [], "tpch", schema, host=host, port=0,
            max_concurrent_queries=clients, single_node=True,
            properties={"plan_cache_enabled": False,
                        "fragment_result_cache_enabled": False,
                        "page_source_cache_enabled": False})
        off_coord.start()
        try:
            off, off_checks = _run_phase(
                off_coord.url, [work[i::clients]
                                for i in range(clients)],
                coord=off_coord)
        finally:
            off_coord.stop()
        identical = identical and _consistent(cold_checks, off_checks)

    restart = None
    if restart_warm:
        # simulate a coordinator process restart: every in-process
        # compiled-kernel layer is wiped (engine LRUs + jax jit
        # caches) along with the serving caches — the ONLY warm thing
        # left is the persistent XLA cache on disk. The new
        # coordinator AOT-prewarms the mix at start(), so the measured
        # phase must perform zero fresh compiles.
        mgr.clear()
        compile_cache.clear_kernel_caches()
        coord2 = Coordinator(
            [], "tpch", schema, host=host, port=0,
            max_concurrent_queries=clients, single_node=True,
            prewarm_sql=[sql for _, sql in work])
        t0 = time.perf_counter()
        coord2.start()  # blocks through the prewarm pass
        startup_s = time.perf_counter() - t0
        try:
            rw_assign = [list(work) * warm_rounds
                         for _ in range(clients)]
            rw, rw_checks = _run_phase(coord2.url, rw_assign,
                                       coord=coord2)
        finally:
            coord2.stop()
        identical = identical and _consistent(warm_checks, rw_checks)
        restart = {
            **rw,
            "startup_s": round(startup_s, 3),
            "prewarm": coord2.prewarm_report,
            "qps_vs_warm": round(rw["qps"] / warm["qps"], 3)
            if warm.get("qps") else None,
            "compilation_cache_dir": cache_dir
            or coord2.prewarm_report.get("disk_cache_dir"),
        }
        if rw["fresh_compiles"] != 0:
            # the restart-warm CONTRACT: prewarm + the persistent
            # cache absorb every re-trace before traffic — a compile
            # in the measured phase means a shape escaped the ladder
            raise RuntimeError(
                "restart-warm phase performed fresh compiles: "
                + json.dumps(restart["distinct_compiles"]))

    history_doc = None
    if history_phase:
        # history-based optimization phase: a FRESH (empty) store so
        # first-vs-second-run deltas are attributable, then each mix
        # query measured and re-planned — emitting plan deltas,
        # fusion upgrades, and the history counter growth
        from presto_tpu import history as _history
        from presto_tpu.telemetry.metrics import METRICS
        from presto_tpu.tools.history_report import (
            build_report as history_build,
        )
        _history.reset_history_store()
        names = ("hits", "misses", "records")
        before = {k: METRICS.total(f"presto_tpu_history_{k}_total")
                  for k in names}
        hr = history_build(sqls, "tpch", schema)
        history_doc = {
            "plans_changed": hr["plans_changed"],
            "fusion_upgraded": hr["fusion_upgraded"],
            "results_identical": hr["all_identical"],
            "history_estimates": {
                n: q["history_estimates"]
                for n, q in hr["queries"].items()},
            "fusion_first_vs_second": {
                n: [q["fusion_first"], q["fusion_second"]]
                for n, q in hr["queries"].items()},
            "store_entries": len(hr["store"]),
            "counters": {
                f"presto_tpu_history_{k}_total": int(
                    METRICS.total(f"presto_tpu_history_{k}_total")
                    - before[k])
                for k in names},
        }
        if not hr["all_identical"]:
            raise RuntimeError(
                "history phase diverged (history-on plans must stay "
                "byte-identical): "
                + json.dumps(history_doc, indent=1))

    churn_doc = None
    if worker_churn:
        # the fleet-robustness phase: real worker subprocesses dying
        # and respawning under live traffic, absorbed by the
        # task-retry tier (server/scheduler.py)
        churn_doc = _run_worker_churn_phase(
            schema, work, clients, churn_rounds, churn_workers,
            churn_kills, churn_period_s, host,
            timeline_out=timeline_out)

    fusion = None
    if fusion_report:
        # per-query fragments fused vs fallen back (with reasons) —
        # observed on a caches-off runner so fragment-cache replays
        # can't hide the chains the pass would have seen
        from presto_tpu.runner.local import LocalRunner
        from presto_tpu.tools.fusion_report import build_report
        fr_runner = LocalRunner("tpch", schema, properties={
            "plan_cache_enabled": False,
            "fragment_result_cache_enabled": False,
            "page_source_cache_enabled": False})
        fusion = build_report(fr_runner, sqls)

    mesh_doc = None
    if mesh_phase:
        # the sharded-execution phase: shard_map fragments +
        # all_to_all waves vs the single-device engine, in process
        # (docs/SHARDING.md)
        mesh_doc = _run_mesh_phase(schema, sqls, rounds=mesh_rounds)

    cache_stats = {name: level.stats.snapshot() for name, level in
                   (("plan", mgr.plan), ("fragment", mgr.fragment),
                    ("page", mgr.page))}
    doc = {
        # STABLE headline shape (CI greps these five keys — see
        # kernel_bench): metric/value/unit/platform/vs
        "metric": "tpch_serving_warm_qps",
        "value": warm["qps"],
        "unit": "qps",
        "platform": _backend(),
        "speedup_warm_vs_cold": round(warm["qps"] / cold["qps"], 2)
        if cold["qps"] else None,
        "clients": clients,
        "schema": schema,
        "mix": list(mix),
        "warm_rounds": warm_rounds,
        "cold": cold,
        "warm": warm,
        "doctor": doctor,
        "critical_path": critical,
        "flight_overhead": flight_doc,
        "caches_off": off,
        "restart_warm": restart,
        "overload": overload_doc,
        "results_identical": identical,
        "cache": cache_stats,
        "chaos": chaos_doc,
        "sanitize": sanitize_doc,
        "fusion": fusion,
        "history": history_doc,
        "worker_churn": churn_doc,
        "mesh": mesh_doc,
    }
    if not identical:
        raise RuntimeError(
            "serving bench results differ between phases: "
            + json.dumps(doc, indent=1))
    return doc


def _backend() -> str:
    import jax
    return jax.default_backend()


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        description="Concurrent serving benchmark (cold vs warm QPS)")
    p.add_argument("--clients", type=int, default=4)
    p.add_argument("--schema", default="sf0_1")
    p.add_argument("--mix", default=",".join(DEFAULT_MIX))
    p.add_argument("--warm-rounds", type=int, default=3)
    p.add_argument("--flight-ab-rounds", type=int, default=3,
                   help="alternating on/off round PAIRS of the "
                        "flight-recorder overhead A/B (medians "
                        "compared)")
    p.add_argument("--skip-off", action="store_true",
                   help="skip the caches-disabled equivalence phase")
    p.add_argument("--chaos", action="store_true",
                   help="run a seeded fault-injection phase and "
                        "report availability + error taxonomy")
    p.add_argument("--chaos-rounds", type=int, default=2)
    p.add_argument("--chaos-spec", default=DEFAULT_CHAOS_SPEC,
                   help="fault spec (site:trigger[:arg][:seed];...)")
    p.add_argument("--restart-warm", action="store_true",
                   help="wipe every in-process kernel cache, rebuild "
                        "the coordinator with AOT prewarm against the "
                        "persistent XLA cache, and measure the "
                        "restart-warm phase (must show zero fresh "
                        "compiles)")
    p.add_argument("--cache-dir", default=None,
                   help="persistent XLA compilation cache directory "
                        "(default: a fresh tmpdir when --restart-warm)")
    p.add_argument("--overload", action="store_true",
                   help="run an offered-load > capacity phase against "
                        "tight admission caps: sheds by kind, "
                        "per-user p50/p99, queue-depth peaks, "
                        "availability of admitted queries")
    p.add_argument("--overload-rounds", type=int, default=2)
    p.add_argument("--overload-concurrency", type=int, default=None,
                   help="hard concurrency cap of the overload "
                        "coordinator (default: clients // 8)")
    p.add_argument("--sanitize", action="store_true",
                   help="run the warm mix once more with the "
                        "concurrency sanitizer fully armed (fresh "
                        "coordinator + executor): reports violations "
                        "and the armed-vs-disarmed wall delta in the "
                        "JSON")
    p.add_argument("--history", action="store_true",
                   help="run the history-based-optimization phase: "
                        "fresh store, measure + re-plan each mix "
                        "query, emit first-vs-second plan deltas, "
                        "fusion upgrades, and history counters")
    p.add_argument("--worker-churn", action="store_true",
                   help="run the fleet-churn phase: a multi-worker "
                        "coordinator with task-level retries serves "
                        "the mix while one worker per window is "
                        "SIGKILLed and respawned; reports admitted "
                        "availability, tasks retried vs reused, and "
                        "the byte-identity oracle")
    p.add_argument("--churn-workers", type=int, default=2)
    p.add_argument("--churn-rounds", type=int, default=2)
    p.add_argument("--churn-kills", type=int, default=1)
    p.add_argument("--churn-period", type=float, default=3.0,
                   help="seconds between churn events")
    p.add_argument("--timeline-out", default="fleet_timeline.json",
                   help="file the --worker-churn phase writes the "
                        "merged Perfetto fleet timeline to")
    p.add_argument("--fusion-report", action="store_true",
                   help="embed the per-query whole-fragment fusion "
                        "coverage (fused chains + fallback reasons, "
                        "tools/fusion_report.py) in the output JSON")
    p.add_argument("--assert-verdict", default=None,
                   choices=("queueing", "kernel", "exchange", "glue"),
                   help="fail the bench unless query_doctor's verdict "
                        "over the warm serving-mix ledger is this "
                        "category (the CI gate that keeps serving "
                        "kernel-dominated)")
    p.add_argument("--mesh", action="store_true",
                   help="run the sharded-execution phase: the mix on "
                        "the MeshRunner vs single device, with "
                        "per-device attribution and exchange "
                        "bytes/row (docs/SHARDING.md)")
    p.add_argument("--mesh-rounds", type=int, default=2)
    p.add_argument("--check-regressions", action="store_true",
                   help="after the run, diff this capture against the "
                        "newest checked-in BENCH_SERVING_r*.json with "
                        "tools/perf_diff.py's structural gates; a "
                        "regression makes the bench exit nonzero")
    p.add_argument("--regression-ref", default=None,
                   help="explicit reference capture for "
                        "--check-regressions (default: the newest "
                        "BENCH_SERVING_r*.json in the cwd)")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    doc = run_serving_bench(
        clients=args.clients, schema=args.schema,
        mix=[m.strip() for m in args.mix.split(",") if m.strip()],
        warm_rounds=args.warm_rounds,
        flight_ab_rounds=args.flight_ab_rounds,
        verify_off=not args.skip_off,
        chaos=args.chaos, chaos_rounds=args.chaos_rounds,
        chaos_spec=args.chaos_spec, restart_warm=args.restart_warm,
        cache_dir=args.cache_dir, fusion_report=args.fusion_report,
        overload=args.overload, overload_rounds=args.overload_rounds,
        overload_concurrency=args.overload_concurrency,
        sanitize_phase=args.sanitize, history_phase=args.history,
        worker_churn=args.worker_churn,
        churn_workers=args.churn_workers,
        churn_rounds=args.churn_rounds,
        churn_kills=args.churn_kills,
        churn_period_s=args.churn_period,
        timeline_out=args.timeline_out,
        assert_verdict=args.assert_verdict,
        mesh_phase=args.mesh, mesh_rounds=args.mesh_rounds)
    text = json.dumps(doc, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.check_regressions:
        # the sentinel's CI gate: structural (load-invariant) diff of
        # this capture against the previous round's
        import glob as _glob
        import re as _re
        from presto_tpu.tools.perf_diff import (
            _load_baseline, _render, diff_captures,
        )
        ref_path = args.regression_ref
        if ref_path is None:
            # newest checked-in round that is NOT this run's output —
            # a fresh capture must diff against its predecessor
            own = os.path.abspath(args.out) if args.out else None
            rounds = sorted(
                (p_ for p_ in _glob.glob("BENCH_SERVING_r*.json")
                 if os.path.abspath(p_) != own),
                key=lambda p_: int(
                    (_re.search(r"_r(\d+)", p_) or [0, 0])[1]))
            ref_path = rounds[-1] if rounds else None
        if ref_path is None:
            print("check-regressions: no reference capture found")
        else:
            with open(ref_path) as f:
                ref_doc = json.load(f)
            out = diff_captures(ref_doc, doc, _load_baseline(None))
            print(f"check-regressions vs {ref_path}:")
            print(_render(out))
            if out["regressions"]:
                return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
