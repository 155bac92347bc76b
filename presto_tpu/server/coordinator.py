"""Coordinator: dispatch + scheduling + client protocol (reference:
dispatcher/DispatchManager.java:143, execution/scheduler/
SqlQueryScheduler.java:114, server/protocol/QueuedStatementResource
.java:156 / ExecutingStatementResource.java:73, and presto-client's
StatementClientV1 nextUri loop).

The coordinator plans and fragments a query, POSTs one task per worker
per distributed fragment (task spec = SQL + session + fragment id — the
worker re-derives the deterministic plan), runs the single-partition
fragments itself (root output lands here), and serves the two-phase
queued/executing client protocol.
"""

from __future__ import annotations

import json
import os
import threading
import time
import uuid
from typing import Dict, List, Optional

from presto_tpu import sanitize
from presto_tpu.execution import faults
from presto_tpu.telemetry.metrics import METRICS
from presto_tpu.server.node import (
    TRANSPORT_RETRIES, Node, build_http_exchanges, derive_fragments,
    http_delete, http_get, http_post,
)
from presto_tpu.server.scheduler import (
    HeartbeatMonitor, StageScheduler, TaskOutputSpool,
)


class TaskFailed(RuntimeError):
    """A remote task failed; carries the structured retry hint when
    the failure is one of the engine's sync-free overflow errors, and
    the worker url when the failure implicates the WORKER (unreachable
    / connection-level) rather than the query — the elastic retry
    loop blacklists implicated workers for the query's later attempts
    even if their /v1/info recovers (a flapping worker must not be
    re-picked)."""

    def __init__(self, message: str, kind: Optional[str] = None,
                 suggested: Optional[int] = None,
                 worker: Optional[str] = None):
        super().__init__(message)
        self.kind = kind
        self.suggested = suggested
        self.worker = worker


class QueryFailed(RuntimeError):
    """Client-side structured failure (reference: presto-client's
    QueryError): `kind` carries the engine's failure taxonomy
    ("cancelled", "deadline_exceeded", "abandoned", "client_timeout",
    or None)."""

    def __init__(self, message: str, kind: Optional[str] = None,
                 query_id: Optional[str] = None):
        super().__init__(message)
        self.kind = kind
        self.query_id = query_id


class QueryCancelled(QueryFailed):
    """The query was killed (client DELETE / abandonment)."""


class QueryTimedOut(QueryFailed):
    """Client-side poll timeout or server-side deadline. When the
    CLIENT times out it first issues the kill, so the server stops
    burning coordinator/worker/cache budget on an answer nobody will
    read."""


class QueryLifecycle:
    """Per-query control surface threaded from the coordinator's
    client protocol down to every drive loop: the cooperative cancel
    event, the monotonic deadline, the live attempt's remote tasks
    (so a kill can fan out task DELETEs immediately instead of
    waiting a drive round), and the attempt counter chaos tests
    assert on (a transient exchange fault absorbed below this tier
    must leave attempts == 1)."""

    def __init__(self, cancel: Optional[threading.Event] = None,
                 deadline: Optional[float] = None,
                 query_id: str = ""):
        self.cancel = cancel if cancel is not None \
            else threading.Event()
        self.deadline = deadline
        #: the client-visible query id: the statement's ledger frames
        #: carry it on the profiler's timeline (telemetry/ledger.py)
        self.query_id = query_id
        #: (task_id, worker_url) of the CURRENT attempt
        self.remote: List[tuple] = []
        self.attempts = 0
        #: WHY the cancel event was set ("cancelled" vs "abandoned")
        #: — the drive loop only knows it was told to stop
        self.kill_kind: Optional[str] = None

    def abort_remote(self) -> None:
        """Best-effort DELETE of the live attempt's worker tasks —
        idempotent with the attempt's own release path."""
        for task_id, wurl in list(self.remote):
            try:
                http_delete(f"{wurl}/v1/task/{task_id}", timeout=5)
            except Exception:  # noqa: BLE001 — best-effort abort
                pass


def _retry_hint(e: Exception):
    """(property_name, suggested) when the error asks for a re-run
    with a raised setting; (None, None) otherwise."""
    from presto_tpu.operators.aggregation import GroupLimitExceeded
    from presto_tpu.operators.join_ops import JoinCapacityExceeded
    if isinstance(e, JoinCapacityExceeded):
        return "join_expansion_factor", e.suggested
    if isinstance(e, GroupLimitExceeded):
        return "max_groups", e.suggested
    if isinstance(e, TaskFailed) and e.kind == "join_capacity":
        return "join_expansion_factor", e.suggested
    if isinstance(e, TaskFailed) and e.kind == "group_limit":
        return "max_groups", e.suggested
    return None, None


class _Query:
    def __init__(self, sql: str):
        self.id = uuid.uuid4().hex[:16]
        self.sql = sql
        self.state = "QUEUED"
        self.error: Optional[str] = None
        self.error_kind: Optional[str] = None
        self.columns: Optional[List[dict]] = None
        self.data: Optional[List[list]] = None
        self.done_at: Optional[float] = None  # set at terminal state
        #: when the answer's tail page (the one without nextUri) was
        #: first handed to the client: `served_ms` in the stats tree
        self.served_at: Optional[float] = None
        self.user = ""
        self.source = ""
        self.group = "root"
        self.dispatch = None  # resource-group dispatch callback
        self.last_poll = time.monotonic()
        self.created_at = time.monotonic()
        self.run_started_at: Optional[float] = None  # leaves QUEUED
        #: queue-wait deadline (monotonic) + the structured kind an
        #: expiry sheds with (see Coordinator._stamp_queue_deadline)
        self.queue_deadline: Optional[float] = None
        self.queue_shed_kind: Optional[str] = None
        self.lifecycle = QueryLifecycle(query_id=self.id)
        #: QueryStats tree (telemetry.build_query_stats) — served by
        #: GET /v1/query/{id} and shipped to event listeners
        self.stats: Optional[dict] = None
        #: Chrome trace_event list when the query was traced
        self.trace: Optional[list] = None
        #: flight-recorder window snapshotted at failure (the always-
        #: on post-mortem; served on GET /v1/query/{id} and in the
        #: FAILED statement payload)
        self.flight: Optional[list] = None


#: result rows per client page (reference: the target-result-size
#: paging of ExecutingStatementResource)
PAGE_ROWS = 4096


class Coordinator(Node):
    """Admission control runs through hierarchical RESOURCE GROUPS
    (reference: execution/resourceGroups/InternalResourceGroup +
    DispatchManager.java:167): the client's X-Presto-User /
    X-Presto-Source headers route each query to a leaf group via the
    configured selectors; per-group concurrency/memory caps gate
    execution, per-group queue bounds reject overload, and releases
    dispatch queued queries weighted-fair across leaves. The default
    configuration (no `resource_groups` argument) is one root group
    sized by max_concurrent_queries / max_queued_queries — the old
    single-semaphore behavior, expressed as the trivial hierarchy."""

    def __init__(self, worker_urls: List[str],
                 catalog: str = "tpch", schema: str = "tiny",
                 properties: Optional[dict] = None,
                 host: str = "127.0.0.1", port: int = 0,
                 max_concurrent_queries: int = 4,
                 max_queued_queries: int = 100,
                 resource_groups=None, selectors=None,
                 access_control=None, single_node: bool = False,
                 prewarm_sql: Optional[List[str]] = None,
                 compilation_cache_dir: Optional[str] = None,
                 history_dir: Optional[str] = None,
                 heartbeat_interval_s: float = 1.0):
        from presto_tpu.execution import compile_cache
        # history-based optimization store (same surface shape as the
        # compile cache: arg > env > unset); the embedded single-node
        # runner and the coordinator's own root drives share the ONE
        # process-wide store through this configuration
        from presto_tpu import history as _history
        if history_dir is not None:
            _history.configure(history_dir)
        else:
            _history.configure_from_env()
        from presto_tpu.execution.resource_groups import (
            GroupSpec, ResourceGroupManager,
        )
        super().__init__(host, port)
        # compile-amortization config (docs/COMPILATION.md): a
        # persistent XLA cache dir (arg > env > unset) and an optional
        # warmup statement list replayed at start() BEFORE the server
        # takes traffic, so restart-warm serving compiles nothing
        compile_cache.configure(compilation_cache_dir)
        if prewarm_sql is None:
            prewarm_sql = compile_cache.parse_prewarm_sql(
                os.environ.get(compile_cache.ENV_PREWARM_SQL))
        self.prewarm_sql = list(prewarm_sql or [])
        #: prewarm(...) report from the last start(), for /v1/info
        #: consumers and the serving bench
        self.prewarm_report: Optional[dict] = None
        self.worker_urls = list(worker_urls)
        #: single-node serving mode: no workers — every query runs on
        #: ONE shared in-process LocalRunner behind the same HTTP
        #: client protocol + resource-group admission. This is the
        #: serving-bench topology: the shared runner is what lets the
        #: plan/fragment/page cache hierarchy serve repeat traffic
        #: (a per-query runner would still warm the process-wide
        #: caches, but session state like PREPARE would not stick).
        self.single_node = single_node
        self._embedded_runner = None
        self._embedded_lock = sanitize.lock("coordinator.embedded")
        self.catalog = catalog
        self.schema = schema
        self.properties = dict(properties or {})
        self.queries: Dict[str, _Query] = {}
        if resource_groups is None:
            resource_groups = GroupSpec(
                "root", hard_concurrency=max_concurrent_queries,
                max_queued=max_queued_queries)
        self.resource_groups = ResourceGroupManager(
            resource_groups, selectors)
        #: table-level access control applied at analysis, with the
        #: client's X-Presto-User identity (None = allow all)
        self.access_control = access_control
        #: event listener SPI (reference: spi/eventlistener/
        #: EventListener + EventListenerManager.java): callables
        #: receiving {"event": "query_created"|"query_completed", ...};
        #: listener errors never fail queries
        self.event_listeners: List = []
        #: periodic pruner (reference: DispatchManager's scheduled
        #: query-abandonment sweep): abandonment must fire on an
        #: OTHERWISE-IDLE coordinator too — with pruning only on new
        #: statement POSTs, a lone client that submitted and died
        #: would leave its RUNNING query burning to completion
        self._pruner_stop = threading.Event()
        self._pruner = sanitize.thread(
            target=self._prune_loop, daemon=True, owner=self,
            stop_signal=self._pruner_stop.is_set,
            purpose="coordinator-pruner")
        # -- fleet control plane (server/scheduler.py) -----------------
        #: durable stage-boundary exchange store for fault-tolerant
        #: task retries (session property task_retries > 0)
        self.task_spool = TaskOutputSpool()
        #: cluster-wide memory gate fed by heartbeat reports (session
        #: property fleet_memory_bytes); None = unenforced
        from presto_tpu.session_properties import get_property
        fleet_budget = get_property(self.properties,
                                    "fleet_memory_bytes")
        self.fleet_memory = None
        if fleet_budget:
            from presto_tpu.execution.cluster_memory import (
                FleetMemoryEnforcer,
            )
            self.fleet_memory = FleetMemoryEnforcer(int(fleet_budget))
        #: background heartbeat failure detector over the worker
        #: fleet — a LIVE membership view instead of the static
        #: worker_urls list checked once; started with the server
        self.membership: Optional[HeartbeatMonitor] = None
        if self.worker_urls and not self.single_node:
            self.membership = HeartbeatMonitor(
                self.worker_urls, interval_s=heartbeat_interval_s,
                memory_sink=self.fleet_memory)
        sanitize.track("coordinator", self)

    def start(self) -> None:
        # AOT prewarm completes BEFORE the HTTP thread serves (the
        # whole point: the first client query after a restart finds
        # warm kernels, never races the warmup for the shared
        # runner). On the worker topology the statements fan out to
        # every worker's /v1/prewarm so ITS kernel caches warm too —
        # per-worker compile counts land in the aggregate report and
        # on each worker's /v1/info
        if self.prewarm_sql:
            if self.single_node:
                from presto_tpu.execution import compile_cache
                self.prewarm_report = compile_cache.prewarm(
                    self._runner(), self.prewarm_sql)
            else:
                self.prewarm_report = self._prewarm_workers()
        from presto_tpu.session_properties import get_property
        if self.single_node and int(get_property(
                self.properties, "mesh_devices")) > 1:
            # a deployment's layout is met at start or not at all: a
            # mesh wider than the visible devices raises here, before
            # the first client is told the server is up
            self._runner()
        super().start()
        self._pruner.start()
        if self.membership is not None:
            self.membership.start()

    def stop(self) -> None:
        self._pruner_stop.set()
        if self.membership is not None:
            self.membership.stop()
        super().stop()
        # join the pruner: before this, a stopped coordinator leaked
        # its pruner thread for up to one 15s sweep period — the
        # first finding of the armed full-suite thread-leak audit
        if self._pruner.is_alive():
            self._pruner.join(timeout=5)
        # spool files must not outlive the coordinator
        self.task_spool.close()

    def _prewarm_workers(self) -> dict:
        """Distributed AOT prewarm (closes the 'workers start cold'
        gap): POST the warmup statements to every worker's
        /v1/prewarm concurrently; each replays them through a local
        runner against ITS kernel caches. Per-worker failures are
        recorded, never raised — the fleet must come up even if one
        member's warmup rots."""
        from concurrent.futures import ThreadPoolExecutor
        from presto_tpu.telemetry.metrics import METRICS
        body = json.dumps({
            "statements": self.prewarm_sql,
            "catalog": self.catalog, "schema": self.schema,
            "properties": self.properties,
        }).encode()

        def warm(url):
            try:
                report = json.loads(http_post(
                    f"{url}/v1/prewarm", body, timeout=600))
                METRICS.inc("presto_tpu_prewarm_statements_total",
                            value=len(self.prewarm_sql),
                            status="worker_ok")
                return url, report
            except Exception as e:  # noqa: BLE001 — best-effort
                METRICS.inc("presto_tpu_prewarm_statements_total",
                            value=len(self.prewarm_sql),
                            status="worker_failed")
                return url, {"error": f"{type(e).__name__}: {e}"}
        with ThreadPoolExecutor(
                max_workers=max(len(self.worker_urls), 1)) as pool:
            workers = dict(pool.map(warm, self.worker_urls))
        return {
            "statements": len(self.prewarm_sql),
            "workers": workers,
            "failed": [u for u, r in workers.items() if "error" in r],
        }

    def _prune_loop(self, period_s: float = 15.0) -> None:
        while not self._pruner_stop.wait(period_s):
            try:
                self._prune_queries()
            except Exception:  # noqa: BLE001 — the sweep must outlive
                pass           # any one bad query entry

    def _fire_event(self, payload: dict) -> None:
        for listener in self.event_listeners:
            try:
                listener(payload)
            except Exception:  # noqa: BLE001 — observers cannot fail
                pass          # the query (EventListenerManager.java)

    # -- health / membership (reference: failureDetector/
    # HeartbeatFailureDetector pinging discovered nodes) ---------------

    def check_workers(self, require_all: bool = False,
                      timeout: float = 5.0) -> Dict[str, str]:
        """Probe every worker CONCURRENTLY (a dead worker costs the
        caller at most one timeout, not one per worker) and return
        {url: state} with the dead ones reported as
        "unreachable: ...". Degradation-tolerant by default — the
        coordinator starts with the live majority; it raises only
        when NO worker is active (or, with `require_all`, when any
        is not)."""
        from concurrent.futures import ThreadPoolExecutor

        def probe(url):
            try:
                info = json.loads(http_get(f"{url}/v1/info",
                                           timeout=timeout))
                return url, info.get("state", "unknown")
            except Exception as e:  # noqa: BLE001 — reported, and
                return url, f"unreachable: {e}"  # raised below if
                # nothing at all answered
        if not self.worker_urls:
            return {}
        with ThreadPoolExecutor(
                max_workers=len(self.worker_urls)) as pool:
            report = dict(pool.map(probe, self.worker_urls))
        dead = {u: s for u, s in report.items() if s != "active"}
        if dead and require_all:
            raise RuntimeError(f"workers not active: {dead}")
        if len(dead) == len(report):
            raise RuntimeError(f"no active workers: {dead}")
        return report

    # -- client protocol ---------------------------------------------------

    def handle_post(self, path: str, body: bytes,
                    headers: Optional[dict] = None) -> bytes:
        if path != "/v1/statement":
            return self._handle_post(path, body, headers)
        # the protocol layer timed from inside: POST in to response
        # out (admission decided, runner thread started)
        t0 = time.perf_counter_ns()
        try:
            return self._handle_post(path, body, headers)
        finally:
            METRICS.inc("presto_tpu_protocol_ns_total",
                        time.perf_counter_ns() - t0, phase="accept")

    def _handle_post(self, path: str, body: bytes,
                     headers: Optional[dict]) -> bytes:
        if path == "/v1/statement":
            from presto_tpu.execution.resource_groups import (
                QueryRejected,
            )
            self._prune_queries()
            h = {k.lower(): v for k, v in (headers or {}).items()}
            q = _Query(body.decode())
            q.user = h.get("x-presto-user", "")
            q.source = h.get("x-presto-source", "")
            # admission decided synchronously AT SUBMIT so queue
            # accounting can't race the worker thread: the resource-
            # group manager either grants a slot, parks the dispatch
            # callback, or SHEDS with a structured kind — overload is
            # absorbed as rejected/queue_full failures, never as
            # collapse
            dispatched = threading.Event()
            q.dispatch = dispatched.set
            self._stamp_queue_deadline(q)
            try:
                state, q.group = self.resource_groups.submit(
                    q.user, q.source, self._query_memory(),
                    on_dispatch=q.dispatch,
                    deadline=q.queue_deadline,
                    on_expire=lambda: self._expire_queued_query(q))
            except QueryRejected as e:
                q.state = "FAILED"
                q.error = str(e)
                q.error_kind = e.kind
                q.done_at = time.monotonic()
                self.queries[q.id] = q
                return json.dumps({
                    "id": q.id,
                    "nextUri": f"{self.url}/v1/statement/"
                               f"executing/{q.id}/0"}).encode()
            except Exception as e:  # noqa: BLE001 — e.g. an injected
                # admission fault (faults site admission.enqueue):
                # still a CLEAN per-query failure, never a 500 that
                # takes the submit endpoint down
                q.state = "FAILED"
                q.error = f"{type(e).__name__}: {e}"
                q.error_kind = getattr(e, "kind", None)
                q.done_at = time.monotonic()
                self.queries[q.id] = q
                return json.dumps({
                    "id": q.id,
                    "nextUri": f"{self.url}/v1/statement/"
                               f"executing/{q.id}/0"}).encode()
            has_slot = state == "run"
            self.queries[q.id] = q
            self._fire_event({"event": "query_created", "id": q.id,
                              "user": q.user, "source": q.source,
                              "group": q.group, "sql": q.sql})
            sanitize.thread(target=self._run_query,
                            args=(q, has_slot, dispatched),
                            daemon=True,
                            purpose="query-runner").start()
            return json.dumps({
                "id": q.id,
                "nextUri": f"{self.url}/v1/statement/executing/"
                           f"{q.id}/0",
            }).encode()
        if path.startswith("/v1/spool/"):
            # fault-tolerant task output pages land HERE (tagged by
            # task attempt) instead of streaming to consumers — see
            # server/scheduler.py TaskOutputSpool
            import urllib.parse as _up
            rest = path[len("/v1/spool/"):]
            params: Dict[str, str] = {}
            if "?" in rest:
                rest, qs = rest.split("?", 1)
                params = dict(_up.parse_qsl(qs))
            key, consumer_s = rest.rsplit("/", 1)
            self.task_spool.put(
                key, int(consumer_s), params["task"],
                int(params["attempt"]), int(params["producer"]),
                int(params["seq"]), body)
            return b"{}"
        return super().handle_post(path, body, headers)

    def _stamp_queue_deadline(self, q: _Query) -> None:
        """Derive the instant after which a QUEUED query is dead:
        query_max_run_time_ms (which counts queue time and fails with
        deadline_exceeded) and/or admission_queue_timeout_ms (pure
        load shedding, kind="rejected") — the earlier wins, and its
        kind is remembered for the expiry path."""
        from presto_tpu.session_properties import get_property
        q.queue_deadline = None
        q.queue_shed_kind = None
        limit_ms = get_property(self.properties,
                                "query_max_run_time_ms")
        if limit_ms:
            q.queue_deadline = q.created_at + float(limit_ms) / 1000.0
            q.queue_shed_kind = "deadline_exceeded"
        qt_ms = get_property(self.properties,
                             "admission_queue_timeout_ms")
        if qt_ms:
            qd = q.created_at + float(qt_ms) / 1000.0
            if q.queue_deadline is None or qd < q.queue_deadline:
                q.queue_deadline = qd
                q.queue_shed_kind = "rejected"

    def _expire_queued_query(self, q: _Query) -> bool:
        """A queued query's deadline passed WITHOUT it ever being
        scheduled: fail it with the structured kind, release its
        waiting runner thread, and charge nothing — no slot was held,
        no MemoryPool entry exists, no lifecycle task ever started.
        Idempotent (the manager sweep and the waiting thread race to
        call this)."""
        if q.done_at is not None or q.state != "QUEUED":
            return False
        kind = q.queue_shed_kind or "rejected"
        q.state = "FAILED"
        q.error = ("query exceeded query_max_run_time_ms while "
                   "queued" if kind == "deadline_exceeded" else
                   "query shed: queue wait exceeded "
                   "admission_queue_timeout_ms")
        q.error_kind = kind
        q.done_at = time.monotonic()
        q.lifecycle.kill_kind = kind
        q.lifecycle.cancel.set()
        if q.dispatch is not None:
            q.dispatch()  # unblock the waiting runner thread
        return True

    def _query_memory(self) -> int:
        """Declared per-query memory reservation charged against the
        resource-group memory caps (the coordinator has no live worker
        memory feed; see resource_groups.py)."""
        from presto_tpu.session_properties import get_property
        try:
            return int(get_property(self.properties,
                                    "query_memory_bytes"))
        except Exception:
            return 0

    # -- observability surface (reference: server/QueryResource.java:49
    # + the webapp/ status UI, collapsed to one self-contained page) ---

    def _query_rows(self) -> List[dict]:
        now = time.monotonic()
        out = []
        for q in list(self.queries.values()):
            elapsed = ((q.done_at or now) - q.created_at) \
                if q.created_at is not None else 0.0
            out.append({
                "id": q.id, "state": q.state, "user": q.user,
                "source": q.source, "group": q.group,
                "elapsed_ms": round(elapsed * 1000, 1),
                "rows": len(q.data) if q.data is not None else 0,
                "error": q.error,
                "error_kind": q.error_kind,
                "sql": q.sql[:500],
            })
        return sorted(out, key=lambda r: -r["elapsed_ms"])

    def handle_get(self, path: str) -> bytes:
        if path == "/v1/info":
            # the coordinator's info adds the live MEMBERSHIP view
            # (heartbeat states, load/memory feedback, flap counts)
            # and the spool/fleet gauges to the node basics
            info = json.loads(super().handle_get(path))
            if self.membership is not None:
                info["workers"] = self.membership.snapshot()
                info["membership"] = self.membership.counts()
            info["spool"] = self.task_spool.stats()
            if self.fleet_memory is not None:
                info["fleet_memory"] = {
                    "budget_bytes": self.fleet_memory.budget,
                    "reserved_bytes": self.fleet_memory.reserved(),
                    "sheds": self.fleet_memory.sheds,
                }
            return json.dumps(info).encode()
        if path == "/v1/query":
            return json.dumps(self._query_rows()).encode()
        if path.startswith("/v1/query/") and path.endswith("/trace"):
            # Chrome trace_event export of a traced query (session
            # property query_trace_enabled) — loads directly in
            # chrome://tracing / Perfetto, or tools/trace_viewer.py
            qid = path.split("/")[3]
            q = self.queries[qid]  # KeyError -> 404
            return json.dumps({
                "displayTimeUnit": "ms",
                "otherData": {"query_id": qid, "state": q.state},
                "traceEvents": q.trace or [],
            }).encode()
        if path.startswith("/v1/query/"):
            qid = path.rsplit("/", 1)[1]
            for row in self._query_rows():
                if row["id"] == qid:
                    q = self.queries[qid]
                    row["sql"] = q.sql
                    row["columns"] = q.columns
                    # the full stats tree: wall/queued/compile/execute
                    # rollup + per-task, per-operator detail
                    row["stats"] = q.stats
                    # the flight-recorder window captured at failure
                    # (None for healthy queries)
                    row["flight"] = q.flight
                    return json.dumps(row).encode()
            raise KeyError(qid)
        if path == "/v1/resourceGroups":
            return json.dumps(self.resource_groups.snapshot()).encode()
        if path in ("/ui", "/ui/"):
            return self._ui_page()
        if path.startswith("/v1/statement/executing/"):
            parts = path.split("/")
            qid = parts[4]
            token = int(parts[5]) if len(parts) > 5 else 0
            q = self.queries[qid]
            q.last_poll = time.monotonic()
            out = {"id": q.id, "stats": {"state": q.state}}
            # columns surface as soon as planning determines them —
            # before FINISHED (reference: ExecutingStatementResource
            # emits columns with the first response that knows them)
            if q.columns is not None:
                out["columns"] = q.columns
            if q.state == "FINISHED":
                # real paging: each nextUri token serves PAGE_ROWS
                # rows; the tail page omits nextUri (protocol end)
                lo = token * PAGE_ROWS
                hi = lo + PAGE_ROWS
                out["data"] = q.data[lo:hi]
                if hi < len(q.data):
                    out["nextUri"] = \
                        f"{self.url}/v1/statement/executing/" \
                        f"{qid}/{token + 1}"
                else:
                    self._stamp_served(q)
                t0 = time.perf_counter_ns()
                page = json.dumps(out).encode()
                METRICS.inc("presto_tpu_protocol_ns_total",
                            time.perf_counter_ns() - t0,
                            phase="encode")
                return page
            elif q.state == "FAILED":
                out["error"] = {"message": q.error,
                                "errorKind": q.error_kind}
                if q.flight:
                    # the flight-recorder post-mortem rides the error
                    # payload itself (bounded window) — no second
                    # round trip to understand a failure
                    out["error"]["flight"] = q.flight[-64:]
            else:
                out["nextUri"] = f"{self.url}/v1/statement/executing/" \
                                 f"{qid}/{token}"
            return json.dumps(out).encode()
        return super().handle_get(path)

    def _stamp_served(self, q: _Query) -> None:
        """The answer's tail page is being handed out: how long the
        finished answer waited for the client's poll is the protocol's
        `result_wait`, and submit-to-here is `served_ms` beside
        `wall_ms`. Once per query (a client may re-read a page)."""
        if q.served_at is not None:
            return
        q.served_at = time.monotonic()
        if q.done_at is None:
            # polled between state=FINISHED and the stats rollup of
            # _run_query: nothing waited; the rollup writes served_ms
            return
        METRICS.inc("presto_tpu_protocol_ns_total",
                    (q.served_at - q.done_at) * 1e9,
                    phase="result_wait")
        if isinstance(q.stats, dict):  # None until the rollup's end
            q.stats["served_ms"] = round(
                (q.served_at - q.created_at) * 1000, 3)

    def _ui_page(self) -> bytes:
        """Single self-contained cluster status page (the webapp/
        analog): workers, resource groups, recent queries; refreshes
        itself from the JSON endpoints."""
        import html as _html
        from concurrent.futures import ThreadPoolExecutor

        def probe(url):
            try:
                info = json.loads(http_get(f"{url}/v1/info",
                                           timeout=2))
                return (url, info.get("state", "?"),
                        info.get("devices", "?"))
            except Exception:  # noqa: BLE001
                return (url, "unreachable", "-")
        # concurrent probes: with dead workers, serial 2s timeouts
        # would make the status page slower than its own 5s refresh
        # exactly when the operator needs it
        with ThreadPoolExecutor(
                max_workers=max(len(self.worker_urls), 1)) as pool:
            workers = list(pool.map(probe, self.worker_urls))
        rows = "".join(
            f"<tr><td><a href='/v1/query/{r['id']}'>{r['id']}</a></td>"
            f"<td class='{r['state']}'>{r['state']}</td>"
            f"<td>{_html.escape(r['user'] or '-')}</td>"
            f"<td>{_html.escape(r['group'])}</td>"
            f"<td>{r['elapsed_ms']}</td><td>{r['rows']}</td>"
            f"<td><code>{_html.escape(r['sql'][:120])}</code></td></tr>"
            for r in self._query_rows()[:100])
        wrows = "".join(
            f"<tr><td>{u}</td><td>{s}</td><td>{d}</td></tr>"
            for u, s, d in workers)
        grows = "".join(
            f"<tr><td>{g['group']}</td><td>{g['running']}/"
            f"{g['hard_concurrency']}</td><td>{g['queued']}/"
            f"{g['max_queued']}</td>"
            f"<td>{g['memory_reserved']}</td></tr>"
            for g in self.resource_groups.snapshot())
        page = f"""<!doctype html><html><head>
<meta http-equiv="refresh" content="5">
<title>presto-tpu coordinator</title><style>
body{{font-family:monospace;margin:2em;background:#111;color:#ddd}}
table{{border-collapse:collapse;margin:1em 0}}
td,th{{border:1px solid #444;padding:4px 10px;text-align:left}}
th{{background:#222}}
.FINISHED{{color:#7c7}}.FAILED{{color:#e77}}.RUNNING{{color:#7cf}}
.QUEUED{{color:#fc7}} a{{color:#9cf}}
</style></head><body>
<h2>presto-tpu coordinator</h2>
<h3>workers ({len(workers)})</h3>
<table><tr><th>url</th><th>state</th><th>devices</th></tr>{wrows}</table>
<h3>resource groups</h3>
<table><tr><th>group</th><th>running</th><th>queued</th>
<th>mem reserved</th></tr>{grows}</table>
<h3>queries</h3>
<table><tr><th>id</th><th>state</th><th>user</th><th>group</th>
<th>elapsed ms</th><th>rows</th><th>query</th></tr>{rows}</table>
</body></html>"""
        return page.encode()

    # -- query execution ---------------------------------------------------

    def _prune_queries(self, ttl_s: float = 600.0,
                       queued_abandon_s: float = 60.0,
                       running_abandon_s: float = 300.0) -> None:
        """Evict terminal queries (and their buffered result rows)
        `ttl_s` after they FINISHED/FAILED — the clock starts at
        completion so a slow query's results stay fetchable. pop()
        keeps concurrent handler threads from double-deleting.

        QUEUED queries whose client stopped polling for
        `queued_abandon_s` are cancelled out of their resource group's
        queue — an abandoned submission must not hold a queue position
        against live clients — and RUNNING queries whose client
        stopped polling for `running_abandon_s` are KILLED through the
        same cooperative-cancel path as an explicit DELETE: an
        abandoned query must not burn coordinator, worker, and cache
        budget to completion for an answer nobody will fetch
        (reference: DispatchManager's query-abandonment pruning + the
        client protocol's abandonment semantics in the Presto
        paper)."""
        now = time.monotonic()
        # queue-wait deadlines must fire on an otherwise-idle
        # coordinator too (no submit/finish traffic = no sweeps)
        self.resource_groups.expire_queued()
        for q in list(self.queries.values()):
            if q.done_at is not None:
                continue
            if q.state == "QUEUED" \
                    and now - q.last_poll > queued_abandon_s:
                self._kill_query(q, "query abandoned while queued",
                                 kind="abandoned")
            elif q.state == "RUNNING" \
                    and now - q.last_poll > running_abandon_s:
                self._kill_query(q, "query abandoned while running",
                                 kind="abandoned")
        for qid in [qid for qid, q in list(self.queries.items())
                    if q.done_at is not None
                    and now - q.done_at > ttl_s]:
            self.queries.pop(qid, None)

    def _kill_query(self, q: _Query, message: str,
                    kind: str = "cancelled") -> bool:
        """Cooperatively stop a query in ANY non-terminal state; a
        no-op on terminal queries (kill is idempotent — cancelling a
        FINISHED query must not disturb its fetchable results).

        QUEUED: the dispatch callback is cancelled out of its resource
        group's queue and the waiting runner thread unblocked — the
        queue position frees without ever running.

        RUNNING: the per-query cancel event is set (every drive loop
        — coordinator root drive, shared single-node runner, worker
        tasks — polls it each round) and the live attempt's remote
        tasks get an immediate best-effort DELETE fan-out; state
        transition + resource release stay with _run_query's finally,
        which owns them."""
        if q.done_at is not None:
            return False
        q.lifecycle.kill_kind = kind
        q.lifecycle.cancel.set()
        if q.state == "QUEUED" and q.dispatch is not None \
                and self.resource_groups.cancel_queued(q.group,
                                                       q.dispatch):
            q.state = "FAILED"
            q.error = message
            q.error_kind = kind
            q.done_at = time.monotonic()
            q.dispatch()  # unblock the waiting runner thread
            return True
        q.lifecycle.abort_remote()
        return True

    def handle_delete(self, path: str) -> bytes:
        if path.startswith("/v1/statement/"):
            # client kill (reference: StatementClientV1.close DELETEs
            # its nextUri; QueuedStatementResource.cancelQuery):
            # accepts both the submit URI (/v1/statement/{id}) and
            # the executing nextUri form
            parts = [p for p in path.split("/") if p]
            qid = parts[3] if len(parts) > 3 \
                and parts[2] == "executing" else parts[2]
            q = self.queries[qid]  # KeyError -> 404
            self._kill_query(q, "query cancelled by client request",
                             kind="cancelled")
            return json.dumps({"id": q.id,
                               "state": q.state}).encode()
        return super().handle_delete(path)

    def _run_query(self, q: _Query, has_slot: bool = True,
                   dispatched: Optional[threading.Event] = None) -> None:
        # admission: wait for the group's dispatch callback (QUEUED
        # state is client-visible while waiting). An abandoned queued
        # query (client stopped polling) is cancelled by the pruner —
        # its queue position frees without running — and a queue-wait
        # deadline expires HERE, precisely, without ever scheduling:
        # the manager sweep drops the entry and _expire_queued_query
        # marks the failure.
        if not has_slot:
            while not dispatched.wait(
                    0.25 if q.queue_deadline is not None else None):
                if time.monotonic() > q.queue_deadline:
                    self.resource_groups.expire_queued()
            if q.state == "FAILED":  # cancelled/expired while queued
                return
        q.state = "RUNNING"
        q.run_started_at = time.monotonic()
        try:
            # per-query deadline: anchored at SUBMIT (queue time
            # counts — reference: query_max_run_time, which includes
            # queued time, vs query_max_execution_time)
            from presto_tpu.session_properties import get_property
            limit_ms = get_property(self.properties,
                                    "query_max_run_time_ms")
            if limit_ms:
                q.lifecycle.deadline = \
                    q.created_at + float(limit_ms) / 1000.0
            if q.lifecycle.cancel.is_set():
                raise QueryFailed("query cancelled before execution",
                                  kind="cancelled")
            result = self.execute(
                q.sql, on_columns=lambda cols: setattr(
                    q, "columns", cols), user=q.user,
                lifecycle=q.lifecycle)
            q.columns = [
                {"name": n, "type": f.type.display()}
                for n, f in zip(result.names, result.fields)]
            # result materialization (pylist conversion for the client
            # protocol) is real host glue INSIDE the query's wall —
            # measured here so the ledger re-close below can attribute
            # it instead of leaving it in the residual
            t_mat = time.monotonic()
            rows = result.rows()
            q.data = [list(r) for r in rows]
            q.materialize_ms = (time.monotonic() - t_mat) * 1000
            q.state = "FINISHED"
            q.stats = getattr(result, "query_stats", None)
            q.trace = getattr(result, "trace_events", None)
        except Exception as e:  # noqa: BLE001
            q.error = f"{type(e).__name__}: {e}"
            # the kill reason (abandoned vs cancelled) outranks the
            # drive loop's generic "cancelled": the drive only knows
            # it was told to stop, the killer knows why
            q.error_kind = q.lifecycle.kill_kind \
                or getattr(e, "kind", None)
            q.state = "FAILED"
            # the failure trace + partial stats (when present) ride
            # the exception — compile time spent before the failure
            # must survive into the stats tree
            q.trace = getattr(e, "trace_events", None)
            q.stats = getattr(e, "query_stats", None)
            # flight-recorder post-mortem: the recent window rides the
            # error payload (attached by the runner tier when the
            # failure crossed it; snapshot here otherwise so the
            # distributed path is covered too)
            from presto_tpu.telemetry import flight as _flight
            q.flight = getattr(e, "flight_events", None)
            if q.flight is None and _flight.ENABLED:
                _flight.record("query", "FAILED",
                               q.error_kind or type(e).__name__,
                               q.sql[:80])
                q.flight = _flight.snapshot_dicts(64)
        finally:
            q.done_at = time.monotonic()
            # QueryStats rollup: the coordinator owns wall/queued (it
            # saw submit and dispatch); the execution tier contributed
            # compile/execute/tasks through the result
            from presto_tpu.telemetry import build_query_stats
            queued_ms = ((q.run_started_at or q.done_at)
                         - q.created_at) * 1000
            wall_ms = (q.done_at - q.created_at) * 1000
            inner = dict(q.stats or {})
            inner.pop("wall_ms", None)
            inner.pop("queued_ms", None)
            base = build_query_stats(
                wall_ms, queued_ms, state=q.state,
                error_kind=q.error_kind,
                rows_out=len(q.data) if q.data is not None else 0)
            if inner:
                # don't resurrect fields the execution tier
                # deliberately dropped (distributed trees omit
                # kernel_calls/compiles — counts aren't shipped in
                # task snapshots, zeros here would contradict the ns
                # sums)
                for k in ("kernel_calls", "kernel_compiles"):
                    if k not in inner:
                        base.pop(k, None)
            q.stats = {**base, **inner,
                       "wall_ms": round(wall_ms, 3),
                       "queued_ms": round(queued_ms, 3)}
            if q.served_at is not None:
                # the tail page left before this rollup (_stamp_served)
                q.stats["served_ms"] = q.stats["wall_ms"]
            # re-close the attribution ledger against the FULL query
            # wall (coordinator queue + execution + result
            # materialization + protocol overhead): categories come
            # from the execution tier, queue wait is added here (the
            # coordinator owns it), and the residual absorbs the
            # protocol share — Σ categories + unattributed == wall
            # stays exact at this level too
            led = q.stats.get("ledger")
            if led is not None:
                cats = dict(led.get("categories_ms", {}))
                if queued_ms > 0:
                    cats["queued"] = round(
                        cats.get("queued", 0.0) + queued_ms, 3)
                mat_ms = getattr(q, "materialize_ms", 0.0)
                if mat_ms > 0:
                    cats["driver.reassembly"] = round(
                        cats.get("driver.reassembly", 0.0) + mat_ms, 3)
                total = sum(cats.values())
                if total > wall_ms > 0:
                    # same normalization contract as QueryLedger.
                    # finish: proportions stay, the invariant stays
                    # exact
                    cats = {c: round(v * wall_ms / total, 3)
                            for c, v in cats.items()}
                unattr = wall_ms - sum(cats.values())
                q.stats["ledger"] = {
                    "wall_ms": round(wall_ms, 3),
                    "categories_ms": cats,
                    "unattributed_ms": round(unattr, 3),
                    "unattributed_frac": round(unattr / wall_ms, 4)
                    if wall_ms > 0 else 0.0,
                }
                if "details_ms" in led:
                    # parts of their categories, scaled with them
                    k = wall_ms / total if total > wall_ms > 0 else 1.0
                    q.stats["ledger"]["details_ms"] = {
                        c: {d: round(v * k, 3) for d, v in per.items()}
                        for c, per in led["details_ms"].items()}
            if q.trace and isinstance(q.stats, dict) \
                    and "critical_path" not in q.stats:
                # blocking-chain extraction over the merged fleet
                # trace (the single-node runner computed its own; the
                # distributed root span closes only in this tier)
                try:
                    from presto_tpu.telemetry import (
                        critical_path as _cp)
                    cp_doc = _cp.extract(q.trace)
                    if cp_doc is not None:
                        q.stats["critical_path"] = cp_doc
                except Exception:  # noqa: BLE001 — advisory
                    pass
            self.resource_groups.finish(q.group, self._query_memory())
            if not self.single_node:
                # the worker topology never passes through a
                # LocalRunner statement path (which owns this counter
                # on single-node/embedded runners) — count here so
                # /v1/metrics reports query totals on every topology
                from presto_tpu.telemetry.metrics import METRICS
                METRICS.inc("presto_tpu_queries_total",
                            state=q.state,
                            error_kind=q.error_kind or "")
                if q.state == "FINISHED":
                    from presto_tpu.telemetry import flight as _fl
                    if _fl.ENABLED:
                        # worker-topology lifecycle edge (the runner
                        # tier records these on single-node paths)
                        _fl.record("query", "FINISHED", "",
                                   q.sql[:80])
            # event listeners see the COMPLETED QueryStats payload —
            # the same numbers GET /v1/query/{id} serves (satellite:
            # external sinks must not need a second code path)
            self._fire_event({
                "event": "query_completed", "id": q.id,
                "state": q.state, "user": q.user, "group": q.group,
                "elapsed_ms": round(
                    (q.done_at - q.created_at) * 1000, 1),
                "rows": len(q.data) if q.data is not None else 0,
                "error": q.error,
                "stats": q.stats})

    def execute(self, sql: str, on_columns=None, user: str = "",
                lifecycle: Optional[QueryLifecycle] = None):
        """Distributed execution with elastic retry: a failed or dead
        worker fails the attempt, the membership is re-probed, and the
        query re-runs on the survivors — splits regenerate identically
        anywhere, so no state needs recovering (reference:
        SqlQueryScheduler section retry :667-690 + P7/P8 relocatable
        splits; a whole-query retry is the single-section case).
        `on_columns` fires once the output schema is known (before any
        result rows exist — the client protocol's early-columns).
        `lifecycle` carries the cooperative cancel event + deadline
        (see QueryLifecycle); its attempt counter is how tests prove a
        transient exchange fault was absorbed BELOW this retry tier."""
        from presto_tpu.session_properties import get_property
        if lifecycle is None:
            lifecycle = QueryLifecycle()
        if self.single_node:
            # lint-ok: CC002 lifecycle is per-query; only the one
            lifecycle.attempts += 1  # driving thread writes attempts
            runner = self._runner()
            result = runner.execute_as(
                sql, user, cancel=lifecycle.cancel.is_set,
                deadline=lifecycle.deadline,
                query_id=lifecycle.query_id)
            if on_columns is not None:
                on_columns([
                    {"name": n, "type": f.type.display()}
                    for n, f in zip(result.names, result.fields)])
            return result
        retries = int(get_property(self.properties,
                                   "query_retries"))
        workers = list(self.worker_urls)
        props = dict(self.properties)
        # distributed tracing: the coordinator's drive/exchange/backoff
        # spans record onto this thread's recorder; the finished trace
        # rides the result to GET /v1/query/{id}/trace
        import time as _time
        from presto_tpu.telemetry import trace as _trace
        recorder = None
        prev_rec = None
        t0_ns = _time.perf_counter_ns()
        if bool(get_property(self.properties, "query_trace_enabled")):
            recorder = _trace.TraceRecorder()
            prev_rec = _trace.activate(recorder)
        #: workers implicated in a connection-level failure this
        #: query: never re-picked by a later attempt, even if their
        #: /v1/info answers again (a flapping worker would otherwise
        #: eat the whole retry budget)
        blacklist: set = set()
        attempt = 0
        bumps = 0
        try:
            while True:
                try:
                    result = self._execute_attempt(
                        sql, workers, props, on_columns=on_columns,
                        user=user, lifecycle=lifecycle)
                    if recorder is not None:
                        # root span closes the containment hierarchy
                        # (same contract as LocalRunner.execute)
                        recorder.add(
                            "query", "query", t0_ns,
                            _time.perf_counter_ns() - t0_ns,
                            {"sql": sql[:200]})
                        result.trace_events = recorder.events()
                    return result
                except Exception as e:  # noqa: BLE001 — inspect+retry
                    # a killed/expired query must NOT burn the elastic
                    # retry budget re-running work nobody wants, and a
                    # fleet-memory shed is structural admission
                    # control, not a failure to retry around
                    if getattr(e, "kind", None) in ("cancelled",
                                                    "deadline_exceeded",
                                                    "cluster_memory"):
                        raise
                    # sync-free overflow protocol: re-run the WHOLE
                    # query with the suggested setting (any fragment
                    # may have raised it, local or remote) — not a
                    # failure retry
                    prop, suggested = _retry_hint(e)
                    if prop is not None and bumps < 8:
                        bumps += 1
                        props[prop] = max(suggested,
                                          props.get(prop, 0) or 0)
                        continue
                    attempt += 1
                    if attempt > retries:
                        raise
                    from presto_tpu.telemetry import flight as _fl
                    if _fl.ENABLED:
                        _fl.record("retry", "query", attempt,
                                   f"{type(e).__name__}: {e}"[:120])
                    bad = getattr(e, "worker", None)
                    if bad:
                        blacklist.add(bad)
                        if self.membership is not None:
                            # inline failure evidence accelerates the
                            # heartbeat tier's suspicion
                            self.membership.report_failure(bad)
                    alive = []
                    for url in workers:
                        if url in blacklist:
                            continue
                        try:
                            st = json.loads(http_get(
                                f"{url}/v1/info", timeout=5))
                            if st.get("state") == "active":
                                alive.append(url)
                        except Exception:  # noqa: BLE001 — dead worker
                            pass
                    if not alive:
                        raise
                    if len(alive) == len(workers):
                        # nothing died and no worker was implicated —
                        # the failure is the query's own (analysis
                        # error, execution bug): don't mask it behind
                        # a retry
                        raise
                    workers = alive
                    continue
        except BaseException as e:
            # a failed traced query keeps its timeline (same contract
            # as LocalRunner.execute): events — root span included —
            # ride the exception to _run_query, which serves them on
            # the trace endpoint
            _trace.attach_failure(recorder, e, t0_ns, sql)
            raise
        finally:
            if recorder is not None:
                _trace.deactivate(prev_rec)

    def _runner(self):
        """The shared single-node runner (lazy; LocalRunner.execute is
        concurrency-safe — per-query pools, thread-local session
        overrides). With `mesh_devices` above 1 in the coordinator's
        properties it is a MeshRunner over that many chips: the same
        class behind the same calls, one statement's collectives at a
        time (runner/mesh.py)."""
        with self._embedded_lock:
            if self._embedded_runner is None:
                from presto_tpu.runner import runner_for
                self._embedded_runner = runner_for(
                    self.catalog, self.schema, self.properties,
                    access_control=self.access_control)
            return self._embedded_runner

    def _worker_clock_offset(self, url: str) -> Optional[int]:
        """Best clock-offset estimate for merging `url`'s trace spans:
        the heartbeat's smallest-RTT estimate when membership runs,
        else one cached direct /v1/info handshake."""
        if self.membership is not None:
            off = self.membership.clock_offset(url)
            if off is not None:
                return off
        cache = getattr(self, "_clock_offsets", None)
        if cache is None:
            cache = self._clock_offsets = {}
        if url not in cache:
            from presto_tpu.telemetry.trace import (
                estimate_clock_offset,
            )
            off = estimate_clock_offset(url, timeout=2.0)
            if off is None:
                # transient failure: don't poison the cache — the
                # next traced query retries the handshake
                return None
            cache[url] = off
        return cache[url]

    def _worker_devices(self, worker_urls: List[str]) -> List[int]:
        """Per-worker device counts (mesh-per-worker: a worker's tasks
        expand to one subtask per device)."""
        ks = []
        for url in worker_urls:
            try:
                info = json.loads(http_get(f"{url}/v1/info",
                                           timeout=10))
                ks.append(max(1, int(info.get("devices", 1))))
            except Exception:  # noqa: BLE001 — treat as single-device
                ks.append(1)
        return ks

    def _execute_attempt(self, sql: str, worker_urls: List[str],
                         properties: Optional[dict] = None,
                         on_columns=None, user: str = "",
                         lifecycle: Optional[QueryLifecycle] = None):
        """Counter shell around _execute_attempt_inner: the attempt's
        per-query kernel counters must span PLANNING too —
        compile_expression credits expr_compile_ns while fragments are
        planned, and counters installed only around the drive loop
        would report expr_compile_ms = 0 on this topology forever."""
        import time as _time
        from presto_tpu.telemetry import build_query_stats
        from presto_tpu.telemetry import kernels as _tk
        from presto_tpu.telemetry import ledger as _ledger
        # honor the statement's kernel_shape_buckets on the
        # coordinator's own root-fragment drive too: this thread plans
        # and drives pipelines directly, outside LocalRunner.execute
        # which normally sets the thread-local gate (the PR 6 gap —
        # workers get the same fix in node.execute_fragment)
        from presto_tpu import batch as _batch
        from presto_tpu.session_properties import get_property as _gp
        prev_sb = _batch.set_shape_buckets(bool(_gp(
            dict(self.properties if properties is None
                 else properties), "kernel_shape_buckets")))
        prev_q = _tk.begin_query()
        # attribution ledger for the ATTEMPT: the coordinator's own
        # planning/drive/exchange wall decomposes like a local
        # statement's (remote-task device time is attributed on the
        # workers; here it shows up as exchange-wait inside driver/
        # unattributed — the honest cross-process picture)
        led = _ledger.QueryLedger(
            lifecycle.query_id if lifecycle is not None else "")
        prev_led = _ledger.install(led)
        t0_ns = _time.perf_counter_ns()
        result = None
        try:
            # top-level `driver` frame, same contract as the runner's
            # statement shell: attempt-level host overhead (dispatch
            # bookkeeping, task-status collection) is driver overhead;
            # nested planning/exchange/serde spans subtract and the
            # root drive's executor wait is absorbed by run_drivers
            with _ledger.span("driver.quantum", detail="statement",
                              query_id=led.query_id):
                result = self._execute_attempt_inner(
                    sql, worker_urls, properties, on_columns, user,
                    lifecycle)
            return result
        except BaseException as e:
            # failed attempts keep their kernel attribution (compile
            # time burned before the failure); _run_query's merge
            # supplies the real wall/queued
            try:
                e.query_stats = build_query_stats(
                    0.0, 0.0, _tk.query_counters())
            except Exception:  # noqa: BLE001
                pass
            raise
        finally:
            _tk.end_query(prev_q)
            _batch.set_shape_buckets(prev_sb)
            _ledger.uninstall(prev_led)
            led_doc = led.finish(_time.perf_counter_ns() - t0_ns)
            _ledger.publish(led_doc)
            qs = getattr(result, "query_stats", None)
            if qs is None:
                import sys as _sys
                exc = _sys.exc_info()[1]
                qs = getattr(exc, "query_stats", None)
            if isinstance(qs, dict):
                qs["ledger"] = led_doc

    def _execute_attempt_inner(self, sql: str, worker_urls: List[str],
                               properties: Optional[dict] = None,
                               on_columns=None, user: str = "",
                               lifecycle: Optional[QueryLifecycle]
                               = None):
        """One scheduling attempt over a fixed worker set. An EXPLAIN
        [ANALYZE] statement is handled HERE on the worker topology:
        plain EXPLAIN renders the fragmented plan without executing;
        EXPLAIN ANALYZE runs the inner query with profiling on the
        coordinator AND every worker task (spec carries profile=true),
        then renders per-task operator stats — rows/wall plus the
        compile-vs-execute split — next to the fragment tree."""
        if lifecycle is None:
            lifecycle = QueryLifecycle()
        # lint-ok: CC002 lifecycle is per-query; only the one
        lifecycle.attempts += 1  # driving thread writes attempts
        import time as _time
        from presto_tpu.parser import parse_statement
        from presto_tpu.parser import tree as T
        from presto_tpu.planner.local_planner import (
            LocalExecutionPlanner, TaskContext,
        )
        from presto_tpu.runner.local import (
            LocalRunner, MaterializedResult,
        )
        from presto_tpu.telemetry import kernels as _tk
        properties = dict(self.properties if properties is None
                          else properties)
        # the client's identity gates access control at the
        # COORDINATOR, where analysis happens — workers only execute
        # already-authorized fragments
        runner = LocalRunner(self.catalog, self.schema, properties,
                             user=user,
                             access_control=self.access_control)
        stmt = parse_statement(sql)
        explain = isinstance(stmt, T.Explain)
        profile = explain and stmt.analyze
        fplan = derive_fragments(runner, sql, stmt=stmt)
        if explain and not profile:
            # plain EXPLAIN: the fragmented plan, no execution
            result = runner._text_result(
                "Query Plan", fplan.text().split("\n"))
            if on_columns is not None:
                on_columns([{"name": "Query Plan",
                             "type": "varchar"}])
            return result
        from presto_tpu.session_properties import get_property as _gp
        if not explain and int(_gp(properties, "task_retries")) > 0:
            # fault-tolerant execution (server/scheduler.py): each
            # distributed fragment runs as independently retryable
            # tasks over the live membership with outputs spooled at
            # stage boundaries — a dead worker re-runs only its
            # unfinished tasks. This attempt tier remains above it as
            # the LAST resort (and the overflow-bump protocol rides
            # the TaskFailed kinds unchanged).
            return StageScheduler(
                self, sql, fplan, runner, worker_urls, properties,
                lifecycle, on_columns=on_columns).run()
        if not worker_urls and any(
                f.partitioning == "distributed"
                for f in fplan.fragments.values()):
            raise RuntimeError(
                "query requires distributed fragments but the "
                "coordinator has no workers")
        query_id = uuid.uuid4().hex[:12]
        # global consumer-task space: one slot per (worker, device);
        # row routing is h % total so a key lands on one chip of one
        # worker — the DCN tier addresses devices directly
        ks = self._worker_devices(worker_urls)
        offsets = [0]
        for k in ks:
            offsets.append(offsets[-1] + k)
        total_tasks = max(offsets[-1], 1)
        distributed_urls: List[str] = []
        for url, k in zip(worker_urls, ks):
            distributed_urls.extend([url] * k)
        consumer_urls_by_edge = {}
        n_producers_by_edge = {}
        for xid, edge in fplan.edges.items():
            consumer = fplan.fragments[edge.consumer]
            producer = fplan.fragments[edge.producer]
            consumer_urls_by_edge[xid] = [self.url] \
                if consumer.partitioning == "single" \
                else list(distributed_urls)
            n_producers_by_edge[xid] = 1 \
                if producer.partitioning == "single" else total_tasks
        exchanges = build_http_exchanges(
            query_id, fplan, consumer_urls_by_edge, worker_urls,
            self.url, self.registry,
            n_producers_by_edge=n_producers_by_edge, self_url=self.url)

        # everything from first dispatch to completion runs under one
        # release guard: a failure at ANY point (dead worker mid-
        # dispatch, local planning bug, drive failure) must abort the
        # attempt's remote tasks and drop its exchange state before the
        # retry loop launches the next attempt
        remote: List[tuple] = []
        # the lifecycle sees the live attempt's tasks (same list
        # object) so a kill fans out task DELETEs without waiting for
        # the drive loop's next cancel poll
        lifecycle.remote = remote
        stop = threading.Event()
        # distributed tracing: when this query is traced (the
        # recorder was activated by execute()), every task spec asks
        # the worker to record + ship its spans, and dispatch times
        # anchor coordinator-side task lanes
        from presto_tpu.telemetry import trace as _trace
        recorder = _trace.current()
        dispatch_t0: Dict[str, int] = {}
        try:
            # dispatch distributed fragments: one task per worker
            # (reference: SqlStageExecution.scheduleTask ->
            # HttpRemoteTask)
            for fid, fragment in fplan.fragments.items():
                if fragment.partitioning != "distributed":
                    continue
                for w, wurl in enumerate(worker_urls):
                    task_id = f"{query_id}.{fid}.{w}"
                    spec = {
                        "task_id": task_id,
                        "query_id": query_id,
                        "sql": sql,
                        "session": {"catalog": self.catalog,
                                    "schema": self.schema,
                                    "properties": properties},
                        "fragment_id": fid,
                        "task_index": offsets[w],
                        "local_base": offsets[w],
                        "local_count": ks[w],
                        "n_tasks": total_tasks,
                        "worker_urls": worker_urls,
                        "consumer_urls_by_edge": consumer_urls_by_edge,
                        "n_producers_by_edge": n_producers_by_edge,
                        "coordinator_url": self.url,
                        "profile": profile,
                        "trace": recorder is not None,
                        "trace_ctx": {
                            "query_id": query_id,
                            "task_id": task_id,
                            "attempt": lifecycle.attempts,
                            "parent_span": "query"},
                    }
                    body = json.dumps(spec).encode()
                    dispatch_t0[task_id] = \
                        _time.perf_counter_ns()

                    def dispatch(wurl=wurl, body=body):
                        # fault site + transport retry INSIDE one
                        # dispatch: a lost response re-POSTs, and the
                        # worker's idempotent create_task dedups
                        if faults.ARMED:
                            faults.fire("task.dispatch", url=wurl)
                        http_post(f"{wurl}/v1/task", body)
                    from presto_tpu.server.node import _retry_transient
                    try:
                        _retry_transient(dispatch, TRANSPORT_RETRIES)
                    except Exception as e:  # noqa: BLE001
                        raise TaskFailed(
                            f"task dispatch to {wurl} failed: {e}",
                            worker=wurl) from e
                    remote.append((task_id, wurl))

            # run single-partition fragments here (root last -> result)
            result = None
            pipelines: List[list] = []
            root_planner = None
            root_fragment = None
            root_span = (0, 0)
            for fid, fragment in fplan.fragments.items():
                if fragment.partitioning != "single":
                    continue
                task = TaskContext(index=0, count=1, device=None,
                                   exchanges=exchanges)
                planner = LocalExecutionPlanner(
                    runner.catalogs, runner.session, task=task)
                if fid == fplan.root_id:
                    start = len(pipelines)
                    lplan = planner.plan(fragment.root)
                    pipelines.extend(lplan.pipelines)
                    result = lplan
                    root_planner, root_fragment = planner, fragment
                    root_span = (start, len(pipelines))
                else:
                    sinks = [exchanges[e.exchange_id]
                             for e in fplan.producer_edges(fid)]
                    pipelines.extend(
                        planner.plan_fragment(fragment.root, sinks))
            assert result is not None
            # history recording tap (coordinator root drive): the root
            # fragment runs as ONE task here, so its fully-local nodes
            # (subtrees without a RemoteSource) measure whole-node
            # truth. Other single fragments are skipped — operator ids
            # restart per planner, and their snapshots would alias the
            # root's in one merged id space.
            from presto_tpu import history as _history
            hist_ops = None
            singles = sum(1 for f in fplan.fragments.values()
                          if f.partitioning == "single")
            if root_planner is not None and singles == 1 \
                    and _history.enabled(properties) \
                    and not faults.ARMED:
                # singles == 1: operator ids restart per planner, so
                # with several single fragments in one merged driver
                # set, arming by id would also count colliding ids of
                # non-root operators (wasted per-batch device work)
                hist_ops = _history.interesting_ops(
                    root_fragment.root,
                    root_planner.node_ops_prefusion,
                    id_remap=(root_planner.fusion_report or {}).get(
                        "id_remap"),
                    catalogs=runner.catalogs)
            if on_columns is not None and not explain:
                on_columns([
                    {"name": n, "type": f.type.display()}
                    for n, f in zip(result.result_names,
                                    result.result_fields)])

            failure: List[TaskFailed] = []

            def watch():
                # failure detection: poll remote task state; a failed
                # task fails the query (reference:
                # ContinuousTaskStatusFetcher + RequestErrorTracker).
                # Status polls retry with backoff so one dropped poll
                # response doesn't escalate to a whole-query retry —
                # only a worker that stays unreachable does (and it
                # gets blacklisted for this query's later attempts)
                from presto_tpu.server.node import _retry_transient
                while not stop.is_set():
                    for task_id, wurl in remote:
                        def poll(task_id=task_id, wurl=wurl):
                            # the fault site sits INSIDE the retry
                            # loop: a transient injected drop is
                            # absorbed like a real one — only a
                            # PERSISTENT fault models an unreachable
                            # worker and escalates
                            if faults.ARMED:
                                faults.fire("task.status_poll",
                                            url=wurl, task=task_id)
                            return http_get(
                                f"{wurl}/v1/task/{task_id}",
                                timeout=10)
                        try:
                            st = json.loads(_retry_transient(poll, 2))
                        except Exception as e:  # noqa: BLE001
                            failure.append(TaskFailed(
                                f"worker {wurl} unreachable: {e}",
                                worker=wurl))
                            return
                        if st["state"] == "failed":
                            failure.append(TaskFailed(
                                f"task {task_id} failed: "
                                f"{st['error']}",
                                kind=st.get("error_kind"),
                                suggested=st.get("suggested")))
                            return
                    time.sleep(0.2)

            watcher = sanitize.thread(target=watch, daemon=True,
                                      purpose="remote-task-watcher")
            watcher.start()
            t0 = _time.perf_counter()
            drivers = self._drive_with_failures(
                pipelines, failure, profile=profile,
                cancel=lifecycle.cancel.is_set,
                deadline=lifecycle.deadline,
                properties=properties,
                count_rows_ops=hist_ops)
            wall_s = _time.perf_counter() - t0
            if hist_ops is not None and not failure \
                    and not faults.ARMED:
                snap_all = LocalRunner.snapshot_driver_stats(drivers)
                runner._record_history(
                    root_fragment.root, root_planner,
                    snap_all[root_span[0]:root_span[1]])
            # the attempt's counter dict is live on this thread (the
            # shell owns begin/end); snapshot it now so the stats
            # tree can't see a later attempt's accumulation
            kernel_counters = dict(_tk.query_counters() or {})
            # roll the topology's TaskStats up BEFORE releasing: the
            # coordinator's own drivers snapshot here, each worker
            # task's snapshot comes back in its status response.
            # Remote stats collection stays OFF the failure path —
            # it must never delay elastic-retry failover
            tasks = [{"task_id": f"{query_id}.coordinator",
                      "worker": self.url,
                      "wall_s": round(wall_s, 6),
                      "pipelines":
                      LocalRunner.snapshot_driver_stats(drivers)}]
            if not failure:
                # always poll briefly for the snapshot: the root can
                # drain before a worker's task thread PUBLISHES its
                # stats (drive return + materialize), and an empty
                # pipelines entry would zero the query's worker
                # kernel time. Plain queries bound the wait at 2s
                # (concurrent across tasks); EXPLAIN ANALYZE waits
                # longer — its whole point is the numbers
                tasks += self._collect_task_stats(
                    remote, wait=True,
                    timeout_s=10.0 if profile else 2.0)
                if recorder is not None:
                    # merge the workers' shipped spans into one fleet
                    # timeline (per-worker pids, clock offsets from
                    # the heartbeat or a direct handshake) + a
                    # coordinator-side lane per dispatched task. The
                    # merger is per RECORDER, so a retried attempt
                    # reuses the first attempt's pid/lane allocations
                    merger = _trace.FleetTraceMerger.for_recorder(
                        recorder)
                    for t in tasks:
                        ev = t.pop("trace", None)
                        if ev:
                            merger.merge(
                                t["worker"], t["task_id"],
                                lifecycle.attempts, ev,
                                self._worker_clock_offset(
                                    t["worker"]))
                    now_ns = _time.perf_counter_ns()
                    for task_id, wurl in remote:
                        td = dispatch_t0.get(task_id)
                        if td is not None:
                            recorder.add(
                                f"task {task_id}", "task", td,
                                now_ns - td, {"worker": wurl})
        finally:
            stop.set()
            lifecycle.remote = []
            self._release_everywhere(query_id, worker_urls)
        if failure:
            raise failure[0]
        from presto_tpu.telemetry import build_query_stats
        for t in tasks:
            t.pop("trace", None)  # merged above; not a stats field
        qstats = build_query_stats(wall_s * 1000, 0.0,
                                   kernel_counters, tasks=tasks)
        # top-level compile/execute must mean the same thing on every
        # topology: the sum over ALL tasks' operator credit (worker
        # kernel time included — the coordinator-thread counters alone
        # would report ~0 for a query whose compiles happened on
        # workers). The coordinator's drivers ARE a task, so this
        # replaces (not adds to) its thread-local share.
        qstats["compile_ms"] = round(sum(
            t["totals"]["compile_ms"] for t in qstats["tasks"]), 3)
        qstats["execute_ms"] = round(sum(
            t["totals"]["execute_ms"] for t in qstats["tasks"]), 3)
        # call/compile COUNTS are coordinator-thread-only (snapshots
        # don't ship per-op call counts) — serving them next to
        # all-task ns sums would be self-contradictory, so drop them
        # from the distributed tree
        qstats.pop("kernel_calls", None)
        qstats.pop("kernel_compiles", None)
        if profile:
            out = self._render_distributed_profile(
                fplan, tasks, wall_s, qstats)
            result = runner._text_result("Query Plan",
                                         out.split("\n"))
            if on_columns is not None:
                on_columns([{"name": "Query Plan",
                             "type": "varchar"}])
            result.query_stats = qstats
            return result
        out = MaterializedResult(result.result_names,
                                 result.result_sink,
                                 result.result_fields)
        out.query_stats = qstats
        return out

    def _collect_task_stats(self, remote: List[tuple],
                            wait: bool = False,
                            timeout_s: float = 10.0) -> List[dict]:
        """Best-effort fetch of each remote task's operator-stats
        snapshot from its status response. `wait` (EXPLAIN ANALYZE)
        polls briefly for terminal state — the root drained implies
        producers finished, but the task thread may not have published
        its snapshot yet. Plain queries use ONE short-timeout GET per
        task, issued CONCURRENTLY (a slow-but-alive worker must cost
        the query's critical path at most one timeout, not one per
        task), and take whatever is there: stats are best-effort."""
        from concurrent.futures import ThreadPoolExecutor

        def fetch(task):
            task_id, wurl = task
            st = None
            deadline = time.monotonic() + timeout_s
            while True:
                try:
                    st = json.loads(http_get(
                        f"{wurl}/v1/task/{task_id}",
                        timeout=max(2.0, min(timeout_s, 10.0)),
                        retries=1))
                except Exception:  # noqa: BLE001 — best-effort
                    break
                if not wait or st.get("stats") is not None \
                        or st.get("state") not in ("running",) \
                        or time.monotonic() > deadline:
                    break
                time.sleep(0.05)
            if st is None:
                return None
            stats = st.get("stats") or {}
            out = {"task_id": task_id, "worker": wurl,
                   "wall_s": stats.get("wall_s"),
                   "trace": st.get("trace"),
                   "pipelines": stats.get("pipelines") or []}
            if st.get("stats") is None:
                # snapshot not published in time: mark the entry so
                # consumers know the task's kernel share is missing,
                # not zero
                out["partial"] = True
            return out

        if not remote:
            return []
        with ThreadPoolExecutor(
                max_workers=min(len(remote), 16)) as pool:
            return [t for t in pool.map(fetch, remote)
                    if t is not None]

    @staticmethod
    def _render_distributed_profile(fplan, tasks: List[dict],
                                    wall_s: float,
                                    qstats: dict) -> str:
        """Distributed EXPLAIN ANALYZE text: fragment tree + one
        operator-stats section per task (rows/wall + compile-vs-
        execute), + the query-level rollup."""
        from presto_tpu.telemetry import render_operator_stats
        parts = [fplan.text()]
        for t in tasks:
            parts.append(f"Task {t['task_id']} @ {t['worker']}:")
            parts.append(render_operator_stats(
                t.get("pipelines") or [],
                t.get("wall_s") or wall_s))
        # the query footer sums the per-OPERATOR kernel credit across
        # every task (coordinator included). The coordinator's thread-
        # local query counters in `qstats` cover the same calls — its
        # drivers ARE tasks[0] — so they must NOT be added on top
        # (that double-counted coordinator compile time)
        total_c = 0.0
        total_e = 0.0
        for t in qstats.get("tasks", ()):
            tt = t.get("totals", {})
            total_c += tt.get("compile_ms", 0.0)
            total_e += tt.get("execute_ms", 0.0)
        parts.append(
            f"query wall: {wall_s * 1e3:.1f}ms, compile sum: "
            f"{total_c:.1f}ms, execute sum: {total_e:.1f}ms")
        return "\n\n".join(parts)

    def _release_everywhere(self, query_id: str,
                            worker_urls: List[str]) -> None:
        self.release_query(query_id)
        for wurl in worker_urls:
            try:
                http_post(f"{wurl}/v1/query/{query_id}/release",
                          b"", timeout=10)
            except Exception:  # noqa: BLE001 — best-effort cleanup
                pass

    @staticmethod
    def _drive_with_failures(pipelines, failure: List[str],
                             max_idle_s: float = 600.0,
                             profile: bool = False,
                             cancel=None,
                             deadline: Optional[float] = None,
                             properties: Optional[dict] = None,
                             count_rows_ops=None):
        """The coordinator's OWN drive loop (root + single-partition
        fragments) — it polls the same cancel hook and deadline as
        worker tasks do, so a kill stops the whole topology, not just
        the remote fringe. With the time-sliced executor enabled
        (default), the drivers run on the process-wide worker pool
        and the remote-task-failed signal rides the abort_check
        checkpoint at every quantum boundary."""
        from presto_tpu.operators.base import DriverContext
        from presto_tpu.operators.driver import Driver
        from presto_tpu.runner.local import check_lifecycle
        dctx = DriverContext(profile=profile,
                             count_rows_ops=count_rows_ops)
        drivers = [Driver([f.create(dctx) for f in pipe])
                   for pipe in pipelines]
        from presto_tpu.execution.task_executor import (
            executor_for_session,
        )
        executor = executor_for_session(properties or {})
        if executor is not None:
            from presto_tpu.operators.base import run_deferred_checks
            from presto_tpu.session_properties import get_property
            executor.run_drivers(
                drivers, cancel=cancel, deadline=deadline,
                quantum_ms=get_property(properties or {},
                                        "task_executor_quantum_ms"),
                abort_check=lambda: failure[0] if failure else None,
                max_idle_s=max_idle_s, label="coordinator-root")
            run_deferred_checks(dctx)
            return drivers
        idle_since = None
        while True:
            if failure:
                raise failure[0]
            check_lifecycle(cancel, deadline)
            all_done = True
            progress = False
            for d in drivers:
                if not d.is_finished():
                    all_done = False
                    progress = d.process() or progress
            if all_done:
                from presto_tpu.operators.base import (
                    run_deferred_checks,
                )
                run_deferred_checks(dctx)
                return drivers
            if progress:
                idle_since = None
                continue
            # waiting on worker pages: sleep instead of pinning a core,
            # and bound the wait by wall clock (a hung-but-not-failed
            # worker must not wedge the coordinator forever)
            now = time.monotonic()
            if idle_since is None:
                idle_since = now
            elif now - idle_since > max_idle_s:
                raise RuntimeError(
                    f"query made no progress for {max_idle_s:.0f}s "
                    "(hung worker?)")
            time.sleep(0.002)


class StatementClient:
    """Minimal client protocol driver (reference: presto-client
    StatementClientV1.advance:323 following nextUri). `user`/`source`
    travel as X-Presto-User / X-Presto-Source and drive resource-group
    selection.

    Usable as a context manager: leaving the block cancels any query
    still in flight (the reference client's close() semantics), so

        with StatementClient(url) as c:
            c.execute(sql)

    never leaks a server-side RUNNING query on an exception."""

    def __init__(self, server: str, user: str = "",
                 source: str = ""):
        self.server = server.rstrip("/")
        self.user = user
        self.source = source
        #: ids of the in-flight queries (multiple when threads share
        #: the client) — what cancel() kills by default. A set under
        #: a lock, not a single slot: with concurrent executes a lone
        #: slot could resolve to None (no-op) or to ANOTHER thread's
        #: query (wrong kill)
        self._inflight: set = set()
        self._inflight_lock = sanitize.lock("client.inflight")

    def __enter__(self) -> "StatementClient":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.cancel()

    def cancel(self, query_id: Optional[str] = None) -> bool:
        """Kill `query_id` — or, with no argument, EVERY query this
        client currently has in flight (the connection-level cancel
        semantics of the reference client's close()) — server-side
        via DELETE /v1/statement/{id}. Safe to call from another
        thread while execute() polls; idempotent; False when there is
        nothing to cancel or no kill reached the server."""
        if query_id is not None:
            qids = [query_id]
        else:
            with self._inflight_lock:
                qids = list(self._inflight)
        ok = False
        for qid in qids:
            try:
                http_delete(f"{self.server}/v1/statement/{qid}",
                            timeout=10)
                ok = True
            except Exception:  # noqa: BLE001 — best-effort kill
                pass
        return ok

    def execute(self, sql: str, timeout: float = 600.0):
        headers = {}
        if self.user:
            headers["X-Presto-User"] = self.user
        if self.source:
            headers["X-Presto-Source"] = self.source
        resp = json.loads(http_post(
            f"{self.server}/v1/statement", sql.encode(),
            timeout=timeout, headers=headers))
        deadline = time.time() + timeout
        qid = resp["id"]
        with self._inflight_lock:
            self._inflight.add(qid)
        try:
            next_uri = resp["nextUri"]
            columns = None
            data: list = []
            while True:
                # deadline gates EVERY round trip — including result
                # paging of a FINISHED query (a slow multi-page fetch
                # must time out too, not just a slow execution)
                if time.time() > deadline:
                    # kill server-side FIRST: a client that walks away
                    # must not leave the query burning coordinator,
                    # worker, and cache budget to completion
                    self.cancel(qid)
                    raise QueryTimedOut(
                        f"query {qid} exceeded the client timeout "
                        f"({timeout:g}s); kill issued",
                        kind="client_timeout", query_id=qid)
                state = json.loads(http_get(next_uri))
                s = state["stats"]["state"]
                if "columns" in state and columns is None:
                    columns = state["columns"]
                if s == "FAILED":
                    err = state.get("error") or {}
                    kind = err.get("errorKind")
                    cls = QueryCancelled \
                        if kind in ("cancelled", "abandoned") \
                        else QueryTimedOut \
                        if kind == "deadline_exceeded" else QueryFailed
                    raise cls(err.get("message", "query failed"),
                              kind=kind, query_id=qid)
                if s == "FINISHED":
                    data.extend(state.get("data", []))
                    nxt = state.get("nextUri")
                    if nxt is None:
                        return columns, data
                    next_uri = nxt
                    continue
                next_uri = state["nextUri"]
                time.sleep(0.1)
        finally:
            with self._inflight_lock:
                self._inflight.discard(qid)
