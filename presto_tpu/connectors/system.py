"""System connector: engine state as queryable tables (reference: the
system connector `connector/system/` — system.runtime.nodes/queries —
and the jmx connector's introspection role).

Schemas:
  system.runtime.nodes    — node id, uri, state (single local node or
                            the coordinator's worker membership)
  system.runtime.queries  — the runner's query history (id, state,
                            rows, elapsed)
  system.metadata.catalogs — registered catalogs
  system.metadata.tables   — every (catalog, schema, table)

Tables materialize a host-side SNAPSHOT when the planner fetches the
schema (string dictionaries are plan-time static), and the scan serves
that same snapshot — a query observing the engine must not observe
itself mid-flight."""

from __future__ import annotations

import math
from typing import Callable, Dict, Iterator, List, Optional, Sequence

from presto_tpu.batch import Batch
from presto_tpu.connectors.spi import (
    Connector, ConnectorMetadata, ConnectorPageSource,
    ConnectorSplitManager, Split, TableHandle, TupleDomain,
)
from presto_tpu.schema import ColumnSchema, RelationSchema
from presto_tpu.types import BIGINT, DOUBLE, VARCHAR

#: table -> (column, type) list; all VARCHAR dictionaries derive from
#: the snapshot rows
_TABLES: Dict[str, List] = {
    # fleet membership + load feedback: the local node's own gauges
    # plus one row per heartbeat-monitored worker (executor queue
    # depth, reserved bytes, prewarm compile counts — the numbers
    # placement decisions read, now explainable from SQL)
    "runtime.nodes": [("node_id", VARCHAR), ("http_uri", VARCHAR),
                      ("state", VARCHAR), ("devices", BIGINT),
                      ("tasks_running", BIGINT),
                      ("executor_running", BIGINT),
                      ("executor_queued", BIGINT),
                      ("reserved_bytes", BIGINT),
                      ("prewarm_compiles", BIGINT),
                      ("rtt_ms", DOUBLE), ("flaps", BIGINT)],
    "runtime.queries": [("query_id", BIGINT), ("state", VARCHAR),
                        ("query", VARCHAR), ("output_rows", BIGINT),
                        ("elapsed_ms", DOUBLE),
                        ("error_kind", VARCHAR),
                        # QueryStats projection (telemetry): wall_ms
                        # mirrors elapsed_ms, queued_ms is admission
                        # wait (0 on a runner — no queue), compile_ms
                        # is the query's XLA-compile share, rows_out
                        # the lazily-resolved output row count,
                        # unattributed_ms the attribution ledger's
                        # coverage residual (-1 before the ledger
                        # closed / for non-query statements)
                        ("wall_ms", DOUBLE), ("queued_ms", DOUBLE),
                        ("compile_ms", DOUBLE),
                        ("rows_out", BIGINT),
                        ("unattributed_ms", DOUBLE)],
    "runtime.operator_stats": [
        ("query_id", BIGINT), ("pipeline", BIGINT),
        ("operator_id", BIGINT), ("name", VARCHAR),
        ("input_batches", BIGINT), ("input_rows", BIGINT),
        ("output_batches", BIGINT), ("output_rows", BIGINT),
        ("busy_ms", DOUBLE), ("compile_ms", DOUBLE),
        ("execute_ms", DOUBLE), ("blocked_ms", DOUBLE),
        ("cache_hits", BIGINT), ("cache_misses", BIGINT),
        ("peak_bytes", BIGINT)],
    "runtime.caches": [("level", VARCHAR), ("hits", BIGINT),
                       ("misses", BIGINT), ("evictions", BIGINT),
                       ("entries", BIGINT), ("bytes", BIGINT)],
    # the history-based-optimization store's live entries
    # (presto_tpu/history): one row per structural fingerprint with
    # its decayed measurements — the observable face of every
    # history-driven planner decision
    "runtime.plan_history": [
        ("fingerprint", VARCHAR), ("output_rows", BIGINT),
        ("input_rows", BIGINT), ("selectivity", DOUBLE),
        ("wall_ms", DOUBLE), ("peak_bytes", BIGINT),
        ("observations", BIGINT), ("age_ms", DOUBLE)],
    "metadata.catalogs": [("catalog_name", VARCHAR)],
    "metadata.tables": [("table_catalog", VARCHAR),
                        ("table_schema", VARCHAR),
                        ("table_name", VARCHAR)],
}


class SystemConnector(Connector):
    """`snapshot_fns` supplies each table's rows on demand; the runner
    wires its own state in at registration."""

    name = "system"

    def __init__(self, snapshot_fns: Dict[str, Callable[[], List[tuple]]]):
        self._fns = snapshot_fns
        self._snapshots: Dict[str, List[tuple]] = {}
        self._metadata = _SystemMetadata(self)
        self._splits = _SystemSplitManager()
        self._source = _SystemPageSource(self)

    def _key(self, handle: TableHandle) -> str:
        return f"{handle.schema}.{handle.table}"

    def snapshot(self, handle: TableHandle,
                 refresh: bool) -> List[tuple]:
        key = self._key(handle)
        if key not in _TABLES:
            raise KeyError(handle.table)
        if refresh or key not in self._snapshots:
            self._snapshots[key] = list(self._fns[key]())
        return self._snapshots[key]

    @property
    def metadata(self):
        return self._metadata

    @property
    def split_manager(self):
        return self._splits

    @property
    def page_source(self):
        return self._source


class _SystemMetadata(ConnectorMetadata):
    def __init__(self, conn: SystemConnector):
        self._conn = conn

    def list_schemas(self) -> List[str]:
        return sorted({k.split(".")[0] for k in _TABLES})

    def list_tables(self, schema: str) -> List[str]:
        return sorted(k.split(".")[1] for k in _TABLES
                      if k.startswith(schema + "."))

    def get_table_schema(self, handle: TableHandle) -> RelationSchema:
        key = self._conn._key(handle)
        if key not in _TABLES:
            raise KeyError(handle.table)
        # schema fetch = snapshot point: dictionaries are built from
        # the rows this query will scan
        rows = self._conn.snapshot(handle, refresh=True)
        cols = []
        for i, (name, typ) in enumerate(_TABLES[key]):
            dic = None
            if typ.is_string:
                dic = tuple(sorted({r[i] for r in rows
                                    if r[i] is not None}))
            cols.append(ColumnSchema(name, typ, dic))
        return RelationSchema.of(*cols)

    def estimate_row_count(self, handle: TableHandle) -> Optional[int]:
        try:
            return len(self._conn.snapshot(handle, refresh=False))
        except KeyError:
            return None


class _SystemSplitManager(ConnectorSplitManager):
    def get_splits(self, handle: TableHandle,
                   target_splits: int,
                   constraint=None) -> List[Split]:
        return [Split(handle, None, partition=0)]


class _SystemPageSource(ConnectorPageSource):
    def __init__(self, conn: SystemConnector):
        self._conn = conn

    def batches(self, split: Split, columns: Sequence[str],
                batch_rows: int,
                constraint: Optional[TupleDomain] = None
                ) -> Iterator[Batch]:
        key = self._conn._key(split.table)
        rows = self._conn.snapshot(split.table, refresh=False)
        names = [n for n, _ in _TABLES[key]]
        types = dict(_TABLES[key])
        idx = {n: i for i, n in enumerate(names)}
        data = {c: ([r[idx[c]] for r in rows], types[c])
                for c in columns}
        yield Batch.from_pydict(data)


def runner_system_connector(runner) -> SystemConnector:
    """The LocalRunner-backed instance: single local node, the
    runner's query history, and its catalog manager."""

    def nodes():
        # local node row: this process's own executor + memory gauges
        from presto_tpu import sanitize
        ex_running = ex_queued = 0
        try:
            from presto_tpu.execution.task_executor import (
                get_task_executor,
            )
            ex = get_task_executor(create=False)
            if ex is not None:
                snap = ex.snapshot()
                ex_running = snap["running_drivers"]
                ex_queued = sum(snap["queued_drivers"])
        except Exception:  # noqa: BLE001 — gauges are best-effort
            pass
        reserved = 0
        for pool in sanitize.tracked("memory_pool"):
            try:
                reserved += int(pool.reserved)
            except Exception:  # noqa: BLE001 — dying pool mid-sweep
                pass
        out = [("local-0", "local://in-process", "active", 1, 0,
                ex_running, ex_queued, reserved, -1, 0.0, 0)]
        # fleet rows: every heartbeat monitor of this process (the
        # coordinator's membership view) contributes its workers with
        # the load/memory feedback their last probe carried
        for monitor in sanitize.tracked("heartbeat_monitor"):
            try:
                rows = monitor.snapshot()
            except Exception:  # noqa: BLE001
                continue
            for w in rows:
                load = w.get("load") or {}
                mem = w.get("memory") or {}
                # node_id derives from the URL — stable across
                # membership changes and unique across monitors
                # (an enumeration index would be neither)
                host = w["url"].split("//", 1)[-1]
                out.append((
                    f"worker-{host}", w["url"], w["state"],
                    w.get("devices", 1),
                    int(load.get("tasks_running", 0)),
                    int(load.get("executor_running", 0)),
                    int(load.get("executor_queued", 0)),
                    int(mem.get("reserved_bytes", 0)),
                    w.get("prewarm_compiles")
                    if w.get("prewarm_compiles") is not None else -1,
                    w.get("rtt_ms") or 0.0,
                    int(w.get("flaps", 0))))
        return out

    def queries():
        # ids are the runner's monotonic sequence, stable across the
        # history cap trimming old entries; row counts resolve lazily
        # from the (weakly held) result — -1 once it is gone
        out = []
        for q in runner.query_history:
            rows = q["rows"]
            if rows is None:
                ref = q.get("_result")
                res = ref() if ref is not None else None
                # either way the answer is now final: cache it and
                # drop the ref so later snapshots do no work
                rows = q["rows"] = res.row_count \
                    if res is not None else -1
                q.pop("_result", None)
            unattr = q.get("unattributed_ms")
            out.append((q["id"], q["state"], q["sql"], rows,
                        q["elapsed_ms"], q.get("error_kind"),
                        q["elapsed_ms"], q.get("queued_ms", 0.0),
                        q.get("compile_ms", 0.0), rows,
                        unattr if unattr is not None else -1.0))
        return out

    def operator_stats():
        # per-operator drain snapshots of recent queries (rows/bytes
        # populate under EXPLAIN ANALYZE; batch/kernel/cache counters
        # always) — the system-table face of the QueryStats tree
        out = []
        for rec in runner.operator_stats_history:
            for pi, ops in enumerate(rec["pipelines"]):
                for s in ops:
                    out.append((
                        rec["query_id"], pi, s["operator_id"],
                        s["name"], s["input_batches"],
                        s["input_rows"], s["output_batches"],
                        s["output_rows"],
                        round(s["busy_seconds"] * 1e3, 3),
                        round(s.get("compile_ns", 0) / 1e6, 3),
                        round(s.get("execute_ns", 0) / 1e6, 3),
                        round(s.get("blocked_ns", 0) / 1e6, 3),
                        s.get("cache_hits", 0),
                        s.get("cache_misses", 0),
                        s.get("peak_bytes", 0)))
        return out

    def catalogs():
        return [(c,) for c in runner.catalogs.catalogs()]

    def caches():
        # the process-wide cache hierarchy's live counters; stable
        # zeroed rows when no manager exists yet (caches never used)
        from presto_tpu.cache import get_cache_manager
        mgr = get_cache_manager(create=False)
        if mgr is None:
            return [(level, 0, 0, 0, 0, 0)
                    for level in ("plan", "fragment", "page")]
        return mgr.snapshot_rows()

    def plan_history():
        # zero rows (stable schema) when no store exists yet
        from presto_tpu.history import get_history_store
        store = get_history_store(create=False)
        return store.snapshot_rows() if store is not None else []

    def tables():
        out = []
        for cat in runner.catalogs.catalogs():
            if cat == "system":
                for key in _TABLES:
                    s, t = key.split(".")
                    out.append((cat, s, t))
                continue
            conn = runner.catalogs.connector(cat)
            try:
                for schema in conn.metadata.list_schemas():
                    for t in conn.metadata.list_tables(schema):
                        out.append((cat, schema, t))
            except Exception:  # noqa: BLE001 — best-effort listing
                continue
        return out

    return SystemConnector({
        "runtime.nodes": nodes,
        "runtime.queries": queries,
        "runtime.caches": caches,
        "runtime.plan_history": plan_history,
        "runtime.operator_stats": operator_stats,
        "metadata.catalogs": catalogs,
        "metadata.tables": tables,
    })
