"""LocalRunner: parse -> plan -> prune -> pipelines -> drivers -> result
in one process with no RPC (reference: testing/LocalQueryRunner.java:665
execute -> executeInternal -> createDrivers, plus the round-robin drive
loop standing in for TaskExecutor time slicing)."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

from presto_tpu.batch import Batch
from presto_tpu.connectors.spi import Connector, TableHandle
from presto_tpu.operators.base import DriverContext
from presto_tpu.operators.driver import Driver
from presto_tpu.parser import parse_statement, tree as T
from presto_tpu.planner import nodes as N
from presto_tpu.planner.analyzer import AnalysisError, plan_statement
from presto_tpu.planner.local_planner import (
    LocalExecutionPlan, LocalExecutionPlanner,
)
from presto_tpu.schema import RelationSchema


class QueryError(Exception):
    """Engine-facing query failure. `kind` is the structured failure
    taxonomy the lifecycle layer switches on (and `system.runtime.
    queries` / the client protocol surface): "cancelled",
    "deadline_exceeded", "abandoned", or None for ordinary errors."""

    def __init__(self, message: str, kind: Optional[str] = None):
        super().__init__(message)
        self.kind = kind


def check_lifecycle(cancel, deadline: Optional[float]) -> None:
    """THE cooperative kill/deadline checkpoint, shared by every
    drive loop (local runner, mesh phases, the coordinator's root
    drive): polls the cancel callable, then the monotonic deadline,
    and raises the structured QueryError kinds. One copy so the
    message text and kind strings can never drift between loops."""
    if cancel is not None and cancel():
        raise QueryError("query cancelled", kind="cancelled")
    if deadline is not None:
        import time as _time
        if _time.monotonic() > deadline:
            raise QueryError(
                "query exceeded query_max_run_time_ms",
                kind="deadline_exceeded")


#: plugin_dir -> PluginRegistry — module EXECUTION (the expensive,
#: side-effecting part) happens once per process; each runner still
#: builds its own connector instances from the cached factories, so
#: runners stay isolated (a shared stateful connector would leak one
#: session's tables into another). Guarded: concurrent first loads
#: must not exec plugin modules twice.
_PLUGIN_REGISTRY_CACHE: Dict[str, Any] = {}
import itertools as _itertools
import threading as _threading
from presto_tpu import sanitize as _sanitize
_PLUGIN_CACHE_LOCK = _sanitize.lock("runner.plugin_cache")
#: identity tokens minted for unhashable access-control objects and
#: STAMPED onto them (like Connector.cache_token) — the token dies
#: with the policy, so nothing is pinned and a recycled address can
#: never alias a different policy's cached plans
_AC_TOKEN_MINT = _itertools.count()
_AC_TOKEN_LOCK = _sanitize.lock("runner.ac_token")


@dataclasses.dataclass
class Session:
    catalog: str = "tpch"
    schema: str = "tiny"
    properties: Dict[str, Any] = dataclasses.field(default_factory=dict)
    user: str = ""  # identity for access control + resource groups
    #: True on the per-request override minted by execute_as: its
    #: properties dict is a request-scoped copy, so SET/RESET SESSION
    #: would silently evaporate — those statements reject instead
    request_scoped: bool = False


class CatalogManager:
    """Reference: metadata/CatalogManager + MetadataManager.java:124.
    `access_control`, when set, gates table reads at name resolution
    (spi/security SystemAccessControl.checkCanSelectFromColumns)."""

    def __init__(self):
        self._connectors: Dict[str, Connector] = {}
        self.access_control = None

    def register(self, name: str, connector: Connector) -> None:
        self._connectors[name] = connector

    def connector(self, name: str) -> Connector:
        if name not in self._connectors:
            raise QueryError(f"catalog {name!r} does not exist")
        return self._connectors[name]

    def catalogs(self) -> List[str]:
        return sorted(self._connectors)

    @staticmethod
    def handle_for(parts: Tuple[str, ...],
                   session: Session) -> TableHandle:
        """Qualified name -> TableHandle with session defaults filled
        in (the one place name resolution lives)."""
        if len(parts) == 1:
            return TableHandle(session.catalog, session.schema,
                               parts[0])
        if len(parts) == 2:
            return TableHandle(session.catalog, parts[0], parts[1])
        if len(parts) == 3:
            return TableHandle(parts[0], parts[1], parts[2])
        raise QueryError(f"invalid table name {'.'.join(parts)}")

    def check_access(self, kind: str, user: str,
                     handle: TableHandle) -> None:
        """Gate `kind` ("select" | "write") on the handle; raises
        QueryError on denial. The ONE access-check path for reads
        (name resolution) and writes (sink acquisition)."""
        if self.access_control is None:
            return
        from presto_tpu.execution.access_control import (
            AccessDeniedError,
        )
        try:
            if kind == "select":
                self.access_control.check_can_select(user, handle)
            else:
                self.access_control.check_can_write(user, handle)
        except AccessDeniedError as e:
            raise QueryError(str(e)) from e

    def resolve_table(self, parts: Tuple[str, ...], session: Session
                      ) -> Tuple[TableHandle, RelationSchema]:
        handle = self.handle_for(parts, session)
        self.check_access("select", getattr(session, "user", ""),
                          handle)
        conn = self.connector(handle.catalog)
        try:
            schema = conn.metadata.get_table_schema(handle)
        except KeyError:
            raise QueryError(f"table {handle} does not exist") from None
        return handle, schema


def _rename_form_slots(form, plan_sym: str, stored_name: str):
    """Rebuild a plan-symbol form over STORED column names (the
    <stored_name>__suffix convention), returning (stored form,
    {stored name -> plan slot symbol})."""
    from presto_tpu.expr.ir import ArrayValue, InputRef, MapValue

    src_map: Dict[str, Optional[str]] = {}

    def ren(x):
        if not isinstance(x, InputRef):
            raise QueryError(
                "cannot write a complex column whose form is not "
                "slot-backed")
        assert x.name.startswith(plan_sym + "__"), x.name
        stored = stored_name + x.name[len(plan_sym):]
        src_map[stored] = x.name
        return InputRef(stored, x.type)

    if isinstance(form, ArrayValue):
        out = ArrayValue(tuple(ren(e) for e in form.elements),
                         ren(form.length)
                         if form.length is not None else None,
                         form.type)
    elif isinstance(form, MapValue):
        out = MapValue(tuple(ren(e) for e in form.keys),
                       tuple(ren(e) for e in form.values),
                       ren(form.length)
                       if form.length is not None else None,
                       form.type)
    else:
        raise QueryError("cannot write row-typed columns yet")
    return out, src_map


def _count_params(node) -> int:
    """Number of `?` placeholders in a statement AST (their indexes
    are assigned in parse order, so count == max index + 1)."""
    n = 0
    for sub in _walk_ast(node):
        if isinstance(sub, T.Parameter):
            n = max(n, sub.index + 1)
    return n


def _walk_ast(node):
    import dataclasses as _dc
    if isinstance(node, T.Node):
        yield node
        if _dc.is_dataclass(node):
            for f in _dc.fields(node):
                yield from _walk_ast(getattr(node, f.name))
    elif isinstance(node, (list, tuple)):
        for x in node:
            yield from _walk_ast(x)


def _substitute_params(node, args):
    """Rebuild a prepared statement's AST with each `?` replaced by
    the corresponding USING argument expression (reference:
    sql/ParameterRewriter)."""
    import dataclasses as _dc
    if isinstance(node, T.Parameter):
        return args[node.index]
    if isinstance(node, T.Node) and _dc.is_dataclass(node):
        changes = {}
        for f in _dc.fields(node):
            v = getattr(node, f.name)
            nv = _sub_val(v, args)
            if nv is not v:
                changes[f.name] = nv
        return _dc.replace(node, **changes) if changes else node
    return node


def _sub_val(v, args):
    if isinstance(v, T.Node):
        return _substitute_params(v, args)
    if isinstance(v, list):
        out = [_sub_val(x, args) for x in v]
        return out if any(a is not b for a, b in zip(out, v)) else v
    if isinstance(v, tuple):
        out = tuple(_sub_val(x, args) for x in v)
        return out if any(a is not b for a, b in zip(out, v)) else v
    return v


def _assemble_form(form, cols: Dict[str, list], nrows: int) -> list:
    """Per-row python values of a complex field from its slot-column
    pylists. Leaves are InputRefs into `cols` or Literals."""
    from presto_tpu.expr.ir import (
        ArrayValue, InputRef, Literal, MapValue, RowValue,
    )

    def leaf(e) -> list:
        if isinstance(e, InputRef):
            return cols[e.name]
        if isinstance(e, Literal):
            return [e.value] * nrows
        raise QueryError(
            "complex output columns must be slot references "
            f"(got {type(e).__name__})")

    if isinstance(form, ArrayValue):
        elem_cols = [leaf(x) for x in form.elements]
        lens = leaf(form.length) if form.length is not None \
            else [len(elem_cols)] * nrows
        return [
            None if lens[i] is None else
            [c[i] for c in elem_cols[:int(lens[i])]]
            for i in range(nrows)
        ]
    if isinstance(form, MapValue):
        kc = [leaf(x) for x in form.keys]
        vc = [leaf(x) for x in form.values]
        lens = leaf(form.length) if form.length is not None \
            else [len(kc)] * nrows
        return [
            None if lens[i] is None else
            {k[i]: v[i] for k, v in
             zip(kc[:int(lens[i])], vc[:int(lens[i])])}
            for i in range(nrows)
        ]
    if isinstance(form, RowValue):
        fc = [leaf(x) for _, x in form.fields]
        return [tuple(c[i] for c in fc) for i in range(nrows)]
    raise QueryError(f"unsupported output form {type(form).__name__}")


class MaterializedResult:
    def __init__(self, names: List[str], batches: List[Batch],
                 fields: Tuple[N.Field, ...]):
        self.names = names
        self.batches = batches
        self.fields = fields

    @property
    def row_count(self) -> int:
        return sum(b.num_valid() for b in self.batches)

    def rows(self) -> List[Tuple]:
        forms = [getattr(f, "form", None) for f in self.fields] \
            if self.fields else []
        if not any(f is not None for f in forms):
            out: List[Tuple] = []
            for b in self.batches:
                out.extend(b.to_pylist())
            return out
        # complex-typed outputs: assemble array/map/row python values
        # from their exploded slot columns (see nodes.Field.form)
        out = []
        for b in self.batches:
            cols = b.to_pydict()  # keyed by symbol
            nrows = len(next(iter(cols.values()))) if cols else 0
            per_field = []
            for f, form in zip(self.fields, forms):
                if form is None:
                    per_field.append(cols[f.symbol])
                else:
                    per_field.append(
                        _assemble_form(form, cols, nrows))
            out.extend(zip(*per_field))
        return out

    def to_pandas(self):
        import pandas as pd
        if any(getattr(f, "form", None) is not None
               for f in (self.fields or ())):
            # complex-typed columns: assemble through the form-aware
            # row path (the raw batches hold W+1 slot columns each)
            return pd.DataFrame(self.rows(), columns=self.names)
        if not self.batches:
            return pd.DataFrame(columns=self.names)
        frames = [b.to_pandas() for b in self.batches]
        df = pd.concat(frames, ignore_index=True)
        df.columns = self.names
        return df

    def __repr__(self):
        return f"MaterializedResult({self.row_count} rows: {self.names})"


class LocalRunner:
    def __init__(self, catalog: str = "tpch", schema: str = "tiny",
                 properties: Optional[Dict[str, Any]] = None,
                 user: str = "", access_control=None,
                 compilation_cache_dir: Optional[str] = None,
                 resource_groups=None,
                 history_dir: Optional[str] = None):
        # persistent XLA compilation cache: explicit arg wins, else
        # the one default rule (JAX_COMPILATION_CACHE_DIR, or
        # <checkout>/.jax_cache off the CPU); process-global — jax
        # holds one cache dir
        from presto_tpu.execution import compile_cache
        compile_cache.configure(compilation_cache_dir)
        # history-based optimization store (presto_tpu/history): same
        # surface shape as the compile cache — explicit arg wins, else
        # PRESTO_TPU_HISTORY_DIR; both process-global. A restarted
        # process loads persisted measurements and plans from them
        # with zero re-measurement (docs/ADAPTIVE.md)
        from presto_tpu import history as _history
        if history_dir is not None:
            _history.configure(history_dir)
        else:
            _history.configure_from_env()
        from presto_tpu.connectors.memory import (
            BlackholeConnector, MemoryConnector,
        )
        from presto_tpu.connectors.files import FileConnector
        from presto_tpu.connectors.tpch import TpchConnector
        from presto_tpu.connectors.tpcds import TpcdsConnector
        self.catalogs = CatalogManager()
        self.catalogs.register("tpch", TpchConnector())
        self.catalogs.register("tpcds", TpcdsConnector())
        self.catalogs.register("memory", MemoryConnector())
        self.catalogs.register("blackhole", BlackholeConnector())
        self.catalogs.register("file", FileConnector())
        # engine state as tables (system.runtime / system.metadata)
        from presto_tpu.connectors.system import runner_system_connector
        self.query_history: List[Dict[str, Any]] = []
        #: recent queries' per-operator stats snapshots (bounded ring)
        #: — the system.runtime.operator_stats source
        self.operator_stats_history: List[Dict[str, Any]] = []
        self.catalogs.register("system", runner_system_connector(self))
        self._session_tl = _threading.local()
        self._query_id_mint = _itertools.count()
        self.session = Session(catalog, schema, dict(properties or {}),
                               user=user)
        self.catalogs.access_control = access_control
        #: optional admission control for EMBEDDED callers (a
        #: ResourceGroupManager): every execute() then submits through
        #: per-user fair queueing + caps before planning, and sheds
        #: with structured QueryError kinds instead of piling up.
        #: None (the default) = unguarded, the classic local runner.
        #: The single-node coordinator admits at its HTTP layer and
        #: builds its embedded runner WITHOUT one — admission must
        #: gate each query exactly once.
        self.resource_groups = resource_groups
        self._load_plugins()

    def _load_plugins(self) -> None:
        """Plugin + catalog-properties loading (reference:
        PluginManager + StaticCatalogStore): PRESTO_TPU_PLUGIN_DIR
        holds plugin modules contributing connector factories;
        PRESTO_TPU_CATALOG_DIR holds <catalog>.properties files with
        connector.name=<factory> lines."""
        import os
        plugin_dir = os.environ.get("PRESTO_TPU_PLUGIN_DIR")
        catalog_dir = os.environ.get("PRESTO_TPU_CATALOG_DIR")
        if not plugin_dir and not catalog_dir:
            return
        from presto_tpu.connectors.files import FileConnector
        from presto_tpu.connectors.memory import MemoryConnector
        from presto_tpu.connectors.tpch import TpchConnector
        from presto_tpu.server.plugins import (
            PluginRegistry, load_catalogs, load_plugins,
        )
        # module EXECUTION memoized per process (the server builds a
        # runner per statement/task; re-exec'ing plugin files each
        # query would put import side effects on the hot path);
        # connector INSTANCES stay per-runner for session isolation
        with _PLUGIN_CACHE_LOCK:
            reg = _PLUGIN_REGISTRY_CACHE.get(plugin_dir or "")
            if reg is None:
                reg = PluginRegistry()
                reg.register_connector_factory(
                    "file",
                    lambda cfg: FileConnector(cfg.get("file.root")))
                reg.register_connector_factory(
                    "memory", lambda cfg: MemoryConnector())
                reg.register_connector_factory(
                    "tpch", lambda cfg: TpchConnector())
                if plugin_dir:
                    load_plugins(plugin_dir, reg)
                _PLUGIN_REGISTRY_CACHE[plugin_dir or ""] = reg
        if catalog_dir:
            load_catalogs(catalog_dir, reg, self.catalogs)

    def register_connector(self, name: str, connector: Connector):
        self.catalogs.register(name, connector)

    def prewarm(self, statements, user: str = "prewarm") -> Dict:
        """AOT-compile the kernels `statements` need (see
        execution/compile_cache.prewarm): with a persistent
        compilation cache configured, a restarted process re-traces
        against disk-cached executables in ~ms each, so serving
        traffic after prewarm performs zero fresh compiles."""
        from presto_tpu.execution import compile_cache
        return compile_cache.prewarm(self, statements, user=user)

    # ------------------------------------------------------------------

    _cluster_mgr_lock = _sanitize.lock("runner.cluster_mgr")
    #: process-wide query-id mint for cluster-memory tracking
    #: (itertools.count.__next__ is atomic under the GIL)
    _cm_qid_mint = _itertools.count()

    def _cluster_memory(self, session):
        """The shared cross-query memory arbiter, when the session
        sets cluster_memory_bytes (reference: ClusterMemoryManager —
        one per coordinator process). Creation is locked: two
        concurrent queries must attach to ONE manager or the budget
        silently splits."""
        from presto_tpu.session_properties import get_property
        budget = get_property(session.properties,
                              "cluster_memory_bytes")
        if not budget:
            return None
        with self._cluster_mgr_lock:
            cm = getattr(self, "_cluster_mgr", None)
            if cm is None or cm.budget != int(budget):
                from presto_tpu.execution.cluster_memory import (
                    ClusterMemoryManager,
                )
                cm = ClusterMemoryManager(int(budget))
                self._cluster_mgr = cm
            return cm

    # -- per-thread profile scratch (the shared single-node runner is
    # driven by many client threads concurrently: one query's EXPLAIN
    # ANALYZE must never render another query's stats) ----------------

    @property
    def _last_profile(self) -> Optional[str]:
        return getattr(self._session_tl, "last_profile", None)

    @_last_profile.setter
    def _last_profile(self, value) -> None:
        self._session_tl.last_profile = value

    @property
    def _last_annotate(self):
        return getattr(self._session_tl, "last_annotate", None)

    @_last_annotate.setter
    def _last_annotate(self, value) -> None:
        self._session_tl.last_annotate = value

    @property
    def session(self) -> Session:
        """The effective session: a THREAD-LOCAL override (set by the
        width-retry loop) or the runner's base session. Concurrent
        queries on one runner must not see each other's in-flight
        retry overrides."""
        o = getattr(self._session_tl, "override", None)
        return o if o is not None else self._session

    @session.setter
    def session(self, value: Session) -> None:
        self._session = value

    def _with_width_retry(self, fn):
        """Re-plan + re-run on array_agg width overflow: the element
        capacity is baked into the plan's value forms at ANALYSIS
        time, so unlike max_groups this retry must rebuild the plan.
        The bumped session rides a thread-local override — other
        threads' statements keep planning at the base width. The
        PREVIOUS override (execute_as's per-request identity) is
        restored, not cleared: dropping it would hand the rest of the
        request the runner's default identity."""
        from presto_tpu.operators.array_agg import ArrayAggWidthExceeded
        prev = getattr(self._session_tl, "override", None)
        try:
            while True:
                try:
                    return fn()
                except ArrayAggWidthExceeded as e:
                    if e.suggested > 1 << 14:
                        raise QueryError(
                            "array_agg exceeds the supported element "
                            "count") from e
                    self._session_tl.override = dataclasses.replace(
                        self.session, properties={
                            **self.session.properties,
                            "array_agg_width": e.suggested})
        finally:
            self._session_tl.override = prev

    def execute_as(self, sql: str, user: str, cancel=None,
                   deadline: Optional[float] = None,
                   query_id: str = "") -> MaterializedResult:
        """Execute with a per-request identity (the single-node
        coordinator's path: many users share one runner). The user
        rides the THREAD-LOCAL session override, so analysis-time
        access checks — and the plan-cache key, which includes the
        user — see the caller, not the runner's default identity.
        The override gets its OWN properties dict — a shared dict
        would let one HTTP client resize caches or flip planner
        behavior mid-flight for every other user of the shared
        runner — and is marked request_scoped so SET/RESET SESSION
        reject loudly instead of silently evaporating with the
        copy. `query_id`, the server's identifier of the request,
        rides the same thread-local to the statement's ledger, whose
        frames carry it on the profiler's timeline."""
        self._session_tl.override = dataclasses.replace(
            self._session, user=user,
            properties=dict(self._session.properties),
            request_scoped=True)
        self._session_tl.query_id = query_id
        try:
            return self.execute(sql, cancel=cancel, deadline=deadline)
        finally:
            self._session_tl.override = None
            self._session_tl.query_id = ""

    def _reject_request_scoped_mutation(self) -> None:
        """SET/RESET SESSION on a request-scoped session would mutate
        a copy that dies with the request — a success row followed by
        no effect. Servers that want durable per-client properties
        must pass them at Coordinator construction."""
        if getattr(self.session, "request_scoped", False):
            raise QueryError(
                "SET/RESET SESSION is not supported over the "
                "single-node coordinator: sessions are per-request; "
                "configure properties on the Coordinator instead")

    def execute(self, sql: str, cancel=None,
                deadline: Optional[float] = None) -> MaterializedResult:
        """`cancel` is an optional () -> bool polled at every
        drive-loop round (cooperative kill); `deadline` an optional
        time.monotonic() instant enforced at the same checkpoints.
        The session's own `query_max_run_time_ms` tightens the
        deadline — whichever comes first wins. Both ride a THREAD-
        LOCAL (like the session override) so the whole statement tree
        — width retries, write wrappers, EXPLAIN ANALYZE — shares one
        lifecycle without threading two parameters through every
        call."""
        import time as _time
        from presto_tpu.session_properties import get_property
        limit_ms = get_property(self.session.properties,
                                "query_max_run_time_ms")
        if limit_ms:
            d = _time.monotonic() + float(limit_ms) / 1000.0
            deadline = d if deadline is None else min(deadline, d)
        if self.resource_groups is None:
            result = self._execute_admitted(sql, cancel, deadline)
            if _sanitize.ARMED:
                # query-finish checkpoint: every tracked ledger must
                # balance once this statement's drivers closed
                _sanitize.audit()
            return result
        # embedded admission control: submit through the runner's
        # resource groups (per-user fair queueing, caps, shedding)
        # before any planning work happens; the released slot
        # dispatches the next queued query weighted-fair
        group, mem, queued_ms = self._admit(cancel, deadline)
        self._session_tl.queued_ms = queued_ms
        try:
            result = self._execute_admitted(sql, cancel, deadline)
        finally:
            self._session_tl.queued_ms = 0.0
            # release EXACTLY the reservation _admit charged — the
            # statement may have mutated query_memory_bytes (SET
            # SESSION), and recomputing here would corrupt the
            # group's memory ledger permanently
            self.resource_groups.finish(group, mem)
        if _sanitize.ARMED:
            _sanitize.audit()
        return result

    def _admit(self, cancel, deadline: Optional[float]):
        """Submit this statement to the runner's ResourceGroupManager
        under the session identity. Returns (group_path,
        charged_memory_bytes, queued_ms) once a slot is granted;
        raises structured QueryErrors for
        every shed/kill shape: kind="rejected" (no selector match,
        impossible reservation, admission_queue_timeout_ms shed),
        kind="queue_full" (queue bound), kind="deadline_exceeded"
        (query_max_run_time_ms expired WHILE QUEUED — the query never
        schedules), kind="cancelled" (killed while queued). A query
        failed here charged no slot, no MemoryPool reservation, and
        no lifecycle task — there is nothing to leak."""
        import time as _time
        from presto_tpu.execution.resource_groups import QueryRejected
        from presto_tpu.session_properties import get_property
        from presto_tpu.telemetry.metrics import METRICS
        s = self.session
        mem = int(get_property(s.properties,
                               "query_memory_bytes") or 0)
        qt_ms = get_property(s.properties,
                             "admission_queue_timeout_ms")
        qdeadline = deadline
        shed_kind = "deadline_exceeded"
        if qt_ms:
            qd = _time.monotonic() + float(qt_ms) / 1000.0
            if qdeadline is None or qd < qdeadline:
                qdeadline = qd
                shed_kind = "rejected"
        ev = _threading.Event()
        # ONE bound-method object for submit AND cancel_queued: the
        # manager matches queued entries by callback IDENTITY, and
        # every `ev.set` attribute access mints a fresh bound method
        # — passing a second one could never match
        dispatch = ev.set
        expired: List[str] = []

        def on_expire():
            expired.append(shed_kind)
            ev.set()

        def shed_error():
            if shed_kind == "rejected":
                return QueryError(
                    "query shed: queue wait exceeded "
                    "admission_queue_timeout_ms", kind="rejected")
            return QueryError(
                "query exceeded query_max_run_time_ms while queued",
                kind="deadline_exceeded")

        try:
            state, group = self.resource_groups.submit(
                getattr(s, "user", ""), "", mem,
                on_dispatch=dispatch,
                deadline=qdeadline, on_expire=on_expire)
        except QueryRejected as e:
            err = QueryError(str(e),
                             kind=getattr(e, "kind", None)
                             or "rejected")
            METRICS.inc("presto_tpu_queries_total", state="FAILED",
                        error_kind=err.kind)
            raise err from e
        if state == "run":
            return group, mem, 0.0
        t0 = _time.monotonic()
        while not ev.wait(0.05):
            if cancel is not None and cancel():
                if self.resource_groups.cancel_queued(group,
                                                      dispatch):
                    METRICS.inc("presto_tpu_queries_total",
                                state="FAILED",
                                error_kind="cancelled")
                    raise QueryError("query cancelled",
                                     kind="cancelled")
            if qdeadline is not None \
                    and _time.monotonic() > qdeadline:
                if self.resource_groups.cancel_queued(group,
                                                      dispatch):
                    err = shed_error()
                    METRICS.inc("presto_tpu_queries_total",
                                state="FAILED", error_kind=err.kind)
                    raise err
                # lost the race to a concurrent dispatch: run — the
                # deadline trips at the first drive checkpoint
        if expired:
            # the manager's own sweep dropped the entry (no slot was
            # ever charged)
            err = shed_error()
            METRICS.inc("presto_tpu_queries_total", state="FAILED",
                        error_kind=err.kind)
            raise err
        return group, mem, (_time.monotonic() - t0) * 1000.0

    def _execute_admitted(self, sql: str, cancel,
                          deadline: Optional[float]
                          ) -> MaterializedResult:
        import time as _time
        from presto_tpu.session_properties import get_property
        # session-property fault channel: applied (or, when the
        # property is empty/absent again, REMOVED) idempotently —
        # ensure_spec never touches API/env-armed injections
        from presto_tpu.execution import faults
        faults.ensure_spec(
            self.session.properties.get("fault_injection"))
        # telemetry: per-statement kernel counters always (cheap ints
        # on a thread-local), a trace recorder only when the session
        # asks for one (query_trace_enabled)
        from presto_tpu.telemetry import build_query_stats
        from presto_tpu.telemetry import flight as _flight
        from presto_tpu.telemetry import kernels as _tk
        from presto_tpu.telemetry import ledger as _ledger
        from presto_tpu.telemetry import trace as _trace
        recorder = None
        prev_rec = None
        activated = False
        if bool(get_property(self.session.properties,
                             "query_trace_enabled")):
            recorder = _trace.TraceRecorder()
            prev_rec = _trace.activate(recorder)
            activated = True
        prev_q = _tk.begin_query()
        # wall-attribution ledger (telemetry/ledger.py): one per
        # statement, installed on this thread (executor quanta
        # re-install it like the kernel counters). Admission-queue
        # wait happened BEFORE this frame — charge it up front so the
        # finished wall (queue + execution) is fully decomposed.
        led = _ledger.QueryLedger(
            getattr(self._session_tl, "query_id", ""))
        prev_led = _ledger.install(led)
        queued_ns = int((getattr(self._session_tl, "queued_ms", 0.0)
                         or 0.0) * 1e6)
        if queued_ns:
            led.charge("queued", queued_ns)
        #: the statement's history entry, set by _new_history_entry so
        #: the ledger's residual can land on system.runtime.queries;
        #: cleared here so a SHOW/SET statement never annotates a
        #: previous SELECT's row
        self._session_tl.history_entry = None
        prev = getattr(self._session_tl, "lifecycle", None)
        self._session_tl.lifecycle = (cancel, deadline)
        self._session_tl.op_stats = None  # this statement's snapshots
        self._session_tl.fusion_report = None  # planner/fusion.py
        # kernel shape bucketing rides a thread-local gate (operators
        # have no session access): honored by every drive loop this
        # statement runs on THIS thread — remote tasks use the process
        # default
        from presto_tpu import batch as _batch
        prev_sb = _batch.set_shape_buckets(
            bool(get_property(self.session.properties,
                              "kernel_shape_buckets")))
        t0 = _time.perf_counter()
        t0_ns = _time.perf_counter_ns()
        # statement start for sub-renderers that close the ledger
        # mid-statement (EXPLAIN ANALYZE's wall-attribution section)
        self._session_tl.statement_t0_ns = t0_ns
        try:
            # the whole statement runs under a top-level `driver`
            # frame: prologue/epilogue host overhead (session setup,
            # history bookkeeping, GIL preemption inside un-spanned
            # sections) is driver/executor overhead by definition;
            # nested planning/scan/kernel/... spans subtract, and the
            # executor wait is absorbed (run_drivers) so worker-thread
            # quanta never double-book it
            with _ledger.span("driver.quantum", detail="statement",
                              query_id=led.query_id):
                result = self._execute_lifecycled(sql)
        except BaseException as e:
            # a FAILED traced query keeps its timeline: events (root
            # span included) ride the exception; servers forward them
            # to the trace endpoint
            _trace.attach_failure(recorder, e, t0_ns, sql)
            recorder = None  # root span already closed
            # ... and its QueryStats: a query killed after 15s of XLA
            # compiles must still report that compile time (failure is
            # exactly when you want the attribution)
            try:
                e.query_stats = build_query_stats(
                    (_time.perf_counter() - t0) * 1000, 0.0,
                    _tk.query_counters())
            except Exception:  # noqa: BLE001 — slotted exceptions
                pass
            # EVERY statement counts exactly once, whatever its shape
            # (SELECT, SHOW/SET, DDL, even unparseable text) — the
            # per-topology counter on /v1/metrics must match the
            # query registry, not just the SELECT-shaped subset
            from presto_tpu.telemetry.metrics import METRICS
            METRICS.inc("presto_tpu_queries_total", state="FAILED",
                        error_kind=getattr(e, "kind", None)
                        or type(e).__name__)
            # flight recorder: the failure edge plus the recent window
            # riding the error payload (the always-on post-mortem)
            if _flight.ENABLED:
                _flight.record("query", "FAILED",
                               getattr(e, "kind", None)
                               or type(e).__name__, sql[:80])
                _flight.attach_failure(e)
            raise
        finally:
            self._session_tl.lifecycle = prev
            _batch.set_shape_buckets(prev_sb)
            counters = _tk.end_query(prev_q)
            if recorder is not None:
                recorder.add("query", "query", t0_ns,
                             _time.perf_counter_ns() - t0_ns,
                             {"sql": sql[:200]})
            if activated:
                _trace.deactivate(prev_rec)
            # close the attribution ledger against the full wall
            # (queue wait + execution) and surface it everywhere the
            # query's stats go: query_stats (success AND failure —
            # the exception is live in sys.exc_info here), the
            # history entry behind system.runtime.queries, and the
            # process counters + unattributed-ratio histogram
            _ledger.uninstall(prev_led)
            led_doc = led.finish(
                queued_ns + (_time.perf_counter_ns() - t0_ns))
            _ledger.publish(led_doc)
            entry = getattr(self._session_tl, "history_entry", None)
            if entry is not None:
                entry["unattributed_ms"] = led_doc["unattributed_ms"]
                self._session_tl.history_entry = None
            import sys as _sys
            _exc = _sys.exc_info()[1]
            if _exc is not None:
                qs = getattr(_exc, "query_stats", None)
                if isinstance(qs, dict):
                    qs["ledger"] = led_doc
        from presto_tpu.telemetry.metrics import METRICS
        METRICS.inc("presto_tpu_queries_total", state="FINISHED",
                    error_kind="")
        # the full stats tree rides the result so servers (the single-
        # node coordinator) can expose it without reaching back into
        # runner internals
        if _flight.ENABLED:
            _flight.record("query", "FINISHED", "", sql[:80])
        ops = getattr(self._session_tl, "op_stats", None)
        result.query_stats = build_query_stats(
            (_time.perf_counter() - t0) * 1000, 0.0, counters,
            tasks=[{"task_id": "local", "pipelines": ops}]
            if ops is not None else None)
        result.query_stats["ledger"] = led_doc
        result.trace_events = recorder.events() \
            if recorder is not None else None
        if result.trace_events:
            # traced queries additionally carry the blocking chain
            # that determined their wall, in ledger vocabulary —
            # GET /v1/query/{id} and query_doctor consume it
            from presto_tpu.telemetry import critical_path as _cp
            try:
                result.query_stats["critical_path"] = \
                    _cp.extract(result.trace_events)
            except Exception:  # noqa: BLE001 — stats stay servable
                pass
        # whole-fragment fusion report (fused chains + fallback
        # reasons) rides the result for tools/fusion_report.py and
        # the bench JSON schemas
        report = getattr(self._session_tl, "fusion_report", None)
        from presto_tpu.telemetry import kernels as _tk
        if report is not None and _tk.SIGNATURE_TRACKING:
            # kernel-contract cross-check surface: per-family distinct
            # input signatures observed so far (the PREDICTED compile
            # ceiling under the static contracts, tools/kernelcheck) —
            # analysis/runtime.cross_check compares them against the
            # live kernel_retrace_total deltas, and a divergence fails
            # the serving gate in tests/test_kernelcheck.py
            report = dict(report)
            report["kernel_families"] = _tk.signature_report()
            self._session_tl.fusion_report = report
        result.fusion_report = report
        return result

    def _lifecycle(self):
        """(cancel callable | None, monotonic deadline | None) of the
        statement this thread is executing."""
        return getattr(self._session_tl, "lifecycle", None) \
            or (None, None)

    def _execute_lifecycled(self, sql: str) -> MaterializedResult:
        from presto_tpu.telemetry import ledger as _ledger
        with _ledger.span("planning"):
            pc = self._plan_cache()
            skey = self._session_cache_key() if pc is not None \
                else None
            ntext = None
            hit = False
            if pc is not None and skey is not None:
                from presto_tpu.cache import normalize_sql
                ntext = normalize_sql(sql)
                hit = pc.contains(("sql", ntext, skey))
            stmt = None if hit else parse_statement(sql)
        if hit:
            # a repeat statement: skip the parser entirely — the
            # key can only have been inserted by a T.Query path.
            # The normalized text rides along so _plan_query's
            # get() doesn't re-walk the statement (the session
            # key is NOT forwarded: _plan_query must re-derive it
            # per execution for the width-retry re-key)
            return self._run_query_statement(None, sql,
                                             cache_text=ntext)
        # forward the normalized text on the miss path too: without
        # it a cold SELECT lexes three times (key, parse, put-key)
        return self._execute_stmt(stmt, sql, cache_text=ntext)

    # -- plan cache (presto_tpu/cache level 1) -------------------------

    def _plan_cache(self):
        from presto_tpu.session_properties import get_property
        if not bool(get_property(self.session.properties,
                                 "plan_cache_enabled")):
            return None
        from presto_tpu.cache import get_cache_manager
        return get_cache_manager(self.session.properties).plan

    def _session_cache_key(self):
        """Everything session-side a plan may depend on: catalog +
        schema defaults (name resolution), user AND the access-control
        instance (checks run at analysis — a cached plan skips them,
        so two runners with different policies must never share
        entries), and the full effective property set (analysis and
        optimization both read properties). None = this session has
        no stable cache identity (unhashable, unstampable policy);
        callers must skip the plan cache."""
        from presto_tpu.session_properties import effective
        s = self.session
        props = tuple(sorted(
            (k, v) for k, v in effective(s.properties).items()
            if isinstance(v, (int, float, str, bool, type(None)))))
        ac = self.catalogs.access_control
        rules_fp = None
        if ac is not None:
            # fold the policy CONTENT in, not just its identity: a
            # cached plan skips the analysis-time checks, and rule
            # lists are mutated in place (append a revoke) — the key
            # must change when the rules do, or a revoked user keeps
            # reading from cached plans. AccessRule is a dataclass,
            # so repr renders values; policies without a `rules`
            # list key on identity alone and must be replaced
            # wholesale to change
            rules = getattr(ac, "rules", None)
            if isinstance(rules, (list, tuple)):
                rules_fp = tuple(repr(r) for r in rules)
            try:
                hash(ac)  # held in the key: no GC-reuse aliasing
            except TypeError:
                # unhashable policy: mint a token once and stamp it on
                # the object — a per-policy identity that lives exactly
                # as long as the policy does (id() would need the
                # object pinned forever to stay unambiguous)
                tok = getattr(ac, "_plan_cache_token", None)
                if tok is None:
                    with _AC_TOKEN_LOCK:
                        tok = getattr(ac, "_plan_cache_token", None)
                        if tok is None:
                            tok = next(_AC_TOKEN_MINT)
                            try:
                                object.__setattr__(
                                    ac, "_plan_cache_token", tok)
                            except (AttributeError, TypeError):
                                # unstampable (slots) AND unhashable:
                                # no stable identity exists — caller
                                # skips the plan cache entirely
                                return None
                ac = ("ac-token", tok)
        # the history-store GENERATION is part of the plan identity: a
        # cached plan bakes in join order / exchange choices derived
        # from the store's state, and a MATERIAL history change must
        # re-plan — while serving repetitions whose re-measurements
        # merely confirm the store keep hitting the cached plan
        # (store.py bumps the generation only on material change)
        hist_gen = None
        from presto_tpu import history as _history
        if _history.enabled(s.properties):
            store = _history.get_history_store(create=False)
            if store is not None:
                hist_gen = store.generation()
        return (s.catalog, s.schema, getattr(s, "user", ""), ac,
                rules_fp, props, hist_gen)

    def _plan_query(self, stmt: Optional[T.Node], sql: str,
                    cache_text: Optional[str] = None) -> N.OutputNode:
        """Attribution shell: parse/analyze/optimize (and the plan-
        cache lookup) all charge to the ledger's `planning` category —
        nested kernel/expr work subtracts via the span discipline."""
        from presto_tpu.telemetry import ledger as _ledger
        with _ledger.span("planning"):
            return self._plan_query_inner(stmt, sql, cache_text)

    def _plan_query_inner(self, stmt: Optional[T.Node], sql: str,
                          cache_text: Optional[str] = None
                          ) -> N.OutputNode:
        """SELECT text/AST -> OPTIMIZED plan, through the process-wide
        plan cache. Looked up fresh on every (re)execution so the
        width-retry loop — which bumps a session property and thereby
        changes the key — re-plans instead of replaying a stale plan."""
        pc = self._plan_cache()
        key = None
        if pc is not None:
            skey = self._session_cache_key()
            if skey is None:
                pc = None  # no stable session identity -> uncached
        if pc is not None:
            from presto_tpu.cache import normalize_sql
            key = ("sql", cache_text or normalize_sql(sql), skey)
            plan = pc.get(key, self.catalogs)
            if plan is not None:
                return plan
        if stmt is None:
            stmt = parse_statement(sql)
        if not isinstance(stmt, T.Query):
            raise QueryError(
                f"unsupported statement {type(stmt).__name__}")
        try:
            plan = plan_statement(stmt, self.catalogs, self.session)
        except AnalysisError as e:
            raise QueryError(str(e)) from e
        # sanity checks at every pass boundary (reference:
        # PlanSanityChecker between optimizer passes): a pass that
        # corrupts the plan fails HERE, attributed to itself
        from presto_tpu.planner.validation import validate
        validate(plan, "analysis", session=self.session)
        from presto_tpu.planner.optimizer import optimize
        plan = optimize(plan, self.catalogs,
                        session=self.session)
        validate(plan, "optimizer", session=self.session,
                 catalogs=self.catalogs)
        if key is not None:
            # prune BEFORE publishing: every later execution's
            # planner re-prunes the shared graph, and pruning an
            # already-pruned plan writes values equal to what is
            # there — so concurrent consumers only ever race on
            # identical-value writes, never on the wide->narrow
            # first transition
            from presto_tpu.planner.local_planner import (
                prune_unused_columns,
            )
            prune_unused_columns(plan)
            pc.put(key, plan, self.catalogs)
        return plan

    def _invalidate_caches(self, parts: Tuple[str, ...]) -> None:
        """Eager cross-level invalidation at a DDL/DML commit point
        (version bumps already make stale entries unreachable; this
        frees their memory immediately)."""
        from presto_tpu.cache import get_cache_manager
        mgr = get_cache_manager(create=False)
        if mgr is None:
            return
        try:
            mgr.invalidate_table(self._handle_for(parts))
        except Exception:  # noqa: BLE001 — invalid names etc.
            pass

    # -- prepared statements (reference: PREPARE/EXECUTE/DEALLOCATE +
    # DESCRIBE INPUT/OUTPUT, sql/tree/Prepare.java; the reference
    # carries these per-session via client-protocol headers — here the
    # registry lives on the runner's session surface)

    def _prepared_registry(self) -> Dict[str, T.Node]:
        """The CURRENT identity's name -> AST namespace. Scoped per
        user, not per runner: the single-node coordinator drives one
        shared runner for every HTTP client, and a flat registry
        would let user B's PREPARE s1 shadow user A's (A's EXECUTE s1
        silently runs B's statement), or B's DEALLOCATE break A's."""
        reg = getattr(self, "_prepared", None)
        if reg is None:
            reg = self._prepared = {}
        return reg.setdefault(getattr(self.session, "user", ""), {})

    def _execute_stmt(self, stmt: T.Node, sql: str,
                      cache_text: Optional[str] = None
                      ) -> MaterializedResult:
        if isinstance(stmt, T.Prepare):
            self._prepared_registry()[stmt.name] = stmt.statement
            return self._text_result("result", ["PREPARE"])
        if isinstance(stmt, T.Deallocate):
            if self._prepared_registry().pop(stmt.name, None) is None:
                raise QueryError(
                    f"prepared statement {stmt.name!r} not found")
            return self._text_result("result", ["DEALLOCATE"])
        if isinstance(stmt, T.ExecutePrepared):
            prepared = self._prepared_registry().get(stmt.name)
            if prepared is None:
                raise QueryError(
                    f"prepared statement {stmt.name!r} not found")
            need = _count_params(prepared)
            if len(stmt.using) != need:
                raise QueryError(
                    f"EXECUTE {stmt.name}: statement has {need} "
                    f"parameters, USING supplied {len(stmt.using)}")
            bound = _substitute_params(prepared, stmt.using)
            if isinstance(bound, T.Query):
                # content-addressed plan-cache key: prepared name +
                # the bound AST (statement body AND argument values),
                # so re-PREPAREs under the same name can never collide
                import hashlib
                digest = hashlib.blake2b(
                    repr(bound).encode(), digest_size=16).hexdigest()
                return self._run_query_statement(
                    bound, sql,
                    cache_text=f"prep:{stmt.name}:{digest}")
            return self._execute_stmt(bound, sql)
        if isinstance(stmt, T.DescribeInput):
            prepared = self._prepared_registry().get(stmt.name)
            if prepared is None:
                raise QueryError(
                    f"prepared statement {stmt.name!r} not found")
            n = _count_params(prepared)
            from presto_tpu.types import BIGINT, VARCHAR
            rows = [(i, "unknown") for i in range(n)]
            return self._rows_result(
                ["Position", "Type"], rows, (BIGINT, VARCHAR))
        if isinstance(stmt, T.DescribeOutput):
            prepared = self._prepared_registry().get(stmt.name)
            if prepared is None:
                raise QueryError(
                    f"prepared statement {stmt.name!r} not found")
            if not isinstance(prepared, T.Query):
                raise QueryError("DESCRIBE OUTPUT expects a query")
            nulls = [T.NullLit()] * _count_params(prepared)
            bound = _substitute_params(prepared, nulls)
            try:
                plan = plan_statement(bound, self.catalogs,
                                      self.session)
            except AnalysisError as e:
                raise QueryError(str(e)) from e
            from presto_tpu.types import VARCHAR
            rows = [(cn, f.type.display())
                    for cn, f in zip(plan.names, plan.output)]
            return self._rows_result(
                ["Column Name", "Type"], rows, (VARCHAR, VARCHAR))
        if isinstance(stmt, T.Explain):
            return self._explain(stmt, sql)
        if isinstance(stmt, (T.ShowTables, T.ShowSchemas, T.ShowCatalogs,
                             T.ShowColumns, T.ShowSession,
                             T.ShowFunctions)):
            return self._show(stmt)
        if isinstance(stmt, T.SetSession):
            return self._set_session(stmt)
        if isinstance(stmt, T.ResetSession):
            # back to the registry default (reference: RESET SESSION);
            # unknown names reject like SET would — a typo must not
            # silently leave the real override in place
            self._reject_request_scoped_mutation()
            from presto_tpu.session_properties import validate_set
            try:
                # NULL is RESET's value: the same gate as SET (unknown
                # names, a deployment's fixed layout)
                validate_set(stmt.name, None)
            except ValueError as err:
                raise QueryError(str(err)) from None
            self.session.properties.pop(stmt.name, None)
            return self._text_result("result", ["RESET SESSION"])
        if isinstance(stmt, T.CreateTableAs):
            try:
                return self._with_width_retry(
                    lambda: self._create_table_as(stmt))
            finally:
                self._invalidate_caches(stmt.name)
        if isinstance(stmt, T.InsertInto):
            try:
                return self._with_width_retry(
                    lambda: self._insert_into(stmt))
            finally:
                self._invalidate_caches(stmt.name)
        if isinstance(stmt, T.DropTable):
            try:
                return self._drop_table(stmt)
            finally:
                self._invalidate_caches(stmt.name)
        if not isinstance(stmt, T.Query):
            raise QueryError(
                f"unsupported statement {type(stmt).__name__}")
        return self._run_query_statement(stmt, sql, cache_text)

    def _run_query_statement(self, stmt: Optional[T.Node], sql: str,
                             cache_text: Optional[str] = None
                             ) -> MaterializedResult:
        """Run a SELECT (parsed or cache-resolvable) with history
        bookkeeping. `stmt` None = the caller verified a plan-cache
        entry exists for this text (parse is skipped; a lost race
        re-parses inside _plan_query)."""
        import time as _time
        # itertools.count.__next__ is atomic under the GIL — the
        # single-node coordinator drives one shared runner from many
        # client threads, and a read-modify-write here would mint
        # duplicate query ids
        entry = self._new_history_entry(sql)
        t0 = _time.perf_counter()
        try:
            def plan_and_run():
                # array_agg width overflow must RE-PLAN (the width is
                # baked into the plan's value forms) — _plan_query
                # re-keys on the bumped session property, so the retry
                # misses the cache and rebuilds the plan
                return self._run_plan(
                    self._plan_query(stmt, sql, cache_text))
            result = self._with_width_retry(plan_and_run)
            entry["state"] = "FINISHED"
            # row count resolves lazily when system.runtime.queries is
            # read — counting here would put device syncs on the timed
            # hot path of every query
            import weakref
            entry["rows"] = None
            entry["_result"] = weakref.ref(result)
            return result
        except Exception as e:
            entry["state"] = "FAILED"
            # structured failure taxonomy (cancelled / deadline_
            # exceeded / ...) so system.runtime.queries shows WHY,
            # not just that it failed
            entry["error_kind"] = getattr(e, "kind", None) \
                or type(e).__name__
            raise
        finally:
            self._finish_history_entry(entry, t0)

    def _new_history_entry(self, sql: str) -> Dict[str, Any]:
        entry = {"id": next(self._query_id_mint), "sql": sql.strip(),
                 "state": "RUNNING", "rows": 0, "elapsed_ms": 0.0,
                 "error_kind": None,
                 # admission queue wait (embedded resource groups):
                 # per-query queued_ms attribution rides the history
                 # entry into system.runtime.queries
                 "queued_ms": round(float(getattr(
                     self._session_tl, "queued_ms", 0.0) or 0.0), 3),
                 "compile_ms": 0.0, "execute_ms": 0.0,
                 # filled when the statement's attribution ledger
                 # closes (_execute_admitted finally) — the coverage
                 # residual surfaced on system.runtime.queries
                 "unattributed_ms": None}
        self.query_history.append(entry)
        del self.query_history[:-1000]  # bounded history
        # the ledger close runs OUTSIDE _run_query_statement's
        # bookkeeping; hand it the entry through the statement-scoped
        # thread-local
        self._session_tl.history_entry = entry
        return entry

    def _finish_history_entry(self, entry: Dict[str, Any],
                              t0: float) -> None:
        """The ONE finally-side bookkeeping of a statement's history
        entry (shared by SELECT and EXPLAIN ANALYZE paths): elapsed,
        the per-statement kernel counters installed by execute(), the
        drained operator snapshot, and the process query counter —
        feeding system.runtime.queries / .operator_stats and
        /v1/metrics."""
        import time as _time

        from presto_tpu.telemetry import kernels as _tk
        from presto_tpu.telemetry.metrics import METRICS
        entry["elapsed_ms"] = round(
            (_time.perf_counter() - t0) * 1000, 3)
        counters = _tk.query_counters()
        if counters is not None:
            entry["compile_ms"] = round(
                counters["compile_ns"] / 1e6, 3)
            entry["execute_ms"] = round(
                counters["execute_ns"] / 1e6, 3)
        ops = getattr(self._session_tl, "op_stats", None)
        if ops is not None:
            self._record_operator_stats(entry["id"], ops)
        # (presto_tpu_queries_total is counted once per STATEMENT in
        # execute() — counting here too would double-count SELECTs and
        # miss SHOW/SET/DDL/parse failures entirely)

    def create_plan(self, sql: str,
                    stmt: Optional[T.Node] = None) -> N.OutputNode:
        """`stmt` lets a caller that already parsed (and possibly
        unwrapped — derive_fragments strips EXPLAIN) skip re-parsing."""
        if stmt is None:
            stmt = parse_statement(sql)
        if not isinstance(stmt, T.Query):
            raise QueryError("create_plan expects a query")
        return plan_statement(stmt, self.catalogs, self.session)

    def _run_plan(self, plan: N.OutputNode,
                  profile: bool = False,
                  on_retry=None) -> MaterializedResult:
        """`on_retry` fires before every overflow re-execution — write
        plans use it to drop the sink's uncommitted appends so the
        retry cannot duplicate rows."""
        from presto_tpu.execution.memory import MemoryPool
        from presto_tpu.operators.aggregation import GroupLimitExceeded
        from presto_tpu.operators.fused_fragment import (
            FusedChainCompactOverflow,
        )
        from presto_tpu.operators.join_ops import JoinCapacityExceeded
        import time as _time
        from presto_tpu.telemetry import ledger as _ledger
        session = self.session
        while True:
            with _ledger.span("planning"):
                planner = LocalExecutionPlanner(self.catalogs, session)
                lplan = planner.plan(plan)
            self._session_tl.fusion_report = planner.fusion_report
            # history-based optimization: arm row counters for the
            # operators whose measured cardinality the store wants
            # (cheap async device adds; None = profile-only counting).
            # Fault-armed sessions never record — an injected fault
            # can truncate an operator's rows mid-stream.
            from presto_tpu import history as _history
            hist_ops = None
            from presto_tpu.execution import faults as _faults
            if _history.enabled(session.properties) \
                    and not _faults.ARMED:
                with _ledger.span("planning"):
                    hist_ops = _history.interesting_ops(
                        plan, planner.node_ops_prefusion,
                        id_remap=(planner.fusion_report or {}).get(
                            "id_remap"),
                        catalogs=self.catalogs)
            t0 = _time.perf_counter()
            from presto_tpu.session_properties import get_property
            budget = get_property(session.properties,
                                  "hbm_budget_bytes")
            pool = MemoryPool(int(budget) if budget else None)
            cm = self._cluster_memory(session)
            cm_qid = None
            if cm is not None:
                cm_qid = f"cmq{next(self._cm_qid_mint)}"
                pool.attach_cluster(cm, cm_qid)
            from presto_tpu.execution.cluster_memory import (
                QueryKilledByMemoryManager,
            )
            from presto_tpu.execution.memory import MemoryLimitExceeded
            cancel, deadline = self._lifecycle()
            # the time-sliced executor (default on): every statement
            # of this process time-shares one worker pool instead of
            # monopolizing its submitting thread round after round
            from presto_tpu.execution.task_executor import (
                executor_for_session,
            )
            executor = executor_for_session(session.properties)
            quantum_ms = get_property(session.properties,
                                      "task_executor_quantum_ms")
            try:
                try:
                    drivers = self.drive_pipelines(lplan.pipelines,
                                                   profile=profile,
                                                   pool=pool,
                                                   cancel=cancel,
                                                   deadline=deadline,
                                                   executor=executor,
                                                   quantum_ms=quantum_ms,
                                                   count_rows_ops=hist_ops)
                finally:
                    if cm is not None:
                        cm.finish_query(cm_qid)
            except QueryKilledByMemoryManager as e:
                raise QueryError(str(e)) from e
            except MemoryLimitExceeded as e:
                raise QueryError(
                    f"{e} — raise hbm_budget_bytes or run on a "
                    "MeshRunner, which retries bucket-wise") from e
            except GroupLimitExceeded as e:
                # group-by table overflowed: re-run the whole query with a
                # larger table (query-level retry keeps the per-batch hot
                # loop free of device->host syncs)
                if e.suggested > 1 << 26:
                    raise QueryError(
                        "group-by exceeds max supported groups") from e
                session = dataclasses.replace(
                    session, properties={**session.properties,
                                         "max_groups": e.suggested})
                if on_retry is not None:
                    on_retry()
                continue
            except JoinCapacityExceeded as e:
                # a join emitted more rows than probe capacity x factor
                # (many-to-many expansion): re-run with the larger factor
                if e.suggested > 1 << 10:
                    raise QueryError(
                        "join expansion exceeds supported factor") from e
                session = dataclasses.replace(
                    session, properties={
                        **session.properties,
                        "join_expansion_factor": e.suggested})
                if on_retry is not None:
                    on_retry()
                continue
            except FusedChainCompactOverflow:
                # the history-sized in-trace compaction saw more
                # surviving rows than its measured bucket (the data
                # shifted since the measurement): re-run once with the
                # fusion upgrade off — the gated PARTIAL path is
                # always correct, and the re-measurement this clean
                # retry records re-sizes the bucket for next time
                session = dataclasses.replace(
                    session, properties={
                        **session.properties,
                        "history_driven_fusion": False})
                if on_retry is not None:
                    on_retry()
                continue
            # async-dispatch undercount close (docs/OBSERVABILITY.md):
            # all kernels are dispatched by now — block on the result
            # batches HERE, inside the measured wall, so dispatch-
            # then-wait slack lands in the ledger's device_wait
            # category instead of escaping into the caller's rows()
            with _ledger.span("device_wait"):
                import jax as _jax
                _jax.block_until_ready(lplan.result_sink)
            # snapshot per-operator stats ALWAYS (plain dicts — the
            # driver refs drop here, so no device batches get pinned):
            # lightweight counters (batches, busy, compile/execute,
            # cache) on plain runs, plus rows/bytes under profile
            from presto_tpu.telemetry import (
                count_streamed_rows, render_operator_stats,
                snapshot_drivers,
            )
            with _ledger.span("driver.reassembly"):
                snap = snapshot_drivers(drivers, pool)
                self._session_tl.op_stats = snap
                count_streamed_rows(drivers)
                # the history recording tap: ONLY here — past every
                # deferred overflow check, after drivers closed
                # cleanly. Failed/cancelled/shed runs raised out
                # above; fault-armed runs never armed hist_ops
                if hist_ops is not None and not _faults.ARMED:
                    self._record_history(plan, planner, snap)
            if profile:
                self._last_profile = render_operator_stats(
                    snap, _time.perf_counter() - t0, pool)
                # node -> operator-id join for the annotated EXPLAIN
                # ANALYZE tree (plan node identity survives into
                # _explain — the planner mutates the same objects)
                self._last_annotate = (
                    planner.node_ops,
                    {s["operator_id"]: s for ops in snap for s in ops})
            return MaterializedResult(lplan.result_names, lplan.result_sink,
                                      lplan.result_fields)

    def _record_history(self, plan: N.OutputNode, planner,
                        snap: List[List]) -> None:
        """Commit this clean execution's measured per-node rows to the
        history store (presto_tpu/history). Advisory: a recording
        failure must never fail a query that already produced its
        answer."""
        try:
            from presto_tpu import history as _history
            report = planner.fusion_report or {}
            obs = _history.collect_observations(
                plan, self.catalogs, planner.node_ops_prefusion,
                snap, id_remap=report.get("id_remap"))
            if obs:
                _history.get_history_store().commit(obs)
        except Exception:  # noqa: BLE001 — advisory by contract
            pass

    @staticmethod
    def drive_pipelines(pipelines: List[List],
                        max_idle_s: float = 600.0,
                        profile: bool = False,
                        pool=None, cancel=None,
                        deadline: Optional[float] = None,
                        executor=None,
                        quantum_ms: Optional[float] = None,
                        abort_check=None,
                        count_rows_ops=None) -> List[Driver]:
        """Drive all pipelines' drivers to completion — on the shared
        time-sliced TaskExecutor when `executor` is given (the
        default production path: _run_plan and worker tasks resolve
        it from the `task_executor_enabled` session property), else
        on the legacy serial round-robin loop below.

        Progress is judged by wall clock, not round count: a task whose
        input arrives over the network exchange (a producer on another
        node may still be compiling) legitimately spins for a while, so
        no-progress rounds sleep briefly and only a `max_idle_s` stretch
        with zero progress is treated as a deadlock.

        `cancel` is an optional () -> bool polled each round/quantum —
        the cooperative kill point shared by task abort, client kill,
        and query abandonment. `deadline` is an optional
        time.monotonic() instant checked at the same cadence
        (per-query query_max_run_time_ms): a runaway query terminates
        within one round/quantum of either tripping, releasing its
        drivers (and their device buffers) through the error path.
        `abort_check` is an optional () -> exception|None polled at
        the same checkpoints (the distributed root drive's remote-
        task-failed signal)."""
        import time as _time
        from presto_tpu.telemetry import ledger as _ledger
        dctx = DriverContext(profile=profile, memory=pool,
                             count_rows_ops=count_rows_ops)
        drivers = [Driver([f.create(dctx) for f in pipe])
                   for pipe in pipelines]
        if executor is not None:
            # the QUANTA attribute their own wall (executor workers
            # install this statement's ledger per quantum); the
            # submitting thread must NOT span its wait here or the
            # same wall would count twice — the executor charges the
            # scheduling gap (wait minus scheduled time) to `driver`
            executor.run_drivers(drivers, cancel=cancel,
                                 deadline=deadline,
                                 quantum_ms=quantum_ms,
                                 abort_check=abort_check,
                                 max_idle_s=max_idle_s)
        else:
            with _ledger.span("driver.step"):
                idle_since: Optional[float] = None
                while True:
                    check_lifecycle(cancel, deadline)
                    if abort_check is not None:
                        exc = abort_check()
                        if exc is not None:
                            raise exc
                    all_done = True
                    progress = False
                    for d in drivers:
                        if d.is_finished():
                            continue
                        all_done = False
                        progress = d.process() or progress
                    if all_done:
                        break
                    if progress:
                        idle_since = None
                        continue
                    now = _time.monotonic()
                    if idle_since is None:
                        idle_since = now
                    elif now - idle_since > max_idle_s:
                        raise QueryError(
                            f"query made no progress for "
                            f"{max_idle_s:.0f}s (deadlock?)")
                    _time.sleep(0.002)
        # sync-free error protocol: ONE host fetch for every deferred
        # device flag (join capacity overflow etc.), after all drivers
        # finished but before results are trusted. The fetch blocks on
        # outstanding device work — that wall is device_wait, not
        # driver overhead (the async-dispatch undercount)
        from presto_tpu.operators.base import run_deferred_checks
        with _ledger.span("device_wait"):
            run_deferred_checks(dctx)
        for d in drivers:
            d.close()
        return drivers

    # -- DDL / DML ------------------------------------------------------

    def _handle_for(self, parts: Tuple[str, ...]) -> TableHandle:
        return CatalogManager.handle_for(parts, self.session)

    def _sink_for(self, handle: TableHandle):
        self.catalogs.check_access(
            "write", getattr(self.session, "user", ""), handle)
        conn = self.catalogs.connector(handle.catalog)
        sink = conn.page_sink
        if sink is None:
            raise QueryError(
                f"catalog {handle.catalog!r} does not support writes")
        return sink

    def _plan_for_write(self, q: T.Query) -> N.OutputNode:
        from presto_tpu.telemetry import ledger as _ledger
        with _ledger.span("planning"):
            try:
                plan = plan_statement(q, self.catalogs, self.session)
            except AnalysisError as e:
                raise QueryError(str(e)) from e
            from presto_tpu.planner.validation import validate
            validate(plan, "analysis", session=self.session)
            from presto_tpu.planner.optimizer import optimize
            plan = optimize(plan, self.catalogs,
                            session=self.session)
            validate(plan, "optimizer", session=self.session,
                     catalogs=self.catalogs)
            return plan

    def _run_write(self, qplan: N.OutputNode, handle, sink,
                   schema, column_sources: Dict[str, Optional[str]]
                   ) -> int:
        """Wrap a SELECT plan with TableWriter -> TableFinish and run
        it through the normal (possibly distributed) executor: one
        writer per task appends in parallel (reference:
        TableWriterOperator/TableFinishOperator + the scaled-writer
        exchange AddExchanges inserts). The COMMIT happens HERE, only
        after _run_plan returned — which is after the drive loop's
        deferred overflow checks (a deferred JoinCapacityExceeded
        surfaces once all drivers finish; committing any earlier would
        let the retry duplicate committed rows). Overflow retries drop
        uncommitted appends first (ConnectorPageSink.abort)."""
        from presto_tpu.types import BIGINT
        schema_cols = [p for c in schema.columns for p in c.physical()]
        wsym, fsym = "__write_rows__", "__commit_rows__"
        writer = N.TableWriterNode(
            qplan.source, handle, dict(column_sources), schema_cols,
            (N.Field(wsym, BIGINT),))
        finish = N.TableFinishNode(
            writer, handle,
            (N.Field(fsym, writer.output[0].type),))
        out = N.OutputNode(finish, ["rows"], [fsym], finish.output)
        try:
            result = self._run_plan(
                out, on_retry=lambda: sink.abort(handle))
        except Exception:
            # a width-overflow retry restarts the whole write
            # statement — uncommitted appends must not survive into
            # the rerun
            sink.abort(handle)
            raise
        n = int(result.rows()[0][0])
        sink.finish(handle)  # THE commit point
        return n

    def _create_table_as(self, stmt: T.CreateTableAs
                         ) -> MaterializedResult:
        from presto_tpu.schema import ColumnSchema, RelationSchema
        handle = self._handle_for(stmt.name)
        sink = self._sink_for(handle)
        conn = self.catalogs.connector(handle.catalog)
        try:
            conn.metadata.get_table_schema(handle)
            exists = True
        except KeyError:
            exists = False
        if exists:
            if stmt.if_not_exists:
                return self._text_result("result",
                                         ["CREATE TABLE skipped"])
            raise QueryError(f"table {handle} already exists")
        qplan = self._plan_for_write(stmt.query)
        if len(set(qplan.names)) != len(qplan.names):
            raise QueryError(
                "CREATE TABLE AS query has duplicate column names; "
                "alias them")
        fields = [next(f for f in qplan.output if f.symbol == s)
                  for s in qplan.source_symbols]
        cols = []
        column_sources: Dict[str, Optional[str]] = {}
        for n, f in zip(qplan.names, fields):
            form = getattr(f, "form", None)
            if form is None:
                cols.append(ColumnSchema(n, f.type, f.dictionary))
                column_sources[n] = f.symbol
                continue
            # complex column: store its SLOT columns under
            # <name>__a{j}/<name>__len and record the stored-name form
            stored, src_map = _rename_form_slots(form, f.symbol, n)
            cols.append(ColumnSchema(n, f.type, f.dictionary,
                                     form=stored))
            column_sources.update(src_map)
        schema = RelationSchema(cols)
        from presto_tpu.operators.array_agg import ArrayAggWidthExceeded
        sink.create_table(handle, schema, dict(stmt.properties or {}))
        try:
            n = self._run_write(qplan, handle, sink, schema,
                                column_sources)
        except ArrayAggWidthExceeded:
            # the width retry re-runs the whole CTAS (the schema's
            # stored forms are width-dependent): un-create first
            try:
                sink.drop_table(handle)
            except Exception:
                pass
            raise
        return self._text_result(
            "result", [f"CREATE TABLE: {n} rows"])

    def _insert_into(self, stmt: T.InsertInto) -> MaterializedResult:
        handle = self._handle_for(stmt.name)
        sink = self._sink_for(handle)
        conn = self.catalogs.connector(handle.catalog)
        try:
            schema = conn.metadata.get_table_schema(handle)
        except KeyError:
            raise QueryError(f"table {handle} does not exist") from None
        target_cols = stmt.columns or [c.name for c in schema.columns]
        known = {c.name for c in schema.columns}
        unknown = [c for c in target_cols if c not in known]
        if unknown:
            raise QueryError(
                f"INSERT target column(s) {unknown} do not exist "
                f"in {handle}")
        if len(set(target_cols)) != len(target_cols):
            raise QueryError("INSERT target columns must be distinct")
        qplan = self._plan_for_write(stmt.query)
        fields = [next(f for f in qplan.output if f.symbol == s)
                  for s in qplan.source_symbols]
        if len(fields) != len(target_cols):
            raise QueryError(
                f"INSERT has {len(fields)} columns but "
                f"{len(target_cols)} targets")
        # INSERT matches by POSITION (duplicate query names are fine):
        # target column name -> source field
        by_target = dict(zip(target_cols, fields))
        column_sources: Dict[str, Optional[str]] = {}
        for cs in schema.columns:
            ft = by_target.get(cs.name)
            if ft is None:
                for pname, _t, _d in cs.physical():
                    column_sources[pname] = None
                continue
            if ft.type.name != cs.type.name:
                raise QueryError(
                    f"INSERT type mismatch on {cs.name}: "
                    f"{ft.type.display()} vs {cs.type.display()}")
            if cs.form is not None:
                # complex target: map each STORED slot to the source
                # field's corresponding slot (widths must agree — the
                # stored layout is fixed)
                sform = getattr(ft, "form", None)
                if sform is None:
                    raise QueryError(
                        f"INSERT into complex column {cs.name} "
                        "requires a matching array/map value")
                stored = [p[0] for p in cs.physical()]
                src_slots = N.form_slot_symbols(sform)
                if len(stored) != len(src_slots):
                    raise QueryError(
                        f"INSERT into {cs.name}: stored element "
                        f"capacity {len(stored)} != query value's "
                        f"{len(src_slots)} (set array_agg_width to "
                        "the table's width)")
                column_sources.update(zip(stored, src_slots))
                continue
            column_sources[cs.name] = ft.symbol
        n = self._run_write(qplan, handle, sink, schema,
                            column_sources)
        return self._text_result("result", [f"INSERT: {n} rows"])

    def _drop_table(self, stmt: T.DropTable) -> MaterializedResult:
        handle = self._handle_for(stmt.name)
        sink = self._sink_for(handle)
        conn = self.catalogs.connector(handle.catalog)
        try:
            conn.metadata.get_table_schema(handle)
        except KeyError:
            if stmt.if_exists:
                return self._text_result("result", ["DROP skipped"])
            raise QueryError(f"table {handle} does not exist") from None
        sink.drop_table(handle)
        return self._text_result("result", ["DROP TABLE"])

    # -- metadata statements -------------------------------------------

    def _explain(self, stmt: T.Explain,
                 sql: str = "explain") -> MaterializedResult:
        inner = stmt.statement
        if not isinstance(inner, T.Query):
            raise QueryError("EXPLAIN supports queries only")
        plan = plan_statement(inner, self.catalogs, self.session)
        from presto_tpu.planner.local_planner import prune_unused_columns
        from presto_tpu.planner.optimizer import optimize
        plan = optimize(plan, self.catalogs,
                        session=self.session)
        prune_unused_columns(plan)
        est_annotate = self._estimate_annotator()
        # materialize the estimate lines NOW, before any execution:
        # the ANALYZE run itself commits fresh measurements into the
        # history store, and lazily-rendered lines would then show
        # post-run values contradicting the decisions the executed
        # plan was actually built from
        from presto_tpu.history.recorder import walk_nodes
        est_lines = {id(n): est_annotate(n) for n in walk_nodes(plan)}

        def est_cached(node) -> List[str]:
            return list(est_lines.get(id(node), ()))
        if stmt.analyze:
            import time as _time
            self._last_annotate = None
            # a real history entry, appended UP FRONT like
            # _run_query_statement's — a failing EXPLAIN ANALYZE must
            # leave a FAILED row (deadline/OOM/stall are exactly what
            # you profile for), and operator_stats rows must JOIN
            # system.runtime.queries
            entry = self._new_history_entry(sql)
            t0 = _time.perf_counter()
            # critical-path extraction needs trace spans: the analyze
            # run gets its OWN recorder (even when the session is not
            # traced — EXPLAIN ANALYZE is already the heavyweight
            # profiling path), with a root "query" span covering
            # exactly the profiled execution
            from presto_tpu.telemetry import trace as _trace_mod
            _cp_rec = _trace_mod.TraceRecorder()
            _cp_prev = _trace_mod.activate(_cp_rec)
            _cp_t0 = _time.perf_counter_ns()
            try:
                try:
                    result = self._run_plan(plan, profile=True)
                finally:
                    _cp_rec.add("query", "query", _cp_t0,
                                _time.perf_counter_ns() - _cp_t0)
                    _trace_mod.deactivate(_cp_prev)
                # annotated tree: each plan node carries its estimate
                # (+ provenance — measured history vs derived static)
                # and the rows/wall/compile/cache of the operators it
                # planned into, THEN the per-pipeline operator table
                # (the two views join on id=N)
                stats_annotate = self._annotator()

                def combined(node):
                    # measured stat lines FIRST (their `name [id=N]`
                    # adjacency to the node line is load-bearing for
                    # downstream tooling), then the estimate line
                    out = [] if stats_annotate is None \
                        else stats_annotate(node)
                    out.extend(est_cached(node))
                    return out
                text = N.plan_text(plan, annotate=combined) \
                    + "\n\n" + self._last_profile + \
                    f"\n-- rows: {result.row_count}"
                # the attribution ledger's view of the statement so
                # far (the final close happens at statement end; this
                # renders the same categories against elapsed wall)
                from presto_tpu.telemetry import ledger as _ledger
                from presto_tpu.telemetry.stats import render_ledger
                led = _ledger.current()
                led_t0 = getattr(self._session_tl,
                                 "statement_t0_ns", None)
                if led is not None and led_t0 is not None:
                    text += "\n\n" + render_ledger(led.finish(
                        _time.perf_counter_ns() - led_t0))
                # the blocking chain that DETERMINED the profiled
                # run's wall (telemetry/critical_path.py) — the
                # ledger above sums thread-time across categories;
                # this names what actually gated completion
                from presto_tpu.telemetry import (
                    critical_path as _cp,
                )
                cp_doc = _cp.extract(_cp_rec.events())
                if cp_doc is not None:
                    text += "\n\n" + _cp.render(cp_doc)
                entry["state"] = "FINISHED"
                entry["rows"] = result.row_count
            except Exception as e:
                entry["state"] = "FAILED"
                entry["error_kind"] = getattr(e, "kind", None) \
                    or type(e).__name__
                raise
            finally:
                self._finish_history_entry(entry, t0)
        else:
            text = N.plan_text(plan, annotate=est_cached)
        return self._text_result("Query Plan", text.split("\n"))

    def _estimate_annotator(self):
        """plan node -> `est: rows=N [history|static]` lines: the
        stats estimator's view of the plan with provenance, so a
        history-driven rewrite is visible in EXPLAIN without reading
        the store (docs/ADAPTIVE.md). Filters additionally show the
        estimated surviving fraction the fusion gate consumes."""
        from presto_tpu import history as _history
        from presto_tpu.planner.stats import (
            StatsEstimator, UNKNOWN_ROWS,
        )
        est = StatsEstimator(
            self.catalogs,
            history=_history.view_for(self.catalogs,
                                      self.session.properties))

        def annotate(node) -> List[str]:
            try:
                st = est.estimate(node)
            except Exception:  # noqa: BLE001 — stats are advisory
                return []
            if st.rows >= UNKNOWN_ROWS * 0.99:
                return ["est: rows=? [static]"]
            prov = est.provenance_of(node)
            sel = ""
            if isinstance(node, N.FilterNode):
                frac = None
                if est.history is not None:
                    frac = est.history.selectivity(node)
                if frac is None:
                    try:
                        inner = est.estimate(node.source).rows
                        frac = min(1.0, st.rows / inner) \
                            if inner > 0 else None
                    except Exception:  # noqa: BLE001
                        frac = None
                if frac is not None:
                    sel = f" sel={frac:.4f}"
            return [f"est: rows={int(round(st.rows)):,}{sel} "
                    f"[{prov}]"]
        return annotate

    def _annotator(self):
        """plan node -> stat lines, from the last profiled run's
        (node -> operator ids) join (None when unavailable — mesh
        plans are re-exchanged copies, their node identity is gone)."""
        bundle = getattr(self, "_last_annotate", None)
        if bundle is None:
            return None
        node_ops, by_id = bundle
        from presto_tpu.telemetry.stats import operator_line

        def annotate(node) -> List[str]:
            out = []
            for op_id in node_ops.get(id(node), ()):
                s = by_id.get(op_id)
                if s is not None:
                    out.append(operator_line(s).strip())
            return out
        return annotate

    def _record_operator_stats(self, query_id: int,
                               pipelines: List[List]) -> None:
        self.operator_stats_history.append(
            {"query_id": query_id, "pipelines": pipelines})
        del self.operator_stats_history[:-32]  # bounded ring

    @staticmethod
    def snapshot_driver_stats(drivers: List[Driver]) -> List[List]:
        """Materialize per-operator stats into plain dicts WITHOUT
        retaining operators (which would pin their device buffers).
        Kept as the runner-facing alias of telemetry.snapshot_drivers
        (mesh retire + worker tasks call through here)."""
        from presto_tpu.telemetry import snapshot_drivers
        return snapshot_drivers(drivers)

    @staticmethod
    def _render_operator_stats(driver_stats: List[List], wall: float,
                               pool=None) -> str:
        """Per-operator execution stats (reference: planPrinter's
        EXPLAIN ANALYZE fragment rendering over OperatorStats)."""
        from presto_tpu.telemetry import render_operator_stats
        return render_operator_stats(driver_stats, wall, pool)

    def _show(self, stmt) -> MaterializedResult:
        if isinstance(stmt, T.ShowCatalogs):
            return self._text_result("Catalog", self.catalogs.catalogs())
        if isinstance(stmt, T.ShowSchemas):
            conn = self.catalogs.connector(
                stmt.catalog or self.session.catalog)
            return self._text_result("Schema",
                                     conn.metadata.list_schemas())
        if isinstance(stmt, T.ShowTables):
            # FROM may name `schema` or `catalog.schema`
            if stmt.schema and len(stmt.schema) > 2:
                raise QueryError(
                    f"invalid schema name "
                    f"{'.'.join(stmt.schema)}")
            catalog = stmt.schema[0] if stmt.schema \
                and len(stmt.schema) == 2 else self.session.catalog
            schema = stmt.schema[-1] if stmt.schema \
                else self.session.schema
            conn = self.catalogs.connector(catalog)
            return self._text_result("Table",
                                     conn.metadata.list_tables(schema))
        if isinstance(stmt, T.ShowColumns):
            handle, schema = self.catalogs.resolve_table(
                stmt.table, self.session)
            rows = [(c.name, c.type.display()) for c in schema.columns]
            from presto_tpu.types import VARCHAR
            names = ["Column", "Type"]
            b = Batch.from_pydict({
                "column": ([r[0] for r in rows], VARCHAR),
                "type": ([r[1] for r in rows], VARCHAR)})
            return MaterializedResult(
                names, [b],
                tuple(N.Field(n, VARCHAR) for n in names))
        if isinstance(stmt, T.ShowFunctions):
            from presto_tpu.functions import registered_functions
            from presto_tpu.types import VARCHAR
            fns = registered_functions()
            b = Batch.from_pydict({
                "function": ([n for n, _ in fns], VARCHAR),
                "kind": ([k for _, k in fns], VARCHAR)})
            names = ["Function", "Kind"]
            return MaterializedResult(
                names, [b],
                tuple(N.Field(n, VARCHAR) for n in names))
        if isinstance(stmt, T.ShowSession):
            from presto_tpu.session_properties import (
                SESSION_PROPERTIES, effective,
            )
            rows = []
            for k, v in sorted(effective(
                    self.session.properties).items()):
                p = SESSION_PROPERTIES.get(k)
                desc = f"  -- {p.description}" if p else ""
                rows.append(f"{k}={v}{desc}")
            return self._text_result("Property", rows)
        raise QueryError("unsupported SHOW")

    def _set_session(self, stmt: T.SetSession) -> MaterializedResult:
        self._reject_request_scoped_mutation()
        from presto_tpu.planner.analyzer import _Analyzer, Scope
        from presto_tpu.planner.analyzer import PlannerContext
        ctx = PlannerContext(self.catalogs, self.session)
        an = _Analyzer(Scope([]), ctx)
        from presto_tpu.expr.ir import Literal
        e = an.analyze(stmt.value)
        if not isinstance(e, Literal):
            raise QueryError("SET SESSION value must be a constant")
        from presto_tpu.session_properties import validate_set
        try:
            value = validate_set(stmt.name, e.value)
        except ValueError as err:
            raise QueryError(str(err)) from None
        self.session.properties[stmt.name] = value
        return self._text_result("result", ["SET SESSION"])

    def _text_result(self, name: str, lines: List[str]
                     ) -> MaterializedResult:
        from presto_tpu.types import VARCHAR
        b = Batch.from_pydict({name: (list(lines), VARCHAR)})
        return MaterializedResult([name], [b],
                                  (N.Field(name, VARCHAR),))

    def _rows_result(self, names: List[str], rows: List[tuple],
                     types: tuple) -> MaterializedResult:
        cols = {n: ([r[i] for r in rows], t)
                for i, (n, t) in enumerate(zip(names, types))}
        b = Batch.from_pydict(cols)
        return MaterializedResult(
            list(names), [b],
            tuple(N.Field(n, t) for n, t in zip(names, types)))
