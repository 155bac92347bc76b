"""Local execution planner: PlanNode tree -> operator pipelines
(reference: sql/planner/LocalExecutionPlanner.java:549 — the Visitor at
:804 producing PhysicalOperation chains / DriverFactories).

A pipeline is an ordered list of OperatorFactories with one source at
the head; joins/semijoins/unions spawn dependent pipelines that feed
bridges/queues, exactly like the reference's build/probe DriverFactory
split."""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from presto_tpu.batch import Batch, DEFAULT_BATCH_ROWS
from presto_tpu.execution import faults as _faults
from presto_tpu.expr.compile import CompiledExpr, compile_expression
from presto_tpu.expr.ir import InputRef, RowExpression, walk, InputRef
from presto_tpu.operators import misc_ops
from presto_tpu.operators.aggregation import (
    AggSpec, AggregationOperatorFactory, _direct_domains,
)
from presto_tpu.operators.core import (
    FilterProjectOperatorFactory, OutputCollectorOperatorFactory,
    TableScanOperatorFactory, ValuesOperatorFactory,
)
from presto_tpu.operators.join_ops import (
    HashBuildOperatorFactory, JoinBridge, LookupJoinOperatorFactory,
    SemiJoinOperatorFactory,
)
from presto_tpu.operators.sort_ops import (
    DistinctOperatorFactory, OrderByOperatorFactory, TopNOperatorFactory,
)
from presto_tpu.ops import hashagg
from presto_tpu.planner import nodes as N
from presto_tpu.schema import ColumnSchema
from presto_tpu.session_properties import get_property
from presto_tpu.types import DOUBLE, Type
from presto_tpu.expr.ir import SpecialForm

#: scan-iterator exhaustion sentinel (the ledger's scan span wraps
#: each __next__, so the loop can't use the for/else idiom)
_SCAN_DONE = object()


@dataclasses.dataclass
class LocalExecutionPlan:
    pipelines: List[List]              # of OperatorFactory
    result_sink: List[Batch]
    result_names: List[str]
    result_fields: Tuple[N.Field, ...]


@dataclasses.dataclass
class TaskContext:
    """Identity of one fragment task on the mesh (reference: TaskId +
    the split assignment NodeScheduler hands each task). `exchanges`
    maps exchange ids to their MeshExchange runtime objects.
    `df_service`/`cross_df` carry the query-wide cross-fragment
    dynamic-filter service and its plan-derived wiring (see
    exchanges.plan_cross_fragment_filters)."""
    index: int = 0
    count: int = 1
    device: object = None
    exchanges: Dict[int, object] = dataclasses.field(
        default_factory=dict)
    df_service: object = None
    cross_df: object = None
    #: lifespan generation this task instance belongs to (publisher
    #: identity for cross-fragment dynamic-filter dedup on retry)
    generation: int = 0


class LocalPlanningError(Exception):
    pass


def _schema_dicts(schema: Dict[str, ColumnSchema]
                  ) -> Tuple[Tuple[str, tuple], ...]:
    """Hashable (name, dictionary) token of a compile schema's dict-encoded
    columns — part of the filter/project kernel cache key, because compiled
    kernels bake input dictionaries into constants (LIKE lookup tables,
    string comparison ranks)."""
    return tuple(sorted((n, cs.dictionary) for n, cs in schema.items()
                        if cs.dictionary is not None))


def _schema_of(node: N.PlanNode) -> Dict[str, ColumnSchema]:
    out = {}
    for f in node.output:
        form = getattr(f, "form", None)
        if form is None:
            out[f.symbol] = ColumnSchema(f.symbol, f.type,
                                         f.dictionary)
            continue
        # complex-typed field: expose its SLOT columns
        from presto_tpu.expr.ir import InputRef as _IR
        form_dicts = getattr(f, "form_dicts", None) or {}
        for leaf_sym in N.form_slot_symbols(form):
            t = next(
                (x.type for x in _form_leaves(form)
                 if isinstance(x, _IR) and x.name == leaf_sym),
                f.type)
            dic = form_dicts.get(leaf_sym) if t.is_string \
                else None
            if dic is None and t.is_string:
                dic = f.dictionary
            out[leaf_sym] = ColumnSchema(leaf_sym, t, dic)
    return out


_form_leaves = N.form_leaves

#: measured build-side rows past which a dynamic filter is not worth
#: planning: the distinct set (DF_SET_MAX) has long overflowed to
#: bounds-only, and wide surrogate-key bounds prune ~nothing
DF_SKIP_BUILD_ROWS = 1 << 20


def _trace_scan_column(node: N.PlanNode, symbol: str, shared=frozenset()):
    """Follow `symbol` down through filters, semi joins' probe sides
    and identity projections to the TableScanNode that produces it;
    None when anything else (a join, aggregation, or exchange boundary)
    intervenes, or when any node on the path is SHARED (a spooled
    subtree also feeds other consumers — a join-specific filter there
    would corrupt them)."""
    from presto_tpu.expr.ir import InputRef
    while True:
        if id(node) in shared:
            return None
        if isinstance(node, N.TableScanNode):
            return (node, symbol) if symbol in node.assignments else None
        if isinstance(node, N.FilterNode):
            node = node.source
            continue
        if isinstance(node, N.SemiJoinNode):
            # passes its source's rows through unchanged, as a filter
            node = node.source
            continue
        if isinstance(node, N.ProjectNode):
            expr = dict(node.assignments).get(symbol)
            if isinstance(expr, InputRef):
                symbol = expr.name
                node = node.source
                continue
            return None
        return None


class LocalExecutionPlanner:
    def __init__(self, catalog_manager, session,
                 task: Optional[TaskContext] = None):
        self.catalogs = catalog_manager
        self.session = session
        self.task = task or TaskContext()
        self._pipelines: List[List] = []
        self._op_id = 0
        self._shared: set = set()
        self._spools: Dict[int, misc_ops.Spool] = {}
        # dynamic filtering: per-plan registry + scan-node -> [(scan
        # symbol, df_id)] wiring discovered while visiting inner joins
        from presto_tpu.execution.dynamic_filters import (
            DynamicFilterRegistry,
        )
        self._df_registry = DynamicFilterRegistry()
        self._df_scans: Dict[int, List] = {}
        #: planning inside a recorded fragment: nested eligible
        #: subtrees must not wrap again (the outermost wins)
        self._in_fragment = False
        #: telemetry: plan-node id() -> operator ids minted while that
        #: node was being dispatched (EXPLAIN ANALYZE joins operator
        #: stats back onto the plan tree through this)
        self.node_ops: Dict[int, List[int]] = {}
        #: node -> operator ids BEFORE the fusion pass remapped them
        #: (the history recorder's join key; set by _fuse)
        self.node_ops_prefusion: Dict[int, List[int]] = {}
        self._node_stack: List[int] = []
        #: whole-fragment fusion report (planner/fusion.py), populated
        #: by _fuse(); None when the pass is disabled
        self.fusion_report = None
        #: lazy stats estimator for _est_selectivity (fusion gating)
        self._stats = None

    def _next_id(self) -> int:
        self._op_id += 1
        if self._node_stack:
            self.node_ops.setdefault(
                self._node_stack[-1], []).append(self._op_id)
        return self._op_id

    def plan(self, root: N.OutputNode) -> LocalExecutionPlan:
        prune_unused_columns(root)
        # sanity gate at the planner handoff: the pruned plan this
        # visitor consumes must still resolve (prune mutates output
        # tuples in place — a bug there used to surface as a KeyError
        # deep inside an operator, attributed to nothing)
        from presto_tpu.planner.validation import validate
        validate(root, "local_planner", session=self.session)
        self._shared = _shared_nodes(root)
        sink: List[Batch] = []
        pipeline: List = []
        self._visit(root.source, pipeline)
        # final projection to output order; complex-typed fields
        # project their exploded SLOT columns (the named symbol has no
        # physical column — see nodes.Field.form)
        src_schema = _schema_of(root.source)
        projections = []
        for f in root.output:
            for sym in field_symbols(f):
                cs = src_schema[sym]
                projections.append(
                    (sym, compile_expression(InputRef(sym, cs.type),
                                             src_schema)))
        pipeline.append(FilterProjectOperatorFactory(
            self._next_id(), None, projections,
            _schema_dicts(src_schema)))
        pipeline.append(OutputCollectorOperatorFactory(
            self._next_id(), sink))
        self._pipelines.append(pipeline)
        self._fuse()
        return LocalExecutionPlan(self._pipelines, sink, root.names,
                                  root.output)

    def plan_fragment(self, root: N.PlanNode,
                      sink_exchanges: Sequence,
                      staged_output: bool = False) -> List[List]:
        """Plan a non-root fragment for one task: pipelines whose tail
        tees into this fragment's consumer exchange edges (reference:
        LocalExecutionPlanner.plan for a fragment whose root is a
        PartitionedOutput/TaskOutput operator). `staged_output` holds
        outputs until finish (P7 recoverable generations publish
        atomically)."""
        from presto_tpu.operators.exchange_ops import (
            ExchangeSinkOperatorFactory,
        )
        if self.task.index == 0:
            # one validation per fragment, not per task — every task
            # of a fragment plans the SAME root
            from presto_tpu.planner.validation import validate
            validate(root, "local_planner", session=self.session)
        self._shared = _shared_nodes(root)
        pipeline: List = []
        self._visit(root, pipeline)
        pipeline.append(ExchangeSinkOperatorFactory(
            self._next_id(), list(sink_exchanges), self.task.index,
            staged=staged_output))
        self._pipelines.append(pipeline)
        self._fuse()
        if self.fusion_report is not None:
            # second pass: absorb tail chains into their collective
            # exchange so they trace inside the shard_map wave
            # program (docs/SHARDING.md); ineligible exchanges keep
            # the barrier:exchange_sink fallback from the first pass
            from presto_tpu.planner.fusion import fuse_exchange_sinks
            fuse_exchange_sinks(self._pipelines, self.fusion_report,
                                self.node_ops)
        return self._pipelines

    def _fuse(self) -> None:
        """Whole-fragment fusion (planner/fusion.py): collapse
        adjacent FilterProject runs into their consumer's trace. Runs
        LAST — after record/replay, spools, and sinks are placed — so
        every barrier is visible and falling back is simply keeping
        the unfused chain."""
        # the PRE-FUSION node -> operator map is what the history
        # recorder joins measured rows back onto: fusion rewrites
        # node_ops in place for EXPLAIN ANALYZE, which would alias
        # absorbed nodes onto their terminal's operator
        self.node_ops_prefusion = {k: list(v)
                                   for k, v in self.node_ops.items()}
        # a mesh phase plans on worker threads where THIS planner's
        # session object is a fragment-local reconstruction — the
        # runner installs the driving session's gate thread-locally
        # around each statement, and it wins over the property here
        from presto_tpu.planner.fusion import fusion_gate
        gate = fusion_gate()
        enabled = gate if gate is not None else bool(
            get_property(self.session.properties,
                         "fragment_fusion_enabled"))
        if not enabled:
            return
        from presto_tpu.planner.fusion import fuse_pipelines
        # a join build can only spill (handing the probe a host-
        # partitioned table whose partitioner reads key columns
        # host-side) when revocation is BOTH allowed and possible — a
        # finite memory budget exists. Unbudgeted pools never revoke,
        # so probe pre-fusion stays available in the common case.
        spill_possible = bool(
            get_property(self.session.properties, "spill_enabled")) \
            and bool(get_property(self.session.properties,
                                  "hbm_budget_bytes")
                     or get_property(self.session.properties,
                                     "cluster_memory_bytes"))
        from presto_tpu.planner import validation as _validation
        check = _validation.validation_enabled(self.session)
        snapshot = _validation.CHECKER.snapshot_pipelines(
            self._pipelines) if check else None
        # measured (history-provenance) selectivity may upgrade gated
        # chains to full fusion with in-trace compaction — only when
        # both history feedback and the fusion upgrade are enabled
        # (the overflow retry re-plans with the latter off)
        hist_fusion = bool(get_property(
            self.session.properties, "history_driven_fusion")) \
            and bool(get_property(self.session.properties,
                                  "history_based_optimization")) \
            and self.task.count == 1 and not self.task.exchanges \
            and self.task.device is None
        # (single local task only: a mesh/worker task's compact-
        # overflow would surface as a task failure the distributed
        # retry tier cannot fix by re-running the same plan)
        self.fusion_report = fuse_pipelines(
            self._pipelines, self.node_ops,
            spill_enabled=spill_possible,
            history_fusion=hist_fusion)
        if check:
            # barrier legality: fusion may only have absorbed
            # adjacent FilterProject stages; every record/replay/
            # spool/exchange barrier of the snapshot must survive
            _validation.CHECKER.check_fusion(
                snapshot, self._pipelines,
                self.fusion_report.get("id_remap", {}))

    # ------------------------------------------------------------------

    def _visit(self, node: N.PlanNode, pipe: List) -> None:
        # A node with several plan parents (DAG) is computed ONCE into a
        # Spool and replayed to each consumer — the reference dedups via
        # planner CSE; without this the shared subtree would execute once
        # per parent.
        nid = id(node)
        if nid in self._shared:
            spool = self._spools.get(nid)
            if spool is None:
                spool = misc_ops.Spool()
                self._spools[nid] = spool
                sp: List = []
                self._dispatch(node, sp)
                sp.append(misc_ops.spool_sink_factory(self._next_id(),
                                                      spool))
                self._pipelines.append(sp)
            pipe.append(misc_ops.spool_source_factory(self._next_id(),
                                                      spool))
            return
        self._dispatch(node, pipe)

    def _dispatch(self, node: N.PlanNode, pipe: List) -> None:
        m = getattr(self, f"_visit_{type(node).__name__}", None)
        if m is None:
            raise LocalPlanningError(
                f"no local planning for {type(node).__name__}")
        # operator ids minted while this node is on top of the stack
        # belong to IT (children push their own frame) — the node ->
        # operator join EXPLAIN ANALYZE annotates the plan tree with
        self._node_stack.append(id(node))
        try:
            self._dispatch_inner(node, pipe, m)
        finally:
            self._node_stack.pop()

    def _dispatch_inner(self, node: N.PlanNode, pipe: List, m) -> None:
        probe = self._fragment_cache_probe(node)
        if probe is None:
            m(node, pipe)
            return
        cache, key, deps = probe
        hit = cache.get(key)
        if hit is not None:
            from presto_tpu.operators.cache_ops import (
                FragmentReplayOperatorFactory,
            )
            pipe.append(FragmentReplayOperatorFactory(
                self._next_id(), hit))
            return
        from presto_tpu.operators.cache_ops import (
            FragmentRecordOperatorFactory,
        )
        self._in_fragment = True
        try:
            m(node, pipe)
        finally:
            self._in_fragment = False
        pipe.append(FragmentRecordOperatorFactory(
            self._next_id(), cache, key, deps))

    def _fragment_cache_probe(self, node: N.PlanNode):
        """(cache, key, deps) when `node` roots a cacheable leaf
        fragment for THIS task, else None. Local single-task plans
        only: mesh/worker tasks slice splits per task and route
        through exchanges — their partial outputs are not a fragment's
        canonical result."""
        if self._in_fragment or self.task.count != 1 \
                or self.task.device is not None or self.task.exchanges \
                or self.task.df_service is not None:
            return None
        if not bool(get_property(self.session.properties,
                                 "fragment_result_cache_enabled")):
            return None
        from presto_tpu.cache import (
            fragment_fingerprint, get_cache_manager,
        )
        fp = fragment_fingerprint(
            node, self.catalogs, frozenset(self._shared),
            frozenset(self._df_scans))
        if fp is None:
            return None
        key, deps, _scans = fp
        # session properties are part of the key: several change the
        # fragment's OUTPUT beyond its plan shape (streaming vs hash
        # aggregation emit different row orders, max_groups changes
        # packing, array_agg_width changes value forms) — replaying
        # across property changes would not be byte-identical
        from presto_tpu.session_properties import effective
        props = tuple(sorted(
            (k, v) for k, v in effective(
                self.session.properties).items()
            if isinstance(v, (int, float, str, bool, type(None)))))
        mgr = get_cache_manager(self.session.properties)
        triples = [(h.catalog, h.schema, h.table) for h, _ in deps]
        return mgr.fragment, (key, props), triples

    def _visit_TableScanNode(self, node: N.TableScanNode, pipe: List):
        conn = self.catalogs.connector(node.handle.catalog)
        symbols = list(node.assignments.keys())
        columns = [node.assignments[s] for s in symbols]
        rename = dict(zip(columns, symbols))
        batch_rows = int(get_property(self.session.properties,
                                      "batch_rows"))
        target_splits = int(get_property(self.session.properties,
                                         "target_splits"))
        handle = node.handle
        task = self.task
        constraint = node.constraint

        # page-source cache (presto_tpu/cache level 3): raw connector
        # output per (table version, split, columns, constraint),
        # cached BEFORE the per-query rename so every query shape can
        # share the entry. A mesh task's entry holds batches already
        # on the task's chip and its key names the chip, so a warm
        # scan moves no byte (split assignment is deterministic: a
        # split's entry is always read by the same chip). The first
        # device is where a connector's batches land anyway: its
        # tasks, like the one-chip path (task.device None), keep the
        # plain key and share their entries with it
        page_cache = None
        tv = None
        cache_box = {"hits": 0, "misses": 0}
        if bool(get_property(self.session.properties,
                             "page_source_cache_enabled")):
            from presto_tpu.cache import (
                get_cache_manager, table_cache_key,
            )
            tv = table_cache_key(self.catalogs, handle)
            if tv is not None:
                page_cache = get_cache_manager(
                    self.session.properties).page

        def batch_iter():
            import contextlib
            import jax as _jax
            from presto_tpu.cache import split_token
            from presto_tpu.execution.memory import batch_bytes
            from presto_tpu.parallel.mesh import place
            device = task.device
            # a fresh batch is made on the task's chip, not on the
            # first and copied over
            on_chip = _jax.default_device(device) \
                if device is not None else contextlib.nullcontext()
            keyed_by = () if device is None \
                or device == _jax.devices()[0] \
                else (("device", device.id),)
            splits = conn.split_manager.get_splits(
                handle, max(target_splits, task.count), constraint)
            if task.count > 1:
                # round-robin split assignment to this fragment's tasks
                # (reference: NodeScheduler.java:65 split placement)
                splits = splits[task.index::task.count]
            dep = [(handle.catalog, handle.schema, handle.table)]
            entry_cap = page_cache.entry_byte_cap() \
                if page_cache is not None else None
            for s in splits:
                key = None
                if page_cache is not None:
                    st = split_token(s)  # None = no stable identity
                    if st is not None:
                        try:
                            key = ("page", tv, handle.catalog,
                                   handle.schema, handle.table,
                                   st, tuple(columns),
                                   batch_rows, constraint) + keyed_by
                            hash(key)
                        except TypeError:
                            key = None  # unhashable constraint payload
                raw = page_cache.get(key) \
                    if key is not None else None
                if raw is not None:
                    cache_box["hits"] += 1
                    acc = None
                else:
                    if key is not None:
                        cache_box["misses"] += 1
                    raw = conn.page_source.batches(
                        s, columns, batch_rows, constraint)
                    acc = [] if key is not None else None
                acc_bytes = 0
                from presto_tpu.telemetry import ledger as _ledger
                it = iter(raw)
                exhausted = False
                while True:
                    # scan/datagen attribution: the connector's
                    # __next__ is where per-query datagen, file
                    # decode, and page assembly burn host time — the
                    # biggest slice of the caches-off glue gap
                    with on_chip:
                        if _ledger.current() is not None:
                            with _ledger.span("scan"):
                                b = next(it, _SCAN_DONE)
                        else:
                            b = next(it, _SCAN_DONE)
                    if b is _SCAN_DONE:
                        exhausted = True
                        break
                    if device is not None:
                        # commits the batch to the chip; a copy (and a
                        # charge) only where it was made elsewhere
                        b = place(b, device)
                    if _faults.ARMED:
                        # fault site `page_source.next`: every batch a
                        # connector yields, cached or fresh
                        _faults.fire("page_source.next",
                                     table=handle.table,
                                     catalog=handle.catalog)
                    if acc is not None:
                        acc_bytes += batch_bytes(b)
                        if entry_cap is not None \
                                and acc_bytes > entry_cap:
                            acc = None  # too big — stream uncached
                        else:
                            acc.append(b)
                    yield b.rename(rename)
                if exhausted:
                    # natural exhaustion only: an abandoned iterator
                    # (downstream LIMIT) must not commit a partial split
                    if acc is not None:
                        page_cache.put(key, acc, dep)
        df_specs = list(self._df_scans.get(id(node), []))
        if self.task.df_service is not None \
                and self.task.cross_df is not None:
            df_specs += [
                (sym, df_id, self.task.df_service)
                for sym, df_id
                in self.task.cross_df.scans.get(id(node), [])]
        pipe.append(TableScanOperatorFactory(
            self._next_id(), f"scan:{handle.table}", batch_iter,
            df_specs=df_specs or None,
            cache_box=cache_box if page_cache is not None else None))

    def _visit_RemoteSourceNode(self, node, pipe: List):
        from presto_tpu.operators.exchange_ops import (
            ExchangeSourceOperatorFactory,
        )
        exchange = self.task.exchanges[node.exchange_id]
        pipe.append(ExchangeSourceOperatorFactory(
            self._next_id(), exchange, self.task.index,
            device=self.task.device))

    def _visit_ValuesNode(self, node: N.ValuesNode, pipe: List):
        data = {}
        for i, f in enumerate(node.output):
            vals = [row[i] for row in node.rows]
            if f.type.is_string:
                # rows already hold dictionary codes
                import numpy as np
                from presto_tpu.batch import Column, bucket_capacity
                cap = bucket_capacity(max(len(vals), 1))
                arr = np.array([v if v is not None else 0
                                for v in vals], f.type.np_dtype)
                mask = np.array([v is not None for v in vals], bool)
                data[f.symbol] = (arr, mask, f.dictionary)
            else:
                data[f.symbol] = (vals, None, None)
        import numpy as np
        from presto_tpu.batch import Column, bucket_capacity
        import jax.numpy as jnp
        cap = bucket_capacity(max(len(node.rows), 1))
        cols = {}
        for f in node.output:
            vals, mask, dic = data[f.symbol]
            if mask is None:
                col = Column.from_pylist(list(vals), f.type, cap)
            else:
                col = Column.from_numpy(vals, mask, f.type, cap, dic)
            cols[f.symbol] = col
        rv = np.zeros(cap, bool)
        rv[:len(node.rows)] = True
        batch = Batch(cols, jnp.asarray(rv))
        pipe.append(ValuesOperatorFactory(self._next_id(), [batch]))

    def _append_filter_project(self, pipe: List, filter_expr,
                               projections, input_dicts,
                               selectivity=None,
                               sel_provenance: str = "static") -> None:
        """Append a FilterProject — or FUSE it into a lookup join it
        directly follows, so the expression forest evaluates inside
        the probe dispatch and expanded join rows materialize once
        (the probe->project fusion of the radix-join redesign).
        `selectivity` is the estimated surviving-row fraction the
        fusion pass gates fold-terminal fusion on (None = unknown);
        `sel_provenance` says whether it was MEASURED on a prior
        execution ("history") or derived ("static")."""
        tail = pipe[-1] if pipe else None
        if isinstance(tail, LookupJoinOperatorFactory) \
                and not tail.fused:
            # probe-tail fusion keeps the selectivity estimate: the
            # in-trace filter leaves its dead lanes to the deferred-
            # compact protocol, so a chain the probe later feeds into
            # a fold terminal must inherit this fraction or the
            # fusion pass's selective-chain gate goes blind here
            tail.fuse(filter_expr, projections, input_dicts,
                      selectivity=selectivity,
                      sel_provenance=sel_provenance)
            return
        pipe.append(FilterProjectOperatorFactory(
            self._next_id(), filter_expr, projections, input_dicts,
            selectivity=selectivity, sel_provenance=sel_provenance))

    def _estimator(self):
        """The lazily-built stats estimator, history-armed when the
        session enables feedback (planner/stats.py; one estimator —
        and one fingerprint memo — per planned fragment)."""
        if self._stats is None:
            from presto_tpu import history as _history
            from presto_tpu.planner.stats import StatsEstimator
            self._stats = StatsEstimator(
                self.catalogs,
                history=_history.view_for(self.catalogs,
                                          self.session.properties))
        return self._stats

    def _est_selectivity(self, node: N.FilterNode):
        """(estimated fraction of source rows surviving `node`,
        provenance), or (None, "static") when nothing can be said.
        A MEASURED fraction (the node's own prior in->out row ratio
        from the history store) wins over the derived estimate and is
        tagged "history" — the fusion pass treats it as licence to
        fold the chain into its terminal with an in-trace compaction
        sized by the measurement (planner/fusion.py). The derived
        fallback gates fold-terminal fusion exactly as before: below
        a quarter, live rows drop a power-of-four kernel bucket and
        compacting beats folding over full-width dead lanes."""
        try:
            est = self._estimator()
            if est.history is not None:
                sel = est.history.selectivity(node)
                if sel is not None:
                    return sel, "history"
            inner = est.estimate(node.source).rows
            if inner <= 0:
                return None, "static"
            return min(1.0, est.estimate(node).rows / inner), "static"
        except Exception:  # noqa: BLE001 — stats are advisory
            return None, "static"

    def _est_predicate_selectivity(self, source_node, predicate):
        """Estimated surviving fraction of a bare predicate over
        `source_node`'s rows — the join-filter analog of
        _est_selectivity (a join's residual filter never lives in a
        FilterNode, but its FilterProject must still carry an
        estimate or selective join filters always fold into their
        terminals). StatsEstimator's JoinNode estimate ignores the
        node's own filter, so estimating the join and applying the
        predicate's selectivity on top does not double-count."""
        try:
            est = self._estimator()
            inner = est.estimate(source_node)
            if inner.rows <= 0:
                return None
            from presto_tpu.planner.stats import (
                predicate_selectivity,
            )
            return min(1.0, max(0.0, predicate_selectivity(
                predicate, inner)))
        except Exception:  # noqa: BLE001 — stats are advisory
            return None

    def _visit_FilterNode(self, node: N.FilterNode, pipe: List):
        self._visit(node.source, pipe)
        schema = _schema_of(node.source)
        pred = compile_expression(node.predicate, schema)
        projections = [
            (f.symbol, compile_expression(InputRef(f.symbol, f.type),
                                          schema))
            for f in node.output]
        sel, prov = self._est_selectivity(node)
        self._append_filter_project(pipe, pred, projections,
                                    _schema_dicts(schema),
                                    selectivity=sel,
                                    sel_provenance=prov)

    def _visit_ProjectNode(self, node: N.ProjectNode, pipe: List):
        self._visit(node.source, pipe)
        schema = _schema_of(node.source)
        projections = [(sym, compile_expression(e, schema))
                       for sym, e in node.assignments]
        self._append_filter_project(pipe, None, projections,
                                    _schema_dicts(schema))

    def _visit_AggregationNode(self, node: N.AggregationNode, pipe: List):
        self._visit(node.source, pipe)
        schema = _schema_of(node.source)
        key_names = [s for s, _ in node.keys]
        key_exprs = [compile_expression(e, schema) for _, e in node.keys]
        collecting = [a for a in node.aggregates
                      if a.function in ("array_agg", "map_agg")]
        if collecting:
            if len(collecting) != len(node.aggregates):
                raise LocalPlanningError(
                    "array_agg/map_agg cannot be combined with other "
                    "aggregates in one GROUP BY yet — split the query")
            from presto_tpu.operators.array_agg import (
                ArrayAggOperatorFactory, CollectSpec,
            )
            cspecs = []
            for a in collecting:
                mask_ce = compile_expression(a.filter, schema) \
                    if a.filter is not None else None
                cspecs.append(CollectSpec(
                    a.out_symbol,
                    compile_expression(a.argument, schema),
                    compile_expression(a.argument2, schema)
                    if a.argument2 is not None else None,
                    mask_ce))
            width = int(get_property(self.session.properties,
                                     "array_agg_width"))
            pipe.append(ArrayAggOperatorFactory(
                self._next_id(), key_names, key_exprs, cspecs, width))
            return
        specs = []
        for a in node.aggregates:
            arg_ce = None
            if a.argument is not None:
                arg = a.argument
                if a.function in DOUBLE_INPUT_AGGS \
                        and arg.type.is_decimal:
                    arg = SpecialForm("cast", (arg,), DOUBLE)
                arg_ce = compile_expression(arg, schema)
            mask_ce = compile_expression(a.filter, schema) \
                if a.filter is not None else None
            fn = self._make_agg(a, arg_ce)
            specs.append(AggSpec(a.out_symbol, fn, arg_ce, mask_ce))
        max_groups = int(get_property(self.session.properties,
                                      "max_groups"))
        # stats-driven sizing (reference: the planner's NDV-based
        # memory planning): a group-by whose estimated cardinality
        # exceeds the session default starts with a big-enough table
        # instead of paying log4(groups/default) whole-query retries
        # cap: overshooting here inflates every merge/finalize shape
        # (compile time + memory); a genuine overflow still retries 4x.
        # NEVER below the session value — the overflow-retry protocol
        # bumps the session property, and clamping under it would
        # livelock the retry at a too-small size
        est = self._estimated_groups(node)
        if est is not None:
            max_groups = max(max_groups,
                             min(int(est * 2), 1 << 22))
        if self._streaming_agg_eligible(node, key_exprs):
            from presto_tpu.operators.aggregation import (
                StreamingAggregationOperatorFactory,
            )
            pipe.append(StreamingAggregationOperatorFactory(
                self._next_id(), key_names, key_exprs, specs,
                input_dicts=_schema_dicts(schema), mode=node.step))
            return
        pipe.append(AggregationOperatorFactory(
            self._next_id(), key_names, key_exprs, specs, node.step,
            max_groups, input_dicts=_schema_dicts(schema)))

    def _streaming_agg_eligible(self, node: N.AggregationNode,
                                key_exprs) -> bool:
        """True when the aggregation's input arrives sorted by its
        group keys (ascending, nulls last — the grouping kernel's
        canonical packing order, so the carried boundary group is
        always the packed-last slot): a sorted subquery, a merge, or a
        scan whose connector declares a physical sort order. The
        streaming operator then runs in O(batch) memory with no
        overflow retry (reference: StreamingAggregationOperator +
        connector local properties)."""
        # single AND partial steps stream over sorted inputs (the
        # reference's streaming-for-partial-aggregation-enabled); the
        # FINAL step's shuffled state arrival order is never sorted
        if node.step not in ("single", "partial") or not node.keys:
            return False
        if not bool(get_property(self.session.properties,
                                 "streaming_aggregation")):
            return False
        if _direct_domains(key_exprs) is not None:
            return False  # the slot-table path is already bounded
        # group-key symbols in kernel key order (must be bare columns)
        syms = []
        for _, e in node.keys:
            if not isinstance(e, InputRef):
                return False
            syms.append(e.name)
        cur = node.source
        while True:
            if isinstance(cur, N.ProjectNode):
                asg = dict(cur.assignments)
                mapped = []
                for s in syms:
                    e = asg.get(s)
                    if not isinstance(e, InputRef):
                        return False
                    mapped.append(e.name)
                syms = mapped
                cur = cur.source
            elif isinstance(cur, N.FilterNode):
                cur = cur.source
            elif isinstance(cur, (N.SortNode, N.MergeNode)):
                k = len(syms)
                if list(cur.keys[:k]) != syms:
                    return False
                return not any(cur.descending[:k]) \
                    and not any(cur.nulls_first[:k])
            elif isinstance(cur, N.TableScanNode):
                try:
                    conn = self.catalogs.connector(cur.handle.catalog)
                    order = conn.metadata.sorted_by(cur.handle)
                except Exception:
                    return False
                if not order:
                    return False
                cols = [cur.assignments.get(s) for s in syms]
                return order[:len(cols)] == cols
            else:
                return False

    def _estimated_expansion(self, node: N.JoinNode, probe) -> int:
        """Estimated join output rows per probe row, rounded UP to a
        power of two and capped (overshooting inflates every output
        shape; a real underestimate still trips the on-device overflow
        retry). 1 when stats are unknowable — the FK->PK common case
        (reference analog: the row-count estimates behind
        DetermineJoinDistributionType)."""
        try:
            from presto_tpu.planner.stats import UNKNOWN_ROWS
            est = self._estimator()
            out_rows = est.estimate(node).rows
            probe_rows = est.estimate(probe).rows
        except Exception:
            return 1
        if out_rows >= UNKNOWN_ROWS * 0.99 \
                or probe_rows >= UNKNOWN_ROWS * 0.99 \
                or probe_rows <= 0:
            return 1
        ratio = out_rows / probe_rows
        factor = 1
        while factor < ratio and factor < 16:
            factor *= 2
        return factor

    def _estimated_groups(self, node: N.AggregationNode):
        """Estimated distinct groups, or None when unknowable. With
        history armed, a measured prior group count sizes the table
        exactly instead of by NDV products."""
        try:
            from presto_tpu.planner.stats import UNKNOWN_ROWS
            est = self._estimator().estimate(node).rows
        except Exception:
            return None
        return est if est < UNKNOWN_ROWS * 0.99 else None

    @staticmethod
    def _make_agg(a: N.AggCall, arg_ce: Optional[CompiledExpr]):
        t = a.input_type or (arg_ce.type if arg_ce else None)
        return agg_function_for(a.function, t, a.output_type, a.params)

    def _visit_JoinNode(self, node: N.JoinNode, pipe: List):
        if node.join_type == "cross":
            bridge = misc_ops.NestedLoopBridge()
            build_pipe: List = []
            self._visit(node.right, build_pipe)
            build_pipe.append(misc_ops.nested_loop_build_factory(
                self._next_id(), bridge,
                [(f.symbol, f.type, f.dictionary)
                 for f in node.right.output]))
            self._pipelines.append(build_pipe)
            self._visit(node.left, pipe)
            pipe.append(misc_ops.nested_loop_join_factory(
                self._next_id(), bridge))
        elif node.join_type in ("inner", "left", "right", "full"):
            probe, build = node.left, node.right
            criteria = node.criteria
            jt = node.join_type
            if jt == "right":
                probe, build = build, probe
                criteria = [(r, l) for l, r in criteria]
                jt = "left"
            bridge = JoinBridge()
            key_dicts = _unified_key_dicts(probe, build, criteria)
            df_publish = self._plan_dynamic_filters(
                probe, build, criteria) if jt == "inner" else None
            cross = self._cross_df_publish(node)
            if cross:
                df_publish = (df_publish or []) + cross
            build_pipe = []
            self._visit(build, build_pipe)
            build_pipe.append(HashBuildOperatorFactory(
                self._next_id(), bridge, [r for _, r in criteria],
                key_dicts,
                schema_cols=[(f.symbol, f.type, f.dictionary)
                             for f in build.output],
                # a spilled FULL-join build would need per-partition
                # matched-flag tracking; the build stays resident
                spillable=bool(get_property(self.session.properties,
                                            "spill_enabled"))
                and jt != "full",
                df_publish=df_publish,
                consumer_layouts=LookupJoinOperatorFactory
                .readable_layouts(jt)))
            self._pipelines.append(build_pipe)
            self._visit(probe, pipe)
            # stats-seeded output capacity: a many-to-many join whose
            # estimated expansion exceeds the session factor starts
            # with a big-enough capacity instead of paying whole-query
            # x4 retries (the overflow protocol still catches real
            # underestimates). NEVER below the session value — the
            # retry protocol bumps it, and clamping under it would
            # livelock the retry.
            factor = max(
                int(get_property(self.session.properties,
                                 "join_expansion_factor")),
                self._estimated_expansion(node, probe))
            pipe.append(LookupJoinOperatorFactory(
                self._next_id(), bridge,
                [l for l, _ in criteria], jt,
                probe_output=[f.symbol for f in probe.output],
                build_output=[f.symbol for f in build.output],
                build_keys=[r for _, r in criteria],
                key_dicts=key_dicts,
                expansion_factor=factor,
                probe_schema=[(f.symbol, f.type, f.dictionary)
                              for f in probe.output]
                if jt == "full" else None))
        else:
            raise LocalPlanningError(
                f"{node.join_type} join not supported yet")
        if node.filter is not None:
            schema = _schema_of(node)
            pred = compile_expression(node.filter, schema)
            projections = [
                (f.symbol, compile_expression(
                    InputRef(f.symbol, f.type), schema))
                for f in node.output]
            self._append_filter_project(
                pipe, pred, projections, _schema_dicts(schema),
                selectivity=self._est_predicate_selectivity(
                    node, node.filter))

    def _cross_df_publish(self, node) -> List[tuple]:
        """Cross-fragment publications this join owes the query-wide
        DynamicFilterService (wired by plan_cross_fragment_filters;
        node identity keys survive fragmentation — fragments reference
        subtrees of the same plan object)."""
        svc = self.task.df_service
        cdf = self.task.cross_df
        if svc is None or cdf is None:
            return []
        from presto_tpu.execution.dynamic_filters import BoundPublisher
        bound = BoundPublisher(
            svc, (self.task.index, self.task.generation))
        return [(key, df_id, bound)
                for key, df_id in cdf.joins.get(id(node), [])]

    def _plan_dynamic_filters(self, probe, build, criteria):
        """For an INNER join, wire build-key min/max bounds to probe-
        side scans in THIS fragment (reference: the dynamic-filter
        planner rules; mesh plans hit this exactly on broadcast/star
        joins, where the scan and join are co-fragment)."""
        if not bool(get_property(self.session.properties,
                                 "dynamic_filtering")):
            return None
        # history-driven aggressiveness: a build side MEASURED far past
        # the distinct-set bound degrades to bounds-only filters whose
        # collection cost buys nearly nothing (surrogate keys span the
        # whole range) — skip planning the filter at all. Results are
        # unaffected either way; only work moves.
        try:
            est = self._estimator()
            if est.history is not None:
                e = est.history.lookup(build)
                if e is not None and e["rows"] > DF_SKIP_BUILD_ROWS:
                    return None
        except Exception:  # noqa: BLE001 — stats are advisory
            pass
        build_fields = {f.symbol: f for f in build.output}
        publish = []
        for l, r in criteria:
            bf = build_fields.get(r)
            if bf is None or bf.dictionary is not None:
                continue  # numeric/date keys only
            traced = _trace_scan_column(probe, l, self._shared)
            if traced is None:
                continue
            scan_node, scan_sym = traced
            df_id = self._df_registry.new_id()
            publish.append((r, df_id, self._df_registry))
            self._df_scans.setdefault(id(scan_node), []).append(
                (scan_sym, df_id, self._df_registry))
        return publish or None

    def _visit_SemiJoinNode(self, node: N.SemiJoinNode, pipe: List):
        bridge = JoinBridge()
        key_dicts = _unified_key_dicts(
            node.source, node.filtering_source,
            [(node.source_key, node.filtering_key)])
        # IN/EXISTS keeps only source rows whose key appears in the
        # filtering side, so the build publishes to scans in OTHER
        # fragments (pruning there saves an exchange). No local filter:
        # one could only reach a scan feeding this probe through
        # filters and projections, and would repeat the probe's own
        # membership search lane for lane. NOT IN publishes nothing:
        # pruning would drop exactly the rows it keeps.
        df_publish = (self._cross_df_publish(node) or None) \
            if not node.negate else None
        build_pipe: List = []
        self._visit(node.filtering_source, build_pipe)
        build_pipe.append(HashBuildOperatorFactory(
            self._next_id(), bridge, [node.filtering_key], key_dicts,
            schema_cols=[(f.symbol, f.type, f.dictionary)
                         for f in node.filtering_source.output],
            df_publish=df_publish))
        self._pipelines.append(build_pipe)
        self._visit(node.source, pipe)
        pipe.append(SemiJoinOperatorFactory(
            self._next_id(), bridge, [node.source_key], node.negate,
            build_keys=[node.filtering_key], key_dicts=key_dicts))

    def _visit_TopNRowNumberNode(self, node: N.TopNRowNumberNode,
                                 pipe: List):
        """Window (single rank call) + fused rank <= N filter."""
        from presto_tpu.expr.ir import Call, Literal
        from presto_tpu.operators.window_ops import WindowOperatorFactory
        from presto_tpu.ops.window import WindowCallSpec
        from presto_tpu.types import BIGINT, BOOLEAN
        self._visit(node.source, pipe)
        pipe.append(WindowOperatorFactory(
            self._next_id(), node.partition_by, node.order_by,
            node.descending, node.nulls_first,
            [WindowCallSpec(node.row_number_symbol, node.function,
                            None, "FULL", BIGINT, None, 1)]))
        schema = {f.symbol: ColumnSchema(f.symbol, f.type, f.dictionary)
                  for f in node.source.output}
        schema[node.row_number_symbol] = ColumnSchema(
            node.row_number_symbol, BIGINT, None)
        pred = compile_expression(
            Call("less_than_or_equal",
                 (InputRef(node.row_number_symbol, BIGINT),
                  Literal(node.max_rank, BIGINT)), BOOLEAN), schema)
        projections = [
            (f.symbol, compile_expression(
                InputRef(f.symbol, f.type), schema))
            for f in node.output]
        pipe.append(FilterProjectOperatorFactory(
            self._next_id(), pred, projections,
            _schema_dicts(schema)))

    def _visit_WindowNode(self, node: N.WindowNode, pipe: List):
        from presto_tpu.operators.window_ops import WindowOperatorFactory
        from presto_tpu.ops.window import WindowCallSpec
        self._visit(node.source, pipe)
        src_schema = _schema_of(node.source)
        out_fields = {f.symbol: f for f in node.output}
        calls = []
        for c in node.calls:
            out_dict = None
            default = c.default
            if c.argument is not None and c.output_type is not None \
                    and c.output_type.is_string:
                # the call's OUTPUT field carries the (possibly
                # default-extended) dictionary the analyzer chose
                out_dict = out_fields[c.out_symbol].dictionary
                if isinstance(default, str) and out_dict is not None:
                    default = out_dict.index(default)
            calls.append(WindowCallSpec(
                c.out_symbol, c.function, c.argument, c.frame,
                c.output_type, out_dict, c.offset,
                fstart=c.frame_start, fend=c.frame_end,
                filter_arg=c.filter, default=default))
        pipe.append(WindowOperatorFactory(
            self._next_id(), node.partition_by, node.order_by,
            node.descending, node.nulls_first, calls))

    def _visit_SortNode(self, node: N.SortNode, pipe: List):
        self._visit(node.source, pipe)
        pipe.append(OrderByOperatorFactory(
            self._next_id(), node.keys, node.descending,
            node.nulls_first))

    def _visit_TableWriterNode(self, node: N.TableWriterNode,
                               pipe: List):
        from presto_tpu.operators.write_ops import (
            TableWriterOperatorFactory,
        )
        self._visit(node.source, pipe)
        conn = self.catalogs.connector(node.handle.catalog)
        pipe.append(TableWriterOperatorFactory(
            self._next_id(), conn.page_sink, node.handle,
            node.column_sources, node.schema_cols,
            node.output[0].symbol))

    def _visit_TableFinishNode(self, node: N.TableFinishNode,
                               pipe: List):
        from presto_tpu.operators.write_ops import (
            TableFinishOperatorFactory,
        )
        self._visit(node.source, pipe)
        conn = self.catalogs.connector(node.handle.catalog)
        pipe.append(TableFinishOperatorFactory(
            self._next_id(), conn.page_sink, node.handle,
            node.source.output[0].symbol, node.output[0].symbol))

    def _visit_MergeNode(self, node: N.MergeNode, pipe: List):
        from presto_tpu.operators.sort_ops import MergeOperatorFactory
        self._visit(node.source, pipe)
        pipe.append(MergeOperatorFactory(
            self._next_id(), node.keys, node.descending,
            node.nulls_first))

    def _visit_TopNNode(self, node: N.TopNNode, pipe: List):
        self._visit(node.source, pipe)
        schema_cols = [(f.symbol, f.type, f.dictionary)
                       for f in node.output]
        pipe.append(TopNOperatorFactory(
            self._next_id(), node.n, node.keys, node.descending,
            node.nulls_first, schema_cols))

    def _visit_LimitNode(self, node: N.LimitNode, pipe: List):
        from presto_tpu.operators.core import LimitOperatorFactory
        self._visit(node.source, pipe)
        pipe.append(LimitOperatorFactory(self._next_id(), node.n))

    def _visit_DistinctNode(self, node: N.DistinctNode, pipe: List):
        self._visit(node.source, pipe)
        schema_cols = [(f.symbol, f.type, f.dictionary)
                       for f in node.output]
        pipe.append(DistinctOperatorFactory(self._next_id(),
                                            schema_cols))

    def _visit_EnforceSingleRowNode(self, node, pipe: List):
        self._visit(node.source, pipe)
        pipe.append(misc_ops.enforce_single_row_factory(self._next_id()))

    def _visit_AssignUniqueIdNode(self, node: N.AssignUniqueIdNode,
                                  pipe: List):
        self._visit(node.source, pipe)
        # ids strided by task so they are unique across a distributed
        # fragment's tasks (reference: AssignUniqueIdOperator packs the
        # driver instance id into the high bits)
        pipe.append(misc_ops.AssignUniqueIdOperatorFactory(
            self._next_id(), node.symbol,
            start=self.task.index, stride=self.task.count))

    def _visit_UnnestNode(self, node: N.UnnestNode, pipe: List):
        self._visit(node.source, pipe)
        out_dicts = {s: node.field(s).dictionary
                     for s, _, _ in node.items}
        pipe.append(misc_ops.UnnestOperatorFactory(
            self._next_id(), node.items, node.ordinality_symbol,
            out_dicts))

    def _visit_GroupIdNode(self, node: N.GroupIdNode, pipe: List):
        self._visit(node.source, pipe)
        pipe.append(misc_ops.GroupIdOperatorFactory(
            self._next_id(), node.groupings, node.gid_symbol,
            node.grouping_outputs))

    def _visit_UnionNode(self, node: N.UnionNode, pipe: List):
        queue = misc_ops.LocalQueue(len(node.inputs))
        for inp, symmap in zip(node.inputs, node.symbol_maps):
            p: List = []
            self._visit(inp, p)
            rename = {src: out for out, src in symmap.items()}
            p.append(misc_ops.queue_sink_factory(self._next_id(), queue,
                                                 rename))
            self._pipelines.append(p)
        pipe.append(misc_ops.queue_source_factory(self._next_id(),
                                                  queue))

    def _visit_ExchangeNode(self, node: N.ExchangeNode, pipe: List):
        # single-process mode: exchanges are free (pjit reshard analog)
        self._visit(node.source, pipe)

    def _visit_OutputNode(self, node: N.OutputNode, pipe: List):
        self._visit(node.source, pipe)


# ---------------------------------------------------------------------------

#: aggregates whose DECIMAL argument is pre-cast to DOUBLE (the kernel
#: state is float64); shared by local planning and AddExchanges so both
#: sides of a partial/final split agree on the input type
DOUBLE_INPUT_AGGS = frozenset({
    "avg", "var_samp", "var_pop", "variance", "stddev", "stddev_samp",
    "stddev_pop", "geometric_mean",
})

_VARIANCE_CANON = {"variance": "var_samp", "stddev_samp": "stddev"}


#: aggregates whose state has no intermediate column representation —
#: the planner co-locates whole groups (like DISTINCT aggs) instead of
#: splitting partial/final across an exchange
NO_SPLIT_AGGS = {"approx_percentile", "approx_distinct",
                 "array_agg", "map_agg"}


def agg_function_for(name: str, input_type: Optional[Type],
                     output_type: Optional[Type],
                     params: tuple = ()) -> hashagg.AggFunction:
    """Resolve an aggregate name + argument type to its state machine.
    Shared by local planning and the AddExchanges partial/final split
    (both sides must construct bit-identical state layouts)."""
    if name == "approx_percentile":
        return hashagg.make_approx_percentile(params[0])
    if name == "approx_distinct":
        return hashagg.make_approx_distinct(
            input_type, params[0] if params else hashagg.HLL_DEFAULT_ERROR)
    if name == "count":
        return hashagg.make_count(input_type)
    if name == "sum":
        return hashagg.make_sum(input_type, output_type)
    if name == "avg":
        return hashagg.make_avg(input_type)
    if name in ("min", "max", "arbitrary", "any_value"):
        fn = hashagg.make_min if name != "max" else hashagg.make_max
        return fn(input_type)
    if name in ("var_samp", "var_pop", "variance", "stddev",
                "stddev_samp", "stddev_pop"):
        return hashagg.make_variance(_VARIANCE_CANON.get(name, name))
    if name == "count_if":
        return hashagg.make_count_if()
    if name in ("bool_and", "bool_or", "every"):
        return hashagg.make_bool_and(name == "bool_or")
    if name == "geometric_mean":
        return hashagg.make_geometric_mean()
    if name == "checksum":
        return hashagg.make_checksum(input_type)
    if name in ("skewness", "kurtosis"):
        return hashagg.make_moments(name)
    if name == "entropy":
        return hashagg.make_entropy()
    raise LocalPlanningError(f"unknown aggregate {name}")


def _unified_key_dicts(probe: N.PlanNode, build: N.PlanNode,
                       criteria) -> Optional[List[Optional[tuple]]]:
    """For string join keys, the union dictionary both sides re-encode
    onto so code equality is string equality (batch.remap_column)."""
    from presto_tpu.batch import union_dictionary
    out: List[Optional[tuple]] = []
    any_string = False
    for l, r in criteria:
        lf = probe.field(l)
        rf = build.field(r)
        if lf.type.is_string or rf.type.is_string:
            any_string = True
            out.append(union_dictionary(lf.dictionary, rf.dictionary))
        else:
            out.append(None)
    return out if any_string else None


def _parent_counts(root: N.PlanNode) -> Dict[int, int]:
    """Parent-edge count per node id over the plan DAG."""
    counts: Dict[int, int] = {}
    seen: set = set()

    def walk(n: N.PlanNode) -> None:
        for s in n.sources():
            counts[id(s)] = counts.get(id(s), 0) + 1
            if id(s) not in seen:
                seen.add(id(s))
                walk(s)
    walk(root)
    return counts


def _shared_nodes(root: N.PlanNode) -> set:
    """ids of plan nodes with more than one parent (DAG sharing)."""
    return {nid for nid, c in _parent_counts(root).items() if c > 1}


def field_symbols(f: "N.Field") -> List[str]:
    """Physical column symbols of an output field: the symbol itself,
    or — for complex-typed fields — the slot symbols its form
    references (the named symbol has no column)."""
    form = getattr(f, "form", None)
    if form is None:
        return [f.symbol]
    return N.form_slot_symbols(form)


def prune_unused_columns(root: N.PlanNode) -> None:
    """Demand-driven column pruning, top-down (reference:
    PruneUnreferencedOutputs): each node narrows its output to what its
    consumer demands and propagates its own input needs to its sources.
    Mutates the plan in place; symbols are globally unique.

    DAG-aware: a subtree shared by several parents (e.g. the probe side
    of a unique-id decorrelation feeds both a join and a semi join)
    accumulates demand from ALL parents before being narrowed — the
    naive recursive narrowing would let the first parent's prune hide
    columns the second parent still needs."""
    # pass 0: count parent edges (Kahn topological order over the DAG)
    pending = _parent_counts(root)

    # pass 1: propagate demand top-down, processing a node only once all
    # of its parents have contributed
    demands: Dict[int, set] = {id(root): {
        s for f in root.output for s in field_symbols(f)}}
    order: List[N.PlanNode] = []
    queue: List[N.PlanNode] = [root]
    while queue:
        node = queue.pop()
        order.append(node)
        for child, d in _child_demand(node, demands[id(node)]):
            demands.setdefault(id(child), set()).update(d)
            pending[id(child)] -= 1
            if pending[id(child)] == 0:
                queue.append(child)

    # pass 2: narrow each node once, with its final accumulated demand
    for node in order:
        _apply_prune(node, demands[id(node)])


def _child_demand(node: N.PlanNode, demand: set
                  ) -> List[Tuple[N.PlanNode, set]]:
    if isinstance(node, (N.TableScanNode, N.ValuesNode,
                         N.RemoteSourceNode)):
        return []
    if isinstance(node, N.FilterNode):
        child = set(demand)
        _refs(node.predicate, child)
        return [(node.source, child)]
    if isinstance(node, N.TableWriterNode):
        return [(node.source,
                 {s for s in node.column_sources.values()
                  if s is not None})]
    if isinstance(node, N.TableFinishNode):
        return [(node.source,
                 {f.symbol for f in node.source.output})]
    if isinstance(node, N.ProjectNode):
        child: set = set()
        for s, e in node.assignments:
            if s in demand:
                _refs(e, child)
        return [(node.source, child)]
    if isinstance(node, N.AggregationNode):
        child = set()
        for _, e in node.keys:
            _refs(e, child)
        for a in node.aggregates:
            if _agg_demanded(a, demand):
                if a.argument is not None:
                    _refs(a.argument, child)
                if a.argument2 is not None:
                    _refs(a.argument2, child)
                if a.filter is not None:
                    _refs(a.filter, child)
        return [(node.source, child)]
    if isinstance(node, N.JoinNode):
        extra: set = set()
        for l, r in node.criteria:
            extra.add(l)
            extra.add(r)
        if node.filter is not None:
            _refs(node.filter, extra)
        want = demand | extra
        left_syms = {f.symbol for f in node.left.output}
        right_syms = {f.symbol for f in node.right.output}
        return [(node.left, want & left_syms),
                (node.right, want & right_syms)]
    if isinstance(node, N.SemiJoinNode):
        return [(node.source, demand | {node.source_key}),
                (node.filtering_source, {node.filtering_key})]
    if isinstance(node, (N.SortNode, N.TopNNode, N.MergeNode)):
        return [(node.source, demand | set(node.keys))]
    if isinstance(node, N.WindowNode):
        child = (demand - {c.out_symbol for c in node.calls}) \
            | set(node.partition_by) | set(node.order_by) \
            | {c.argument for c in node.calls if c.argument} \
            | {c.filter for c in node.calls if c.filter}
        return [(node.source, child)]
    if isinstance(node, N.TopNRowNumberNode):
        child = (demand - {node.row_number_symbol}) \
            | set(node.partition_by) | set(node.order_by)
        return [(node.source, child)]
    if isinstance(node, N.DistinctNode):
        # DISTINCT is defined over exactly its output columns
        return [(node.source, {f.symbol for f in node.output})]
    if isinstance(node, (N.LimitNode, N.EnforceSingleRowNode,
                         N.ExchangeNode)):
        return [(node.source, set(demand))]
    if isinstance(node, N.AssignUniqueIdNode):
        return [(node.source, demand - {node.symbol})]
    if isinstance(node, N.GroupIdNode):
        drop = {node.gid_symbol} | {s for s, _ in node.grouping_outputs}
        return [(node.source, (demand - drop) | set(node.all_keys))]
    if isinstance(node, N.UnnestNode):
        drop = {s for s, _, _ in node.items}
        if node.ordinality_symbol:
            drop.add(node.ordinality_symbol)
        elem = {e for _, syms, _ in node.items for e in syms}
        elem |= {ls for _, _, ls in node.items if ls}
        return [(node.source, (demand - drop) | elem)]
    if isinstance(node, N.UnionNode):
        out = []
        for inp, m in zip(node.inputs, node.symbol_maps):
            m2 = {o: src for o, src in m.items() if o in demand}
            out.append((inp, set(m2.values())))
        return out
    if isinstance(node, N.OutputNode):
        # complex-typed outputs demand their SLOT columns, not the
        # (column-less) named symbol
        return [(node.source,
                 {s for f in node.output for s in field_symbols(f)})]
    raise LocalPlanningError(
        f"prune: unhandled node {type(node).__name__}")


def _agg_demanded(a: "N.AggCall", demand: set) -> bool:
    """A collection aggregate (array_agg/map_agg) is demanded through
    its SLOT symbols (<out>__a0, <out>__len, ...), never the
    column-less out symbol itself."""
    if a.out_symbol in demand:
        return True
    prefix = a.out_symbol + "__"
    return any(d.startswith(prefix) for d in demand)


def _apply_prune(node: N.PlanNode, demand: set) -> None:
    def narrowed(extra: set = frozenset()):
        want = demand | extra
        return tuple(f for f in node.output if f.symbol in want)

    if isinstance(node, N.TableScanNode):
        keep = {s: c for s, c in node.assignments.items() if s in demand}
        if not keep:  # keep one column so the scan still yields rows
            first = next(iter(node.assignments.items()))
            keep = {first[0]: first[1]}
        node.assignments = keep
        node.output = tuple(f for f in node.output if f.symbol in keep)
    elif isinstance(node, (N.ValuesNode, N.OutputNode, N.DistinctNode,
                           N.RemoteSourceNode)):
        # a remote source's schema is fixed by its producer fragment;
        # extra columns in received batches are simply ignored
        pass
    elif isinstance(node, N.ProjectNode):
        node.assignments = [(s, e) for s, e in node.assignments
                            if s in demand]
        node.output = narrowed()
    elif isinstance(node, N.AggregationNode):
        node.aggregates = [a for a in node.aggregates
                           if _agg_demanded(a, demand)]
        keep = {s for s, _ in node.keys} | \
            {a.out_symbol for a in node.aggregates}
        node.output = tuple(f for f in node.output if f.symbol in keep)
    elif isinstance(node, N.JoinNode):
        extra: set = set()
        for l, r in node.criteria:
            extra.add(l)
            extra.add(r)
        if node.filter is not None:
            _refs(node.filter, extra)
        node.output = narrowed(extra)
    elif isinstance(node, N.SemiJoinNode):
        node.output = narrowed({node.source_key})
    elif isinstance(node, (N.SortNode, N.TopNNode, N.MergeNode)):
        node.output = narrowed(set(node.keys))
    elif isinstance(node, N.WindowNode):
        node.calls = [c for c in node.calls if c.out_symbol in demand]
        node.output = narrowed(
            set(node.partition_by) | set(node.order_by)
            | {c.argument for c in node.calls if c.argument})
    elif isinstance(node, N.TopNRowNumberNode):
        node.output = narrowed(
            set(node.partition_by) | set(node.order_by))
    elif isinstance(node, N.AssignUniqueIdNode):
        node.output = narrowed({node.symbol})
    elif isinstance(node, N.GroupIdNode):
        node.output = narrowed(
            set(node.all_keys) | {node.gid_symbol}
            | {s for s, _ in node.grouping_outputs})
    elif isinstance(node, N.UnnestNode):
        keep = {s for s, _, _ in node.items}
        if node.ordinality_symbol:
            keep.add(node.ordinality_symbol)
        node.output = narrowed(keep)
    elif isinstance(node, N.UnionNode):
        node.output = narrowed()
        keep_syms = {f.symbol for f in node.output}
        node.symbol_maps = [
            {o: src for o, src in m.items() if o in keep_syms}
            for m in node.symbol_maps]
    else:
        node.output = narrowed()


def _refs(e: RowExpression, out: set) -> None:
    for x in walk(e):
        if isinstance(x, InputRef):
            out.add(x.name)
