"""MeshRunner: distributed SQL execution over a jax.sharding.Mesh.

The in-process analog of the reference's DistributedQueryRunner
(presto-tests DistributedQueryRunner.java:85 — real scheduling, real
shuffle, one process): parse -> plan -> optimize -> AddExchanges ->
fragment -> one task per mesh device per distributed fragment -> one
round-robin driver loop over every task's pipelines, with exchanges
riding jax.lax.all_to_all over the mesh (parallel/shuffle.py).

On real hardware the same code runs over a TPU slice's ICI mesh; tests
use the 8-virtual-device CPU mesh
(XLA_FLAGS=--xla_force_host_platform_device_count=8).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional

from presto_tpu import sanitize
from presto_tpu.operators import exchange_ops
from presto_tpu.operators.exchange_ops import MeshExchange, edge_key_dicts
from presto_tpu.parallel.mesh import make_mesh
from presto_tpu.planner import nodes as N
from presto_tpu.planner.exchanges import (
    FragmentedPlan, add_exchanges, fragment_plan,
)
from presto_tpu.planner.local_planner import (
    LocalExecutionPlanner, TaskContext, prune_unused_columns,
)
from presto_tpu.session_properties import get_property
from presto_tpu.runner.local import (
    LocalRunner, MaterializedResult, QueryError,
)


#: one lock per mesh (keyed by its devices' ids, so two runners over
#: the same chips share it): a wave is ONE SPMD program over every
#: chip of the mesh, and two statements' waves interleaved on one mesh
#: would each wait for the other's shards
_MESH_LOCKS: Dict[tuple, Any] = {}
_MESH_LOCKS_GUARD = sanitize.lock("runner.mesh_registry")


def _mesh_lock(devices):
    key = tuple(d.id for d in devices)
    with _MESH_LOCKS_GUARD:
        if key not in _MESH_LOCKS:
            _MESH_LOCKS[key] = sanitize.lock("runner.mesh")
        return _MESH_LOCKS[key]


class MeshRunner(LocalRunner):
    def __init__(self, catalog: str = "tpch", schema: str = "tiny",
                 properties: Optional[Dict[str, Any]] = None,
                 n_workers: Optional[int] = None, mesh=None,
                 user: str = "", access_control=None):
        super().__init__(catalog, schema, properties, user=user,
                         access_control=access_control)
        self.mesh = mesh if mesh is not None else make_mesh(n_workers)
        self.n_workers = int(self.mesh.devices.size)
        self._devices = list(self.mesh.devices.reshape(-1))
        self._mesh_lock = _mesh_lock(self._devices)

    @contextlib.contextmanager
    def _whole_mesh(self):
        """One statement's collectives at a time: the calling thread
        holds the mesh for one _run_fragments. The wait is admission
        by another name (the ledger's `queued`), counted in
        presto_tpu_mesh_lock_wait_ns_total, and ends on the
        statement's cancel or deadline like any drive round."""
        from presto_tpu.runner.local import check_lifecycle
        from presto_tpu.telemetry import ledger as _ledger
        from presto_tpu.telemetry.metrics import METRICS
        if not self._mesh_lock.acquire(blocking=False):
            cancel, deadline = self._lifecycle()
            t0 = time.perf_counter_ns()
            try:
                with _ledger.span("queued"):
                    while not self._mesh_lock.acquire(timeout=0.05):
                        check_lifecycle(cancel, deadline)
            finally:
                METRICS.inc("presto_tpu_mesh_lock_wait_ns_total",
                            time.perf_counter_ns() - t0)
        try:
            yield
        finally:
            self._mesh_lock.release()

    # ------------------------------------------------------------------

    def _plan_cache(self):
        """Mesh plans are NOT plan-cache eligible: add_exchanges and
        the fragmenter mutate the plan tree in place, so a shared
        cached plan would be poisoned for every other consumer (and
        re-exchanging an exchanged plan is not idempotent). The mesh
        path keeps the page-source cache only; serving-path reuse is
        the single-node coordinator's job."""
        return None

    def _run_plan(self, plan: N.OutputNode,
                  profile: bool = False,
                  on_retry=None) -> MaterializedResult:
        """`on_retry` fires before every overflow/OOM re-execution —
        write plans drop uncommitted sink appends there."""
        from presto_tpu.execution.memory import MemoryLimitExceeded
        from presto_tpu.operators.aggregation import GroupLimitExceeded
        from presto_tpu.operators.fused_fragment import (
            FusedChainCompactOverflow,
        )
        from presto_tpu.operators.join_ops import JoinCapacityExceeded
        prune_unused_columns(plan)
        plan = add_exchanges(plan, self.catalogs, self.session)
        # pass-boundary sanity: the exchanged plan must still resolve
        # (exchanges.py rewrites in place), and the fragment cut must
        # keep producer/consumer schemes, schemas and partition keys
        # consistent — the precondition for sharding-preserving stage
        # boundaries (reference: PlanSanityChecker after AddExchanges)
        from presto_tpu.planner.validation import (
            validate, validate_fragments,
        )
        validate(plan, "exchanges", session=self.session)
        fplan = fragment_plan(plan)
        validate_fragments(fplan, "exchanges", session=self.session)
        session = self.session
        # query-local OOM escalation state: (operator, lifespans at the
        # failure, bytes it asked for) of the previous OOM
        prev_oom = None
        from presto_tpu.telemetry.metrics import METRICS
        while True:
            try:
                with self._whole_mesh():
                    out = self._run_fragments(fplan, session, profile)
                METRICS.inc("presto_tpu_mesh_queries_total",
                            status="ok")
                return out
            except GroupLimitExceeded as e:
                if e.suggested > 1 << 26:
                    raise QueryError(
                        "group-by exceeds max supported groups") from e
                session = dataclasses.replace(
                    session, properties={**session.properties,
                                         "max_groups": e.suggested})
                METRICS.inc("presto_tpu_mesh_retries_total",
                            kind="max_groups")
                if on_retry is not None:
                    on_retry()
            except JoinCapacityExceeded as e:
                if e.suggested > 1 << 10:
                    raise QueryError(
                        "join expansion exceeds supported factor") from e
                session = dataclasses.replace(
                    session, properties={
                        **session.properties,
                        "join_expansion_factor": e.suggested})
                METRICS.inc("presto_tpu_mesh_retries_total",
                            kind="join_expansion")
                if on_retry is not None:
                    on_retry()
            except FusedChainCompactOverflow:
                # same contract as the local runner: a history-sized
                # in-trace compaction overflowed — retry with the
                # fusion upgrade off (always-correct PARTIAL path)
                session = dataclasses.replace(
                    session, properties={
                        **session.properties,
                        "history_driven_fusion": False})
                METRICS.inc("presto_tpu_mesh_retries_total",
                            kind="history_fusion")
                if on_retry is not None:
                    on_retry()
            except MemoryLimitExceeded as e:
                # grouped (bucket-wise) execution retry: split the hash
                # space into lifespans so only 1/G of each shuffled
                # working set is on device at once (P6 — the reference
                # decides this at plan time from bucketing;
                # PlanFragmenter.java:243-260)
                if not any(self._grouped_eligible(fplan, f)
                           for f in fplan.fragments.values()):
                    raise QueryError(
                        f"{e} — no fragment is eligible for bucket-wise "
                        "execution; raise hbm_budget_bytes") from e
                # logical operator identity: name#id is stable across
                # retries (ids restart per planner deterministically);
                # the @instance suffix is not
                oom_op = e.tag.split("@")[0]
                cur = int(get_property(session.properties, "lifespans"))
                if prev_oom is not None:
                    p_op, p_g, p_req = prev_oom
                    if p_op == oom_op and cur > p_g \
                            and e.requested >= 0.75 * p_req:
                        # escalating lifespans did not shrink this
                        # operator's request — it sits in an ineligible
                        # fragment or holds per-bucket-invariant state;
                        # more buckets won't help
                        raise QueryError(
                            f"{e} — bucket-wise execution did not "
                            "reduce this operator's footprint; raise "
                            "hbm_budget_bytes") from e
                prev_oom = (oom_op, cur, e.requested)
                new = max(cur * 4, 4)
                if new > 256:
                    raise QueryError(
                        f"query exceeds the HBM budget even with {cur} "
                        f"lifespans: {e}") from e
                session = dataclasses.replace(
                    session, properties={**session.properties,
                                         "lifespans": new})
                METRICS.inc("presto_tpu_mesh_retries_total",
                            kind="lifespans")
                if on_retry is not None:
                    on_retry()

    def _task_count(self, fragment) -> int:
        if fragment.partitioning == "single":
            return 1
        if getattr(fragment, "max_tasks", None):
            # scaled writers: fragment width sized by data volume
            return max(1, min(self.n_workers, fragment.max_tasks))
        return self.n_workers

    @staticmethod
    def _grouped_eligible(fplan: FragmentedPlan, fragment) -> bool:
        """A fragment can run bucket-wise iff every input is a KEYED
        repartition (the lifespan hash then splits groups/join rows
        consistently) and nothing inside depends on whole-input state
        across buckets (scans stream splits; unique-id generators would
        restart per lifespan)."""
        if fragment.partitioning != "distributed":
            return False
        edges = [fplan.edges[x] for x in fragment.source_edges]
        if not edges or any(e.scheme != "repartition"
                            or not e.partition_keys for e in edges):
            return False
        bad = [False]

        def walk(n):
            if isinstance(n, (N.TableScanNode, N.AssignUniqueIdNode)):
                bad[0] = True
            for s in n.sources():
                walk(s)
        walk(fragment.root)
        return not bad[0]

    def _run_fragments(self, fplan: FragmentedPlan, session,
                       profile: bool = False) -> MaterializedResult:
        # the kernel shape-bucket gate rides a thread-local that
        # LocalRunner.execute sets from the ORIGINAL session; the mesh
        # phased drive re-plans under RETRY-BUMPED sessions (lifespans,
        # max_groups) on this same thread — install the gate from the
        # session actually driving this attempt, like
        # node.execute_fragment and the coordinator root drive do
        from presto_tpu import batch as _batch
        from presto_tpu.planner import fusion as _fusion
        prev_sb = _batch.set_shape_buckets(
            bool(get_property(session.properties,
                              "kernel_shape_buckets")))
        # same deal for the fragment-fusion gate: fragment planning
        # happens per-task below with session objects the retry
        # ladder may have rebuilt — the statement's session decides
        prev_fg = _fusion.set_fusion_gate(
            bool(get_property(session.properties,
                              "fragment_fusion_enabled")))
        try:
            return self._run_fragments_inner(fplan, session, profile)
        finally:
            _batch.set_shape_buckets(prev_sb)
            _fusion.set_fusion_gate(prev_fg)

    def _run_fragments_inner(self, fplan: FragmentedPlan, session,
                             profile: bool = False
                             ) -> MaterializedResult:
        import time as _time
        from presto_tpu.execution.memory import MemoryPool
        from presto_tpu.operators.base import DriverContext
        from presto_tpu.operators.driver import Driver

        budget = get_property(session.properties, "hbm_budget_bytes")
        pool = MemoryPool(int(budget) if budget else None)
        G = int(get_property(session.properties, "lifespans"))
        lifespans_of = {
            fid: (G if G > 1
                  and self._grouped_eligible(fplan, frag) else 1)
            for fid, frag in fplan.fragments.items()
        }

        recover = bool(get_property(session.properties,
                                    "recoverable_grouped_execution"))
        exchanges: Dict[int, MeshExchange] = {}
        for xid, edge in fplan.edges.items():
            producer = fplan.fragments[edge.producer]
            consumer = fplan.fragments[edge.consumer]
            exchanges[xid] = MeshExchange(
                xid, edge.scheme, edge.partition_keys,
                edge.hash_dicts, edge_key_dicts(edge), self.mesh,
                n_producers=self._task_count(producer),
                n_consumers=self._task_count(consumer),
                lifespans=lifespans_of[edge.consumer],
                producer_finishes=lifespans_of[edge.producer],
                pool=pool,
                host_spool_bytes=int(get_property(
                    session.properties, "host_spool_bytes")),
                recoverable=recover
                and lifespans_of[edge.consumer] > 1)

        # cross-fragment dynamic filters: one query-wide service; each
        # filter expects (build fragment tasks x lifespan generations)
        # publications before scans may apply it (see
        # exchanges.plan_cross_fragment_filters)
        df_service = cross_df = None
        if bool(get_property(session.properties, "dynamic_filtering")):
            from presto_tpu.execution.dynamic_filters import (
                DynamicFilterService,
            )
            from presto_tpu.planner.exchanges import (
                plan_cross_fragment_filters,
            )
            cdf = plan_cross_fragment_filters(fplan)
            if cdf.build_fragment:
                df_service = DynamicFilterService()
                cross_df = cdf
                for df_id, fid in cdf.build_fragment.items():
                    df_service.expect(
                        df_id,
                        self._task_count(fplan.fragments[fid])
                        * lifespans_of[fid])

        dctx = DriverContext(profile=profile, memory=pool)
        result = None
        all_drivers: List[Driver] = []
        instance_drivers: Dict[int, List[Driver]] = {}
        remaining_lifespans: Dict[int, int] = {}

        def spawn_fragment(fid: int) -> List[Driver]:
            fragment = fplan.fragments[fid]
            n_tasks = self._task_count(fragment)
            sink_edges = [exchanges[e.exchange_id]
                          for e in fplan.producer_edges(fid)]
            created: List[Driver] = []
            nonlocal result
            # generation number derives from the remaining-lifespan
            # counter (call sites update it BEFORE spawning); a
            # recovery respawn leaves it unchanged, so the retried
            # generation keeps its publisher identity
            gen = (lifespans_of[fid] - 1) \
                - remaining_lifespans.get(fid, lifespans_of[fid] - 1)
            for t in range(n_tasks):
                task = TaskContext(
                    index=t, count=n_tasks,
                    device=self._devices[t] if n_tasks > 1
                    else self._devices[0],
                    exchanges=exchanges,
                    df_service=df_service, cross_df=cross_df,
                    generation=gen)
                planner = LocalExecutionPlanner(self.catalogs, session,
                                                task=task)
                if fid == fplan.root_id:
                    assert n_tasks == 1, "root fragment must be single"
                    lplan = planner.plan(fragment.root)
                    pipelines = lplan.pipelines
                    result = lplan
                else:
                    pipelines = planner.plan_fragment(
                        fragment.root, sink_edges,
                        staged_output=recover
                        and lifespans_of[fid] > 1)
                for pipe in pipelines:
                    d = Driver([f.create(dctx) for f in pipe])
                    # per-device wall attribution (ledger.device_scope
                    # in the phased drive): which mesh slot this
                    # driver's quanta bill against
                    d._mesh_device = t if n_tasks > 1 else None
                    created.append(d)
            return created

        # phased execution (reference: PhasedExecutionSchedule):
        # probe-producer fragments wait for their build-producer
        # fragments to finish — build tables exist and dynamic
        # filters are complete before probe pages flow
        phase_deps: Dict[int, List[int]] = {
            fid: [] for fid in fplan.fragments}
        if bool(get_property(session.properties, "phased_execution")):
            from presto_tpu.planner.exchanges import plan_phases
            phase_deps = plan_phases(fplan)
        deferred = [fid for fid in fplan.fragments
                    if phase_deps[fid]]
        for fid in fplan.fragments:
            if fid in deferred:
                continue
            remaining_lifespans[fid] = lifespans_of[fid] - 1
            drivers = spawn_fragment(fid)
            all_drivers.extend(drivers)
            instance_drivers[fid] = drivers
        # the root fragment is never gated (it produces nothing), so
        # `result` is always materialized by the eager spawns
        assert result is not None

        t0 = _time.perf_counter()
        stat_snaps: List[List] = []
        cancel, deadline = self._lifecycle()
        try:
            self._drive_phased(fplan, all_drivers, instance_drivers,
                               remaining_lifespans, exchanges,
                               spawn_fragment,
                               stat_snaps,
                               deferred=deferred,
                               phase_deps=phase_deps,
                               lifespans_of=lifespans_of,
                               recover=recover,
                               cancel=cancel, deadline=deadline)
            from presto_tpu.operators.base import run_deferred_checks
            run_deferred_checks(dctx)
        finally:
            # spill files must never outlive the query, error or not
            self._last_spilled_pages = sum(
                x.spilled_pages for x in exchanges.values())
            for x in exchanges.values():
                x.close()
        # snapshots are collected for every run (lightweight counters;
        # rows only under profile) — they feed the query-history stats
        # and system.runtime.operator_stats like the local runner's
        self._session_tl.op_stats = stat_snaps
        if profile:
            self._last_profile = self._render_operator_stats(
                stat_snaps, _time.perf_counter() - t0, pool)
            # mesh plans are re-exchanged copies — plan-node identity
            # is gone, so EXPLAIN ANALYZE keeps the pipeline table only
            self._last_annotate = None
        return MaterializedResult(result.result_names,
                                  result.result_sink,
                                  result.result_fields)

    @staticmethod
    def _drive_phased(fplan, all_drivers, instance_drivers,
                      remaining_lifespans, exchanges, spawn_fragment,
                      stat_snaps: Optional[List] = None,
                      max_rounds: int = 2_000_000,
                      deferred: Optional[List[int]] = None,
                      phase_deps: Optional[Dict[int, List[int]]] = None,
                      lifespans_of: Optional[Dict[int, int]] = None,
                      recover: bool = False,
                      cancel=None,
                      deadline: Optional[float] = None) -> None:
        """Round-robin drive with lifespan phases: when the loop stalls
        because a grouped fragment's current bucket is drained, advance
        its input exchanges to the next bucket and spawn fresh task
        instances (reference: SqlTaskExecution's per-driver-group
        lifecycles, SqlTaskExecution.java:193-207). Closed generations
        are DROPPED from the active set so their operators (and the
        device buffers they reference) become collectable — HBM must
        actually shrink per bucket, not just in the pool ledger."""
        from presto_tpu.runner.local import LocalRunner

        def retire(drivers):
            for d in drivers:
                d.close()
            if stat_snaps is not None:
                stat_snaps.extend(
                    LocalRunner.snapshot_driver_stats(drivers))

        deferred = list(deferred or [])

        def fragment_complete(fid: int) -> bool:
            if fid in deferred or fid not in instance_drivers:
                return False
            return remaining_lifespans.get(fid, 0) <= 0 and \
                all(d.is_finished() for d in instance_drivers[fid])

        def spawn_ready_deferred() -> bool:
            fired = False
            for fid in list(deferred):
                if all(fragment_complete(b) for b in phase_deps[fid]):
                    deferred.remove(fid)
                    remaining_lifespans[fid] = \
                        (lifespans_of[fid] if lifespans_of else 1) - 1
                    fresh = spawn_fragment(fid)
                    instance_drivers[fid] = fresh
                    all_drivers.extend(fresh)
                    fired = True
            return fired

        from presto_tpu.operators.base import RetryableTaskError
        bucket_retries: Dict[int, int] = {}

        def swap_generation(fid: int, close_fn) -> None:
            """Replace a fragment's current driver generation: retire
            (or abort) the old drivers, fix the driver lists, spawn a
            fresh generation — the ONE copy of this bookkeeping shared
            by lifespan advance and bucket recovery."""
            retiring = instance_drivers[fid]
            close_fn(retiring)
            gone = set(map(id, retiring))
            all_drivers[:] = [d for d in all_drivers
                              if id(d) not in gone]
            fresh = spawn_fragment(fid)
            instance_drivers[fid] = fresh
            all_drivers.extend(fresh)

        def recover_generation(failed_driver) -> bool:
            """P7: re-run ONLY the failed bucket's generation from its
            retained exchange inputs (reference: recoverable grouped
            execution, PlanFragmenter.java:243-260). Possible when the
            fragment is recoverable (staged outputs + retained bucket
            pages, i.e. bucket > 0), NO task of the generation has
            flushed yet (a finished task already published its staged
            output and signaled done — re-running it would duplicate
            both), and retries remain."""
            fid = next((f for f, ds in instance_drivers.items()
                        if any(d is failed_driver for d in ds)), None)
            if fid is None or not recover:
                return False
            g = (lifespans_of[fid] - 1) - remaining_lifespans[fid] \
                if lifespans_of else 0
            if g <= 0:  # bucket 0 streamed unmaterialized
                return False
            if bucket_retries.get((fid, g), 0) >= 2:
                return False
            # a generation is retryable only while nothing PUBLISHED:
            # the staged SINK is the sole publisher — a finished build
            # pipeline (bridge feed) is fine, a flushed sink is not
            from presto_tpu.operators.exchange_ops import (
                ExchangeSinkOperator,
            )
            for d in instance_drivers[fid]:
                for op in d.operators:
                    if isinstance(op, ExchangeSinkOperator) \
                            and (op.is_finished() or not op.staged):
                        return False
            in_ex = [exchanges[x] for x in
                     fplan.fragments[fid].source_edges]
            if any(ex._retained is None for ex in in_ex):
                return False
            bucket_retries[(fid, g)] = \
                bucket_retries.get((fid, g), 0) + 1
            for ex in in_ex:
                ex.restore_lifespan()

            def abort(retiring):
                for dd in retiring:
                    dd.close()  # aborted: staged sinks publish nothing
            swap_generation(fid, abort)
            return True

        from presto_tpu.runner.local import check_lifecycle
        from presto_tpu.telemetry import ledger as _ledger
        rounds = 0
        while True:
            # the round's own work (lifecycle checks, deferred spawns,
            # lifespan advances: everything around the per-driver
            # frames) is named apart from the statement's root frame
            with _ledger.span("driver.quantum", detail="mesh_round"):
                # the same lifecycle checkpoints as the local drive loop:
                # kill and deadline both terminate within one round, even
                # mid-lifespan (retained bucket pages are dropped by the
                # caller's finally-close of every exchange)
                check_lifecycle(cancel, deadline)
                all_done = not deferred
                progress = False
                for d in list(all_drivers):
                    if d.is_finished():
                        continue
                    all_done = False
                    # per-DRIVER checkpoint, the same cadence the
                    # TaskExecutor gives every quantum: a mesh round walks
                    # (fragments x tasks) drivers and each process() may
                    # hide a multi-second XLA compile — a kill/deadline
                    # must land within one driver hand-off, not one round
                    check_lifecycle(cancel, deadline)
                    try:
                        with _ledger.device_scope(
                                getattr(d, "_mesh_device", None)):
                            with _ledger.span("driver.step"):
                                progress = d.process() or progress
                    except RetryableTaskError:
                        if not recover_generation(d):
                            raise
                        progress = True
                        break  # driver list mutated; restart the round
                if deferred and spawn_ready_deferred():
                    continue
                if all_done:
                    break
                if not progress:
                    advanced = False
                    for fid, left in remaining_lifespans.items():
                        in_exchanges = [
                            exchanges[x] for x in
                            fplan.fragments[fid].source_edges]
                        if left <= 0:
                            # LAST bucket of a recoverable fragment: once
                            # its drivers finish, drop the retained pages
                            # now instead of at query-end close()
                            if recover and fid not in deferred \
                                    and fid in instance_drivers \
                                    and all(d.is_finished() for d
                                            in instance_drivers[fid]):
                                for ex in in_exchanges:
                                    ex.commit_lifespan()
                            continue
                        if not all(d.is_finished()
                                   for d in instance_drivers[fid]):
                            continue
                        if not all(ex.lifespan_drained()
                                   for ex in in_exchanges):
                            continue
                        for ex in in_exchanges:
                            ex.commit_lifespan()  # bucket done: drop its
                            ex.advance_lifespan()  # retained pages
                        remaining_lifespans[fid] = left - 1
                        swap_generation(fid, retire)
                        advanced = True
                    if advanced:
                        continue
                rounds += 1
                if rounds > max_rounds:
                    raise QueryError("query did not converge (deadlock?)")
        retire(all_drivers)

    # ------------------------------------------------------------------

    def explain_text(self, sql: str) -> str:
        """Fragmented EXPLAIN (reference: planPrinter's fragment view)."""
        from presto_tpu.planner.optimizer import optimize
        plan = optimize(self.create_plan(sql), self.catalogs,
                        session=self.session)
        prune_unused_columns(plan)
        plan = add_exchanges(plan, self.catalogs, self.session)
        return fragment_plan(plan).text()
