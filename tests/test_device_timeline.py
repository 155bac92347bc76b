"""The device's time has names (PR 26): every device program is jitted
through telemetry.kernels.jit under its kernel family's name, the
ledger's spans and the kernel wrapper's calls are host events on
jax.profiler's clock, every XLA compile is counted by family, and the
protocol layer is timed from inside."""

import glob
import importlib
import os

import jax
import jax.numpy as jnp
import pytest

from presto_tpu.telemetry import kernels, ledger
from presto_tpu.telemetry.metrics import METRICS

_QUERIES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "queries")

#: device name -> family: the whole naming table. A new program is a
#: new line here (test_registry_holds_only_documented_names).
DEVICE_NAMES = {
    "pad": "pad", "compact": "compact", "compact_shrink": "compact",
    "sort": "sort", "topn": "topn", "limit": "limit",
    "distinct": "distinct", "window": "window", "merge": "merge",
    "join_build_sorted": "join_build", "join_build_hash": "join_build",
    "join_build_apply_perm": "join_build",
    "join_build_direct": "join_build", "join_build_stats": "join_build",
    "join_probe_direct": "join_probe",
    "join_probe": "join_probe", "join_probe_sorted": "join_probe",
    "join_probe_stage1": "join_probe",
    "join_probe_stage2": "join_probe",
    "join_probe_materialize": "join_probe",
    "join_probe_hash": "join_probe", "join_probe_search": "join_probe",
    "join_probe_counts": "join_probe",
    "join_probe_expand": "join_probe",
    "join_probe_expand_general": "join_probe",
    "join_probe_fused": "join_probe",
    "join_outer": "join_outer",
    "semi_join_unique": "semi_join", "semi_join_resolve": "semi_join",
    "semi_join_fused": "semi_join", "semi_join_scan": "semi_join",
    "filter_project": "filter_project",
    "fragment_chain": "fragment", "fragment_limit": "fragment",
    "fragment_topn": "fragment", "fragment_distinct": "fragment",
    "fragment_agg_step": "fragment",
    "fragment_join_probe": "fragment",
    "fragment_join_probe_sorted": "fragment",
    "fragment_join_probe_stage0": "fragment",
    "fragment_join_probe_stage0_direct": "fragment",
    "fragment_join_probe_stage1": "fragment",
    "fragment_join_probe_stage2": "fragment",
    "agg_step": "agg_step", "agg_step_presorted": "agg_step",
    "agg_finalize": "agg_finalize",
    "agg_count": "agg_count", "agg_shrink": "agg_shrink",
    "agg_stream": "agg_stream", "hashagg_merge": "hashagg_merge",
    "array_agg_collect": "array_agg", "array_agg_eval": "array_agg",
    "exchange_partition": "exchange_partition",
    "nested_loop": "nested_loop",
    "dynamic_filter_bounds": "dynamic_filter",
    "dynamic_filter_distinct_set": "dynamic_filter",
    "dynamic_filter_apply": "dynamic_filter",
    "spmd_shuffle": "spmd_shuffle", "spmd_fragment": "spmd_fragment",
}

#: eager jnp ops on the served path: device programs of their own that
#: no kernel family jits. Found, not changed (the inventory with call
#: sites is PERF.md section 7); a new one here is a new dispatch per
#: batch and belongs in a kernel.
EAGER_OPS = {"_reduce_sum", "add", "convert_element_type",
             "broadcast_in_dim", "bitwise_and"}

#: module-level jits that a second family's host wrapper also calls:
#: named once, under the family that owns them
SHARED = {"join_probe_hash", "join_probe_search"}

#: the ledger's detailed host names, `ledger:<category>/<detail>`
#: (PR 39): a detail is a word of this table, or, under `driver.step`
#: and `prefetch`, `<operator kind>.<method>` with the kind one of
#: the plan's operators. A new detail is a new line here
#: (test_every_detailed_name_is_in_the_table).
LEDGER_DETAILS = {
    "driver.quantum": {"statement", "executor", "mesh_round"},
    "exchange.all_to_all": {"assemble", "dispatch", "sync", "slice"},
}
HANDOFF_METHODS = {
    "driver.step": {"get_output", "add_input", "finish"},
    "prefetch": {"get_output"},
}

_NO_RESULT_REPLAY = {"fragment_result_cache_enabled": False}


def _sql(name):
    with open(os.path.join(_QUERIES, f"{name}.sql")) as f:
        return f.read()


def _host_events(log_dir, meta=False):
    """{thread: [(start_ns, end_ns, name)]} of the host's planes; with
    `meta`, each event's metadata as a fourth member."""
    path = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    threads = {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):  # names repeat
            events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      + ((dict(e.stats),) if meta else ())
                      for e in line.events]
            if events:
                threads[(plane.name, i, line.name)] = sorted(
                    events, key=lambda ev: ev[:3])
    return threads


@pytest.fixture(scope="module")
def warm_trace(tmp_path_factory):
    """Q1, Q3 and Q6 at tiny scale through LocalRunner: three
    executions to reach the steady state (the history-based optimizer
    re-plans after the first), then one of each under jax.profiler."""
    from presto_tpu.runner import LocalRunner
    runner = LocalRunner("tpch", "tiny", properties=_NO_RESULT_REPLAY)
    sqls = [_sql(q) for q in ("q1", "q3", "q6")]
    for sql in sqls:
        for _ in range(3):
            runner.execute(sql)
    log_dir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        for sql in sqls:
            runner.execute(sql)
    finally:
        jax.profiler.stop_trace()
    return _host_events(log_dir)


def _programs(threads):
    for thread, events in threads.items():
        for s, e, name in events:
            if name.startswith("PjitFunction("):
                yield thread, s, e, kernels.device_name_of(name)


def test_every_device_program_has_a_family(warm_trace):
    names = {name for _, _, _, name in _programs(warm_trace)}
    assert names, "the profiler recorded no PjitFunction host event"
    assert not names & {"kernel", "fn", "body", "fin", "<lambda>",
                        "stage0", "stage2", "named"}
    unnamed = {n for n in names if kernels.family_of_module(n) is None}
    assert unnamed <= EAGER_OPS, sorted(unnamed - EAGER_OPS)
    families = {kernels.family_of_module(n) for n in names - unnamed}
    # Q3 joins, Q1/Q6 aggregate, every scan pads to its bucket
    assert {"fragment", "join_build", "agg_finalize"} <= families


def test_ledger_and_kernel_spans_share_the_profilers_clock(warm_trace):
    seen = {name for events in warm_trace.values()
            for _, _, name in events}
    for category in ("planning", "driver.step", "scan"):
        assert f"ledger:{category}" in seen, sorted(
            n for n in seen if n.startswith("ledger:"))
    # `dispatch` and `compile` are leaf charges of the kernel wrapper
    # (ledger.add_kernel), not frames: their host events are the
    # wrapper's own kernel:<family> / compile:<family> spans
    assert "ledger:dispatch" not in seen
    assert any(n.startswith("kernel:") for n in seen)
    assert not any(n.startswith("bench:") for n in seen)
    checked = 0
    for thread, s, e, name in _programs(warm_trace):
        family = kernels.family_of_module(name)
        if family is None:
            continue
        around = [(e2 - s2, n2) for s2, e2, n2 in warm_trace[thread]
                  if s2 <= s and e2 >= e
                  and n2.startswith(("kernel:", "compile:"))]
        assert around, f"{name} ran outside any kernel span"
        innermost = min(around)[1].split(":", 1)[1]
        if name not in SHARED:
            assert innermost == family, (name, innermost)
        checked += 1
    assert checked


def test_registry_holds_only_documented_names(warm_trace):
    from presto_tpu.analysis.contracts import CONTRACT_MODULES
    for module in CONTRACT_MODULES:
        importlib.import_module(module)
    registered = kernels.device_names()
    undocumented = {n: f for n, f in registered.items()
                    if DEVICE_NAMES.get(n) != f}
    assert not undocumented, undocumented
    # one device name, one family: `join` + `build_sorted` would take
    # the name that `join_build` + `sorted` owns
    with pytest.raises(ValueError):
        kernels.jit(lambda x: x, "join", "build_sorted")


@pytest.mark.parametrize("name", sorted(DEVICE_NAMES))
def test_device_name_is_the_xla_module_name(name):
    family = DEVICE_NAMES[name]
    part = name[len(family) + 1:] or None
    assert name == (family if part is None else f"{family}_{part}")
    fn = kernels.jit(lambda x, n: x * n, family, part,
                     static_argnums=(1,))
    text = fn.lower(jnp.ones(4), 3).as_text()
    assert text.startswith(f"module @jit_{name} "), text[:80]
    for form in (f"jit_{name}(123456)", f"jit_{name}", f"jit({name})",
                 f"PjitFunction({name})", name):
        assert kernels.family_of_module(form) == family, form


def _xla_compiles():
    return {k: v for k, v in METRICS.snapshot().items()
            if k.startswith("presto_tpu_xla_compiles_total")}


def test_xla_compiles_counted_by_family_cold_not_warm():
    from presto_tpu.runner import LocalRunner
    runner = LocalRunner("tpch", "tiny", properties=_NO_RESULT_REPLAY)
    # an expression no other test compiles: its fused scan-aggregate
    # program is new to this process whatever ran before
    sql = ("select sum(extendedprice * discount * 1.0625 + 0.03125) "
           "from lineitem where quantity < 23.625")
    before = _xla_compiles()
    kernel_before = METRICS.total("presto_tpu_kernel_compiles_total")
    runner.execute(sql)
    cold = _xla_compiles()
    grew = {k for k, v in cold.items() if v > before.get(k, 0)}
    assert grew and grew != {
        'presto_tpu_xla_compiles_total{family="(unnamed)"}'}, grew
    # the complete count is never under the wrapper's heuristic one
    assert sum(cold.values()) - sum(before.values()) >= \
        METRICS.total("presto_tpu_kernel_compiles_total") \
        - kernel_before
    for _ in range(3):  # the history-based optimizer may re-plan
        runner.execute(sql)
    steady = _xla_compiles()
    seconds = METRICS.total("presto_tpu_xla_compile_seconds_total")
    runner.execute(sql)
    assert _xla_compiles() == steady
    assert METRICS.total(
        "presto_tpu_xla_compile_seconds_total") == seconds > 0


def test_eager_op_compiles_as_unnamed():
    key = 'presto_tpu_xla_compiles_total{family="(unnamed)"}'
    before = METRICS.snapshot().get(key, 0)
    (jnp.ones((7, 13)) * 3).block_until_ready()   # its own program
    assert METRICS.snapshot().get(key, 0) > before


def test_protocol_phases_and_served_ms_over_http():
    from presto_tpu.server.coordinator import (
        Coordinator, StatementClient,
    )

    def phases():
        snap = METRICS.snapshot()
        return {p: snap.get(
            f'presto_tpu_protocol_ns_total{{phase="{p}"}}', 0)
            for p in ("accept", "result_wait", "encode")}
    coord = Coordinator([], "tpch", "tiny", single_node=True)
    coord.start()
    try:
        before = phases()
        _, rows = StatementClient(coord.url).execute(
            "select count(*) from nation")
        assert rows == [[25]]
        after = phases()
        (q,) = coord.queries.values()
    finally:
        coord.stop()
    assert after["accept"] > before["accept"]
    assert after["encode"] > before["encode"]
    assert after["result_wait"] >= before["result_wait"]
    assert q.served_at is not None and q.stats["served_ms"] > 0
    # the answer is handed over after it is done: the program's own
    # client sleeps 0.1 s between polls
    assert q.stats["served_ms"] >= q.stats["wall_ms"]
    if q.done_at <= q.served_at:
        assert after["result_wait"] > before["result_wait"]


def test_span_without_a_ledger_reads_no_clock(monkeypatch):
    class NoClock:
        def perf_counter_ns(self):
            raise AssertionError("span read the clock")

    def no_annotation(name):
        raise AssertionError("span opened a TraceAnnotation")
    assert ledger.current() is None
    monkeypatch.setattr(ledger, "time", NoClock())
    monkeypatch.setattr(ledger, "TraceAnnotation", no_annotation)
    with ledger.span("scan"):
        pass
    ledger.add("dispatch", 5)


# ---------------------------------------------------------------------------
# operator-level detail under the ledger's frames (PR 39): the same
# names through the batch pump, the pair loop and the mesh's loop


def _traced(runner, sqls, tmp_path_factory):
    """(host events by thread with each event's metadata, operator
    kinds of the plans) of one warm execution of each statement."""
    for sql in sqls:
        for _ in range(3):
            runner.execute(sql)
    log_dir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    kinds = set()
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        for sql in sqls:
            stats = runner.execute(sql).query_stats
            kinds |= {op["name"] for task in stats["tasks"]
                      for pipeline in task["pipelines"]
                      for op in pipeline}
    finally:
        jax.profiler.stop_trace()
    threads = {
        thread: [ev for ev in events if ev[2].startswith("ledger:")]
        for thread, events in _host_events(log_dir, meta=True).items()}
    return threads, kinds


@pytest.fixture(scope="module", params=["pump", "pair", "mesh"])
def loop_trace(request, tmp_path_factory):
    """Q3 and Q6 under jax.profiler through each of the three drive
    loops: the executor's batch pump, its pair loop (the pump's switch
    off), and MeshRunner's round over four of the host's devices."""
    from presto_tpu.operators import driver as driver_mod
    from presto_tpu.runner import LocalRunner, runner_for
    sqls = [_sql("q3"), _sql("q6")]
    prev = driver_mod.pump_enabled()
    driver_mod.set_pump(request.param != "pair")
    try:
        if request.param == "mesh":
            runner = runner_for("tpch", "tiny", {
                **_NO_RESULT_REPLAY, "mesh_devices": 4})
        else:
            runner = LocalRunner("tpch", "tiny",
                                 properties=_NO_RESULT_REPLAY)
        threads, kinds = _traced(runner, sqls, tmp_path_factory)
    finally:
        driver_mod.set_pump(prev)
    return request.param, threads, kinds


def _names(threads):
    return {name for events in threads.values()
            for _, _, name, _ in events}


def test_every_operator_kind_has_its_handoff_on_the_timeline(loop_trace):
    loop, threads, kinds = loop_trace
    assert {"hash_build", "scan:lineitem", "output"} <= kinds
    seen = _names(threads)
    for kind in kinds:
        mine = {n for n in seen if n.startswith(
            (f"ledger:driver.step/{kind}.", f"ledger:prefetch/{kind}."))}
        assert mine, (loop, kind, sorted(seen))
    # a source is pulled under `prefetch` by the pump alone
    pulled = {n for n in seen if n.startswith("ledger:prefetch/")}
    assert bool(pulled) == (loop == "pump"), pulled
    assert "ledger:driver.step/hash_build.add_input" in seen
    assert "ledger:driver.step/hash_build.finish" in seen


def test_every_detailed_name_is_in_the_table(loop_trace):
    loop, threads, kinds = loop_trace
    detailed = {n for n in _names(threads) if "/" in n}
    assert detailed
    for name in detailed:
        category, detail = name[len("ledger:"):].split("/", 1)
        if category in LEDGER_DETAILS:
            assert detail in LEDGER_DETAILS[category], name
        else:
            kind, method = detail.rsplit(".", 1)
            assert method in HANDOFF_METHODS[category], name
            assert kind in kinds, name
    # what the bare names keep: the loops' own frames
    assert "ledger:driver.step" in _names(threads)
    assert "ledger:driver.quantum" not in _names(threads)


def test_quantum_frames_are_three_names_with_the_query_id(loop_trace):
    loop, threads, _ = loop_trace
    roots = [ev for events in threads.values() for ev in events
             if ev[2] == "ledger:driver.quantum/statement"]
    assert len(roots) == 2                  # Q3 and Q6
    ids = [meta.get("query_id") for _, _, _, meta in roots]
    assert all(ids) and len(set(ids)) == 2, ids
    seen = _names(threads)
    if loop == "mesh":
        assert "ledger:driver.quantum/mesh_round" in seen
    else:
        # an executor worker's quantum names the statement it serves
        quanta = [ev for events in threads.values() for ev in events
                  if ev[2] == "ledger:driver.quantum/executor"]
        assert quanta
        assert {meta.get("query_id") for _, _, _, meta in quanta} \
            == set(ids)
        for start, end, _, meta in quanta:
            (root,) = [r for r in roots
                       if r[3]["query_id"] == meta["query_id"]]
            assert root[0] <= start and end <= root[1]


def test_handoffs_nest_in_the_loops_frame(loop_trace):
    """A hand-off is opened inside a loop's plain `driver.step` frame
    (process_quantum's, or the mesh round's per-driver one): same
    category, so the detail moves time inside it and nowhere else."""
    loop, threads, _ = loop_trace
    checked = 0
    for events in threads.values():
        plain = [(s, e) for s, e, n, _ in events
                 if n == "ledger:driver.step"]
        for s, e, name, _ in events:
            if name.startswith("ledger:driver.step/"):
                assert any(ps <= s and e <= pe for ps, pe in plain), name
                checked += 1
    assert checked


def test_wave_phases_are_frames_of_the_exchange(loop_trace):
    loop, threads, _ = loop_trace
    seen = _names(threads)
    phases = {f"ledger:exchange.all_to_all/{p}"
              for p in LEDGER_DETAILS["exchange.all_to_all"]}
    if loop != "mesh":
        assert not phases & seen
        return
    assert phases <= seen, sorted(seen)
    for events in threads.values():
        waves = [(s, e) for s, e, n, _ in events
                 if n == "ledger:exchange.all_to_all"]
        for s, e, name, _ in events:
            if name in phases:
                assert any(ws <= s and e <= we for ws, we in waves)
