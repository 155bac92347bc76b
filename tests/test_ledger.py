"""Wall-clock attribution ledger (telemetry/ledger.py): the coverage
invariant, the compile/dispatch/device_wait mutual-exclusion oracle,
and every surface the residual is served on (EXPLAIN ANALYZE,
system.runtime.queries, Prometheus)."""

import json
import time

import pytest


@pytest.fixture()
def runner():
    from presto_tpu.runner import LocalRunner
    return LocalRunner("tpch", "tiny")


def _mix_queries():
    import sys
    sys.path.insert(0, "/root/repo/tests")
    from tpch_queries import QUERIES
    return {n: QUERIES[n] for n in (1, 3, 6, 13)}


# ---------------------------------------------------------------------------
# unit: self-time nesting


def test_span_self_time_nesting():
    """A nested span's wall subtracts from its parent's SELF time, and
    leaf adds subtract from the enclosing frame — categories can never
    double-count within a thread."""
    from presto_tpu.telemetry import ledger
    led = ledger.QueryLedger()
    prev = ledger.install(led)
    try:
        t0 = time.perf_counter_ns()
        with ledger.span("driver"):
            time.sleep(0.01)
            with ledger.span("scan"):
                time.sleep(0.01)
            ledger.add("dispatch", 3_000_000)  # 3ms leaf
        wall = time.perf_counter_ns() - t0
    finally:
        ledger.uninstall(prev)
    snap = led.snapshot()
    assert snap["scan"] >= 9_000_000
    assert snap["dispatch"] == 3_000_000
    # driver got ONLY its self time: total minus scan minus the leaf
    assert snap["driver"] >= 9_000_000 - 3_000_000
    total = sum(snap.values())
    # no double counting: the categories sum to <= elapsed wall
    assert total <= wall + 1_000_000
    doc = led.finish(wall)
    ledger.verify_coverage(doc)
    assert doc["unattributed_ms"] >= -0.01


def test_kernel_wrapper_charges_its_inner_frames_once():
    """An instrumented host wrapper whose body opens frames of its own
    (a build's blocking fetch: `device_wait`, `d2h`) charges dispatch
    only with what those frames did not: the enclosing detailed
    frame's self time stays positive, so its detail is charged
    however long the fetch waits."""
    from presto_tpu.telemetry import kernels, ledger

    def host_wrapper():
        time.sleep(0.002)
        with ledger.span("device_wait"):
            time.sleep(0.03)

    wrapped = kernels.instrument_kernel(host_wrapper, "join_build")
    wrapped()       # the first call of a kernel object is its compile
    led = ledger.QueryLedger()
    prev = ledger.install(led)
    try:
        t0 = time.perf_counter_ns()
        with ledger.span("driver.step", detail="hash_build.finish"):
            wrapped()
        wall = time.perf_counter_ns() - t0
    finally:
        ledger.uninstall(prev)
    snap = led.snapshot()
    assert snap["device_wait"] >= 30_000_000
    assert 2_000_000 <= snap["dispatch"] < 30_000_000
    assert led.detail_ns[("driver.step", "hash_build.finish")] > 0
    assert sum(snap.values()) <= wall


def test_uninstalled_thread_is_noop():
    from presto_tpu.telemetry import ledger
    assert ledger.current() is None
    ledger.add("scan", 1_000_000)  # must not raise
    with ledger.span("driver"):
        pass


# ---------------------------------------------------------------------------
# oracle: cold compile / warm dispatch / device_wait are mutually
# exclusive (the async-dispatch undercount satellite)


def test_kernel_oracle_compile_dispatch_device_wait_exclusive():
    """A deterministic FakeJit: its first call grows the jit cache
    (compile), later calls don't (dispatch); a drain-point wait is a
    device_wait span. Each nanosecond lands in EXACTLY one category —
    the invariant holds with zero residual double-count."""
    from presto_tpu.telemetry import kernels as tk
    from presto_tpu.telemetry import ledger

    class FakeJit:
        def __init__(self):
            self.n = 0
            self.compile_next = True

        def _cache_size(self):
            return self.n

        def __call__(self, x):
            if self.compile_next:
                self.compile_next = False
                self.n += 1
                time.sleep(0.01)
            else:
                time.sleep(0.002)
            return x

    fake = FakeJit()
    wrapped = tk.instrument_kernel(fake, "ledger_oracle_fake",
                                   jits=[fake])
    led = ledger.QueryLedger()
    prev = ledger.install(led)
    try:
        t0 = time.perf_counter_ns()
        wrapped(1)            # cold: compile
        wrapped(2)            # warm: dispatch
        with ledger.span("device_wait"):
            time.sleep(0.005)  # drain-point wait
        wall = time.perf_counter_ns() - t0
    finally:
        ledger.uninstall(prev)
    snap = led.snapshot()
    assert snap["compile"] >= 9_000_000
    assert snap["dispatch"] >= 1_000_000
    assert snap["device_wait"] >= 4_000_000
    # mutual exclusion: compile's wall is NOT also in dispatch or
    # device_wait — the three sum to no more than elapsed wall
    assert snap["compile"] + snap["dispatch"] + snap["device_wait"] \
        <= wall
    doc = led.finish(wall)
    ledger.verify_coverage(doc)
    assert doc["unattributed_ms"] >= 0.0


# ---------------------------------------------------------------------------
# integration: the serving mix


def test_serving_mix_coverage_invariant(runner):
    """Every mix query's ledger must satisfy Σ categories +
    unattributed == wall with a small, NON-NEGATIVE residual — the
    machine check behind the <10% acceptance bar (asserted loosely
    here: tiny-schema walls are ms-scale, the bench asserts the real
    bar at sf0_1)."""
    from presto_tpu.telemetry.ledger import verify_coverage
    for name, sql in _mix_queries().items():
        res = runner.execute(sql)
        doc = res.query_stats["ledger"]
        verify_coverage(doc)
        assert doc["unattributed_ms"] >= -1.0, (name, doc)
        assert doc["unattributed_frac"] < 0.6, (name, doc)
        assert doc["categories_ms"], (name, doc)


def test_warm_run_has_dispatch_not_compile(runner):
    sql = "select count(*) from lineitem where quantity < 10"
    runner.execute(sql)
    warm = runner.execute(sql).query_stats["ledger"]
    assert warm["categories_ms"].get("compile", 0.0) == 0.0, warm
    assert warm["categories_ms"].get("dispatch", 0.0) > 0.0, warm


def test_explain_analyze_renders_attribution(runner):
    res = runner.execute(
        "explain analyze select count(*) from orders")
    text = "\n".join(r[0] for r in res.rows())
    assert "wall attribution" in text
    assert "unattributed" in text
    # every category line carries ms + percent columns
    assert "driver" in text


def test_system_runtime_queries_unattributed(runner):
    runner.execute("select count(*) from region")
    rows = runner.execute(
        "select query_id, state, unattributed_ms "
        "from system.runtime.queries order by query_id").rows()
    finished = [r for r in rows if r[1] == "FINISHED"]
    assert finished
    # a finished query's residual is a real (>= 0) measurement; the
    # observing in-flight query reports the -1 sentinel
    assert finished[0][2] >= 0.0
    assert rows[-1][1] == "RUNNING" and rows[-1][2] == -1.0


def test_ledger_metrics_and_histogram(runner):
    from presto_tpu.telemetry.metrics import METRICS
    before_ns = METRICS.total("presto_tpu_ledger_ns_total")
    h_before = METRICS.histogram_snapshot(
        "presto_tpu_ledger_unattributed_ratio")["count"]
    runner.execute("select count(*) from nation")
    assert METRICS.total("presto_tpu_ledger_ns_total") > before_ns
    h = METRICS.histogram_snapshot(
        "presto_tpu_ledger_unattributed_ratio")
    assert h["count"] == h_before + 1
    # render includes the histogram exposition triplet
    rendered = METRICS.render()
    assert "presto_tpu_ledger_unattributed_ratio_bucket" in rendered
    assert "presto_tpu_ledger_unattributed_ratio_count" in rendered


# ---------------------------------------------------------------------------
# the doctor


def test_query_doctor_verdicts():
    from presto_tpu.tools.query_doctor import diagnose

    def doc(cats, wall):
        unattr = wall - sum(cats.values())
        return {"wall_ms": wall, "categories_ms": cats,
                "unattributed_ms": unattr,
                "unattributed_frac": unattr / wall}

    assert diagnose(doc({"queued": 800.0, "dispatch": 50.0},
                        1000.0))["verdict"] == "queueing"
    assert diagnose(doc({"compile": 500.0, "device_wait": 200.0,
                         "scan": 100.0},
                        900.0))["verdict"] == "kernel"
    assert diagnose(doc({"serde": 300.0, "exchange": 300.0,
                         "dispatch": 100.0},
                        800.0))["verdict"] == "exchange"
    # unattributed residual counts as GLUE — host time nobody
    # attributed finer is host glue by definition
    assert diagnose(doc({"scan": 300.0, "driver": 200.0},
                        1000.0))["verdict"] == "glue"


def test_query_doctor_end_to_end(runner, tmp_path):
    from presto_tpu.tools import query_doctor
    res = runner.execute("select count(*) from customer")
    f = tmp_path / "stats.json"
    f.write_text(json.dumps({"stats": res.query_stats}))
    assert query_doctor.main(["--file", str(f)]) == 0
    assert query_doctor.main(["--file", str(f), "--json"]) == 0


# ---------------------------------------------------------------------------
# details: a frame may say WHO inside its category (PR 39)


class _Ticks:
    """A clock that advances 1 ms at every read: frames are then worth
    exact, repeatable nanoseconds."""

    def __init__(self):
        self.now = 0

    def perf_counter_ns(self):
        self.now += 1_000_000
        return self.now


def _nested(monkeypatch, inner_detail, outer_detail=None):
    """outer(category) { inner(category, inner_detail) { leaf } } on a
    ticking clock; returns the finished document."""
    from presto_tpu.telemetry import ledger
    monkeypatch.setattr(ledger, "time", _Ticks())
    led = ledger.QueryLedger()
    prev = ledger.install(led)
    try:
        with ledger.span("driver.step", detail=outer_detail):
            with ledger.span("driver.step", detail=inner_detail):
                ledger.add("dispatch", 250_000)
            with ledger.span("scan"):
                pass
    finally:
        ledger.uninstall(prev)
    return led.finish(10_000_000)


@pytest.mark.parametrize("inner, outer", [
    ("hash_build.add_input", None),
    ("hash_build.add_input", "mesh_round"),
    (None, "statement"),
], ids=["detail-in-plain", "detail-in-detail", "plain-in-detail"])
def test_detail_moves_no_time_between_categories(monkeypatch, inner,
                                                 outer):
    """A detailed frame nested in a plain one of its category leaves
    the category's total, every other category and the coverage
    invariant exactly as two plain frames leave them."""
    from presto_tpu.telemetry import ledger
    plain = _nested(monkeypatch, None)
    doc = _nested(monkeypatch, inner, outer)
    ledger.verify_coverage(doc)
    assert doc["categories_ms"] == plain["categories_ms"]
    assert doc["unattributed_ms"] == plain["unattributed_ms"]
    assert "details_ms" not in plain
    details = doc["details_ms"]
    assert set(details) == {"driver.step"}
    assert set(details["driver.step"]) == {inner, outer} - {None}
    # the inner frame: 1 ms between its two reads, less the leaf
    if inner is not None:
        assert details["driver.step"][inner] == 0.75
    assert sum(details["driver.step"].values()) \
        <= doc["categories_ms"]["driver.step"]


def test_details_sum_to_at_most_their_category_when_normalized():
    """Thread time past the wall is scaled onto it: the details are
    parts of their categories and shrink with them."""
    from presto_tpu.telemetry import ledger
    led = ledger.QueryLedger()
    led.charge("driver.step", 6_000_001, detail="a.add_input")
    led.charge("driver.step", 3_000_001, detail="b.get_output")
    led.charge("driver.step", 1_000_001)
    led.charge("dispatch", 10_000_003)
    doc = led.finish(5_000_000)
    ledger.verify_coverage(doc)
    assert doc["parallel_scale"] < 1
    for c, per in doc["details_ms"].items():
        assert sum(per.values()) <= doc["categories_ms"][c] + 1e-9
    assert doc["details_ms"]["driver.step"]["a.add_input"] \
        == pytest.approx(1.5, abs=0.002)


def test_detailed_frame_without_a_ledger_reads_no_clock(monkeypatch):
    from presto_tpu.telemetry import ledger

    class NoClock:
        def perf_counter_ns(self):
            raise AssertionError("span read the clock")

    def no_annotation(name, **meta):
        raise AssertionError("span opened a TraceAnnotation")
    assert ledger.current() is None
    monkeypatch.setattr(ledger, "time", NoClock())
    monkeypatch.setattr(ledger, "TraceAnnotation", no_annotation)
    with ledger.span("driver.step", detail="hash_build.add_input",
                     query_id="q1") as frame:
        assert frame is None


def test_frame_is_a_named_host_event_with_its_metadata(monkeypatch):
    """`ledger:<category>` without a detail, `ledger:<category>/
    <detail>` with one; `meta` goes to the annotation's keywords."""
    from presto_tpu.telemetry import ledger
    opened = []

    class Annotation:
        def __init__(self, name, **meta):
            opened.append((name, meta))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(ledger, "TraceAnnotation", Annotation)
    led = ledger.QueryLedger("q-17")
    prev = ledger.install(led)
    try:
        with ledger.span("driver.quantum", detail="statement",
                         query_id=led.query_id) as frame:
            with ledger.span("planning"):
                pass
    finally:
        ledger.uninstall(prev)
    assert opened == [
        ("ledger:driver.quantum/statement", {"query_id": "q-17"}),
        ("ledger:planning", {})]
    assert frame.elapsed_ns >= frame.nested_ns > 0
    # a ledger without a server's id still has one of its own
    assert ledger.QueryLedger().query_id != ledger.QueryLedger().query_id


def test_publish_feeds_every_family_once_a_document():
    """The one publishing function: categories, details, the residual
    and its ratio, from the document alone."""
    from presto_tpu.telemetry import ledger
    from presto_tpu.telemetry.metrics import METRICS
    detail = ('presto_tpu_ledger_detail_ns_total{category="driver.step",'
              'detail="unit_test.add_input"}')
    category = 'presto_tpu_ledger_ns_total{category="driver.step"}'
    before = METRICS.snapshot()
    h_before = METRICS.histogram_snapshot(
        "presto_tpu_ledger_unattributed_ratio")["count"]
    ledger.publish({
        "wall_ms": 10.0, "categories_ms": {"driver.step": 6.0},
        "details_ms": {"driver.step": {"unit_test.add_input": 4.0}},
        "unattributed_ms": 4.0, "unattributed_frac": 0.4})
    after = METRICS.snapshot()
    assert after[detail] - before.get(detail, 0) == 4_000_000
    assert after[category] - before.get(category, 0) == 6_000_000
    assert after["presto_tpu_ledger_unattributed_ns_total"] \
        - before.get("presto_tpu_ledger_unattributed_ns_total", 0) \
        == 4_000_000
    assert METRICS.histogram_snapshot(
        "presto_tpu_ledger_unattributed_ratio")["count"] == h_before + 1


def test_statement_details_reach_stats_and_explain_analyze(runner):
    """details_ms rides the document wherever the categories go: the
    statement's stats, and EXPLAIN ANALYZE's attribution section."""
    from presto_tpu.telemetry.ledger import verify_coverage
    sql = ("select returnflag, count(*) from lineitem "
           "group by returnflag")
    doc = runner.execute(sql).query_stats["ledger"]
    verify_coverage(doc)
    details = doc["details_ms"]
    assert "statement" in details["driver.quantum"]
    assert any(d.startswith("scan:lineitem.")
               for d in details["prefetch"]), details
    assert any(d.endswith(".add_input") for d in details["driver.step"])
    for c, per in details.items():
        # each a part of its category (every value rounded to 1 us)
        assert sum(per.values()) <= doc["categories_ms"][c] \
            + 0.001 * (len(per) + 1), (c, per)
    text = "\n".join(r[0] for r in runner.execute(
        "explain analyze " + sql).rows())
    assert "wall attribution" in text
    assert "    /" in text and ".add_input" in text
