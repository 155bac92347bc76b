"""Fault-tolerant fleet execution (server/scheduler.py): heartbeat
membership state machine, the task-output spool's exactly-once
contract, stage-level task retry with spooled-output REUSE, and the
cluster-wide fleet memory gate.

The recovery contract under test: a worker dying mid-query is a
bounded, observable, partially-retried event — only the dead worker's
unfinished tasks re-run (task counters prove it), every finished
task's spooled pages are reused, the result stays byte-identical to
the fault-free run, and the whole-query elastic retry tier NEVER
engages (QueryLifecycle.attempts == 1)."""

import json
import os
import signal
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from presto_tpu.execution import faults

SQL_AGG = ("select returnflag, count(*) c, sum(quantity) q "
           "from lineitem group by returnflag order by returnflag")
SQL_JOIN = ("select n.name, count(*) c from customer c "
            "join nation n on c.nationkey = n.nationkey "
            "group by n.name order by c desc, n.name limit 5")

#: the fault-tolerant session shape shared by the cluster tests: a
#: FIXED partition count (results must stay byte-identical across
#: membership changes) and a per-task retry budget
FT_PROPS = {"task_retries": 2, "task_partitions": 4}


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.disarm()


def _fleet_audit():
    from presto_tpu import sanitize
    return [str(v) for v in sanitize.audit(raise_=False,
                                           include=["fleet"])]


# ---------------------------------------------------------------------------
# heartbeat membership state machine (no real workers needed)


class _ToggleWorker(ThreadingHTTPServer):
    """A fake worker whose health the test flips: healthy probes get
    an active /v1/info with a memory report, unhealthy ones a 500."""

    healthy = True
    reserved = 12345


class _ToggleHandler(BaseHTTPRequestHandler):
    def log_message(self, *a):
        pass

    def do_GET(self):
        if not self.server.healthy:
            self.send_response(500)
            self.send_header("Content-Length", "2")
            self.end_headers()
            self.wfile.write(b"{}")
            return
        body = json.dumps({
            "state": "active", "devices": 1,
            "load": {"tasks_running": 0},
            "memory": {"reserved_bytes": self.server.reserved},
        }).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


@pytest.fixture()
def toggle_worker():
    srv = _ToggleWorker(("127.0.0.1", 0), _ToggleHandler)
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv, url
    srv.shutdown()


def test_heartbeat_membership_flap(toggle_worker):
    """down -> suspected -> removed -> re-admitted, deterministically
    via direct probe rounds (the loop thread is never started), with
    the memory report riding into the fleet enforcer and dropping on
    removal."""
    from presto_tpu.execution.cluster_memory import FleetMemoryEnforcer
    from presto_tpu.server.scheduler import HeartbeatMonitor
    srv, url = toggle_worker
    enforcer = FleetMemoryEnforcer(1 << 30)
    mon = HeartbeatMonitor([url], suspect_after=1, remove_after=3,
                           memory_sink=enforcer)
    mon.probe_now()
    snap = mon.snapshot()[0]
    assert snap["state"] == "active"
    assert snap["memory"]["reserved_bytes"] == 12345
    assert enforcer.snapshot() == {url: 12345}
    # one failed probe: SUSPECTED, still schedulable
    srv.healthy = False
    mon.probe_now()
    assert mon.snapshot()[0]["state"] == "suspected"
    assert mon.is_alive(url)
    # two more: REMOVED, memory report dropped
    mon.probe_now()
    mon.probe_now()
    snap = mon.snapshot()[0]
    assert snap["state"] == "removed"
    assert snap["consecutive_failures"] == 3
    assert not mon.is_alive(url)
    assert mon.alive() == []
    assert enforcer.snapshot() == {}
    assert mon.counts() == {"removed": 1}
    # recovery: graceful RE-ADMISSION with the flap counted
    srv.healthy = True
    mon.probe_now()
    snap = mon.snapshot()[0]
    assert snap["state"] == "active" and snap["flaps"] == 1
    assert mon.is_alive(url)
    # inline scheduler evidence accrues suspicion without a probe
    mon.report_failure(url)
    assert mon.snapshot()[0]["state"] == "suspected"


def test_heartbeat_fault_site(toggle_worker):
    """An armed worker.heartbeat fault counts as one failed probe —
    suspicion accrues exactly like a dropped /v1/info."""
    from presto_tpu.server.scheduler import HeartbeatMonitor
    _, url = toggle_worker
    mon = HeartbeatMonitor([url], suspect_after=1, remove_after=3)
    inj = faults.arm("worker.heartbeat", trigger="once")
    mon.probe_now()
    assert inj.fired == 1
    assert mon.snapshot()[0]["state"] == "suspected"
    mon.probe_now()  # the next real probe recovers
    assert mon.snapshot()[0]["state"] == "active"


# ---------------------------------------------------------------------------
# task-output spool: exactly-once + tiering + hygiene


def test_task_output_spool_exactly_once(tmp_path):
    from presto_tpu.server.scheduler import TaskOutputSpool
    spool = TaskOutputSpool(memory_budget_bytes=1 << 20)
    key = "q1:0"
    spool.put(key, 0, "q1.0.0", 1, 0, 0, b"page-a")
    spool.put(key, 0, "q1.0.0", 1, 0, 1, b"page-b")
    spool.put(key, 0, "q1.0.0", 1, 0, 1, b"page-b-dup")  # seq dedup
    # a racing second attempt streams the same logical pages
    spool.put(key, 0, "q1.0.0", 2, 0, 0, b"page-a2")
    # nothing visible before commit
    assert spool.pages_for(key, 0) == []
    assert spool.commit("q1.0.0", 1) is True
    assert spool.commit("q1.0.0", 2) is False  # first commit WINS
    pages = spool.pages_for(key, 0)
    assert [(p, s, b) for p, s, b in pages] == [
        (0, 0, b"page-a"), (0, 1, b"page-b")]
    # late stragglers of the losing attempt are dropped
    spool.put(key, 0, "q1.0.0", 2, 0, 1, b"late")
    assert len(spool.pages_for(key, 0)) == 2
    assert spool.committed_count("q1") == 1
    assert _fleet_audit() == []
    spool.release_query("q1")
    assert spool.pages_for(key, 0) == []
    assert spool.stats()["pages"] == 0 and spool.stats()["bytes"] == 0
    spool.close()


def test_task_output_spool_disk_tier_and_orphans():
    """Past the memory budget pages go to DISK through the serde
    path; release unlinks them (no orphan spool files — the fleet
    auditor's check)."""
    from presto_tpu.server.scheduler import TaskOutputSpool
    spool = TaskOutputSpool(memory_budget_bytes=8)  # force disk
    spool.put("q2:0", 0, "q2.0.0", 1, 0, 0, b"x" * 64)
    spool.put("q2:0", 0, "q2.0.0", 1, 0, 1, b"y" * 64)
    spool.commit("q2.0.0", 1)
    assert spool.stats()["disk_pages"] == 2
    assert spool._dir is not None and len(os.listdir(spool._dir)) == 2
    assert _fleet_audit() == []
    # spool.read fault site fires on read-back
    inj = faults.arm("spool.read", trigger="once")
    with pytest.raises(faults.InjectedFault):
        spool.pages_for("q2:0", 0)
    faults.disarm()
    assert inj.fired == 1
    assert [b for _, _, b in spool.pages_for("q2:0", 0)] \
        == [b"x" * 64, b"y" * 64]
    spool.release_query("q2")
    assert os.listdir(spool._dir) == []  # no orphan files
    assert _fleet_audit() == []
    spool.close()
    assert not os.path.exists(spool._dir or "/nonexistent")


def test_fleet_memory_enforcer_unit():
    from presto_tpu.execution.cluster_memory import (
        FleetMemoryEnforcer, FleetMemoryExceeded,
    )
    enf = FleetMemoryEnforcer(1000)
    enf.report("w1", 400)
    enf.report("w2", 500)
    enf.admit(100)  # exactly at budget: fine
    with pytest.raises(FleetMemoryExceeded) as ei:
        enf.admit(101)
    assert ei.value.kind == "cluster_memory"
    assert enf.sheds == 1
    enf.drop("w2")  # a removed member frees its reservation
    enf.admit(500)
    enf.report("w1", 2000)  # over budget even with nothing requested
    with pytest.raises(FleetMemoryExceeded):
        enf.admit()


# ---------------------------------------------------------------------------
# the fault-tolerant cluster (subprocess workers)


def _spawn_worker(extra_env=None, port=0):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": "",
           **(extra_env or {})}
    proc = subprocess.Popen(
        [sys.executable, "-m", "presto_tpu.server.node",
         "--port", str(port)],
        cwd="/root/repo", env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    url = json.loads(proc.stdout.readline())["url"]
    return proc, url


def _kill(proc, sig=signal.SIGTERM):
    try:
        proc.send_signal(sig)
        proc.wait(timeout=10)
    except Exception:  # noqa: BLE001 — already gone
        try:
            proc.kill()
        except Exception:  # noqa: BLE001
            pass


@pytest.fixture(scope="module")
def ft_cluster():
    from presto_tpu.server.coordinator import Coordinator
    procs = []
    urls = []
    for _ in range(2):
        p, u = _spawn_worker()
        procs.append(p)
        urls.append(u)
    coord = Coordinator(urls, "tpch", "tiny", dict(FT_PROPS),
                        heartbeat_interval_s=0.3)
    coord.start()
    coord.check_workers()
    yield coord, urls, procs
    coord.stop()
    for p in procs:
        _kill(p)


@pytest.fixture(scope="module")
def local_rows():
    from presto_tpu.runner import LocalRunner
    r = LocalRunner("tpch", "tiny")

    def run(sql):
        return r.execute(sql).rows()
    return run


def test_ft_byte_identity_and_exactly_once(ft_cluster, local_rows):
    """The scheduler path, fault-free: agg + broadcast-join queries
    come back byte-identical to the local reference, every task
    commits exactly once, and the fleet auditor is clean."""
    from presto_tpu.server.coordinator import QueryLifecycle
    coord, _, _ = ft_cluster
    for sql in (SQL_AGG, SQL_JOIN):
        lc = QueryLifecycle()
        res = coord.execute(sql, lifecycle=lc)
        assert res.rows() == local_rows(sql)
        assert lc.attempts == 1
        rep = res.task_report
        assert rep["retried"] == 0 and rep["workers_lost"] == 0
        assert rep["task_attempts"] == rep["tasks"]
    assert _fleet_audit() == []
    # end-of-query hygiene: the spool drained
    assert coord.task_spool.stats()["pages"] == 0


def test_ft_transient_status_poll_absorbed(ft_cluster, local_rows):
    """ONE dropped status poll is absorbed below the task-retry tier
    (the poll's own retry budget) — no task re-runs, no whole-query
    attempt burns."""
    from presto_tpu.server.coordinator import QueryLifecycle
    coord, _, _ = ft_cluster
    inj = faults.arm("task.status_poll", trigger="once")
    lc = QueryLifecycle()
    res = coord.execute(SQL_AGG, lifecycle=lc)
    assert inj.fired == 1, "fault never fired — test is vacuous"
    assert res.rows() == local_rows(SQL_AGG)
    assert lc.attempts == 1
    assert res.task_report["retried"] == 0


def test_ft_unreachable_worker_reschedules_and_reuses(ft_cluster,
                                                      local_rows):
    """Deterministic worker-loss recovery: once at least one task has
    COMMITTED, every status poll against worker 2 fails (the
    registry-based stand-in for an unreachable worker). The scheduler
    must declare it lost, reschedule ONLY its unfinished tasks onto
    the survivor, reuse the committed spooled outputs, and finish
    byte-identical on attempt ONE — task-level recovery, not a
    whole-query reset. First-commit-wins dedup guarantees the zombie
    attempts (the worker is actually alive) publish nothing."""
    from presto_tpu.server.coordinator import QueryLifecycle
    coord, urls, _ = ft_cluster
    spool = coord.task_spool

    def unreachable(ctx):
        return ctx.get("url") == urls[1] \
            and spool.committed_count() > 0
    inj = faults.arm("task.status_poll", trigger="always",
                     predicate=unreachable)
    lc = QueryLifecycle()
    res = coord.execute(SQL_AGG, lifecycle=lc)
    faults.disarm()
    assert inj.fired >= 3, "unreachable worker never simulated"
    assert res.rows() == local_rows(SQL_AGG)
    assert lc.attempts == 1, \
        "worker loss escalated to whole-query retry"
    rep = res.task_report
    assert rep["workers_lost"] == 1
    assert rep["retried"] >= 1, "lost tasks were not rescheduled"
    assert rep["reused_after_failure"] >= 1, \
        "committed spooled outputs were not reused"
    assert _fleet_audit() == []
    # membership saw the inline evidence
    assert any(w["url"] == urls[1]
               and w["consecutive_failures"] > 0
               for w in coord.membership.snapshot()) \
        or coord.membership.is_alive(urls[1])


def test_ft_spool_read_fault_retries_task(ft_cluster, local_rows):
    """An injected spool.read fault during a WORKER task's input
    replay fails that attempt only — the task retries and the query
    completes identically with attempts == 1. (The join's broadcast
    edge is distributed -> distributed, so worker tasks replay
    spooled pages; consumer slot > 0 keeps the root's own replay out
    of the blast radius.)"""
    from presto_tpu.server.coordinator import QueryLifecycle
    coord, _, _ = ft_cluster
    fired = []

    def worker_replay(ctx):
        if ctx.get("consumer", 0) > 0 and not fired:
            fired.append(ctx)
            return True
        return False
    inj = faults.arm("spool.read", trigger="always",
                     predicate=worker_replay)
    lc = QueryLifecycle()
    res = coord.execute(SQL_JOIN, lifecycle=lc)
    faults.disarm()
    assert inj.fired == 1, "spool.read never fired — test is vacuous"
    assert res.rows() == local_rows(SQL_JOIN)
    assert lc.attempts == 1
    assert res.task_report["retried"] >= 1
    assert _fleet_audit() == []


def test_ft_sigkill_worker_mid_query(local_rows):
    """THE chaos proof: a worker process SIGKILLed mid-phase. The
    query completes byte-identical to the fault-free run WITHOUT a
    whole-query restart — the task ledger proves finished tasks'
    spooled outputs were reused and only the dead worker's tasks
    re-ran."""
    from presto_tpu.server.coordinator import (
        Coordinator, QueryLifecycle,
    )
    w1, u1 = _spawn_worker()
    w2, u2 = _spawn_worker()
    coord = Coordinator(
        [u1, u2], "tpch", "tiny",
        {"task_retries": 2, "task_partitions": 6,
         # widen the mid-stage window so the kill deterministically
         # lands while tasks are still outstanding
         "task_dispatch_stagger_ms": 200},
        heartbeat_interval_s=0.3)
    try:
        coord.start()
        coord.check_workers()
        coord.execute(SQL_AGG)  # warm kernels: the kill run measures
        # recovery, not compile
        want = local_rows(SQL_AGG)
        lc = QueryLifecycle()
        out = {}

        def run():
            try:
                res = coord.execute(SQL_AGG, lifecycle=lc)
                out["rows"] = res.rows()
                out["report"] = res.task_report
            except Exception as e:  # noqa: BLE001 — recorded
                out["err"] = repr(e)
        t = threading.Thread(target=run)
        t.start()
        # barrier: at least one task committed => its spooled output
        # MUST be reused by the recovery
        deadline = time.monotonic() + 60
        while coord.task_spool.committed_count() == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert coord.task_spool.committed_count() > 0, \
            "no task committed before the kill — vacuous"
        _kill(w2, signal.SIGKILL)
        t.join(timeout=120)
        assert not t.is_alive(), "recovery hung"
        assert "err" not in out, out.get("err")
        assert out["rows"] == want  # byte-identical to fault-free
        assert lc.attempts == 1, \
            "worker death escalated to whole-query restart"
        rep = out["report"]
        assert rep["workers_lost"] >= 1
        assert rep["retried"] >= 1, "dead worker's tasks not re-run"
        assert rep["reused_after_failure"] >= 1, \
            "finished tasks' spooled outputs not reused"
        # only the lost tasks re-ran: attempts = tasks + retries
        assert rep["task_attempts"] == rep["tasks"] + rep["retried"]
        assert _fleet_audit() == []
        # the membership view converges on the death
        deadline = time.monotonic() + 10
        while coord.membership.is_alive(u2) \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not coord.membership.is_alive(u2)
    finally:
        coord.stop()
        _kill(w1)
        _kill(w2, signal.SIGKILL)


def test_ft_worker_rejoins_on_its_port_and_serves(local_rows):
    """A worker SIGKILLed between statements and started again on the
    same port: the statement sent while it is gone is answered by the
    other worker alone, the heartbeat re-admits the newcomer in place
    (a flap of that member, not a new one), and the next statement
    gives it tasks. All three answers equal the fault-free one."""
    from presto_tpu.server.coordinator import Coordinator
    from presto_tpu.server.node import http_get
    w1, u1 = _spawn_worker()
    w2, u2 = _spawn_worker()
    coord = Coordinator([u1, u2], "tpch", "tiny", dict(FT_PROPS),
                        heartbeat_interval_s=0.3)

    def member(url):
        return next(w for w in coord.membership.snapshot()
                    if w["url"] == url)

    def wait_for(holds, seconds, what):
        deadline = time.monotonic() + seconds
        while not holds() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert holds(), what

    try:
        coord.start()
        coord.check_workers()
        want = local_rows(SQL_AGG)
        assert coord.execute(SQL_AGG).rows() == want
        _kill(w2, signal.SIGKILL)
        wait_for(lambda: not coord.membership.is_alive(u2), 15,
                 "the dead worker was never removed")
        assert coord.execute(SQL_AGG).rows() == want
        w2, again = _spawn_worker(port=int(u2.rsplit(":", 1)[1]))
        assert again == u2
        wait_for(lambda: member(u2)["state"] == "active", 30,
                 "the restarted worker was never re-admitted")
        assert member(u2)["flaps"] >= 1
        assert len(coord.membership.snapshot()) == 2
        assert coord.execute(SQL_AGG).rows() == want
        # a process that has run no task has called no kernel
        assert "presto_tpu_kernel_calls_total" in http_get(
            f"{u2}/v1/metrics").decode()
        assert _fleet_audit() == []
    finally:
        coord.stop()
        _kill(w1)
        _kill(w2)


# ---------------------------------------------------------------------------
# fleet memory gate + distributed prewarm + degradation-tolerant probe


def test_fleet_memory_shed_structured(ft_cluster):
    """An over-budget fleet sheds at dispatch with the structured
    cluster_memory kind (never an OOM, never a retry burn)."""
    from presto_tpu.execution.cluster_memory import FleetMemoryExceeded
    from presto_tpu.server.coordinator import Coordinator
    _, urls, _ = ft_cluster
    coord = Coordinator(urls, "tpch", "tiny",
                        {"task_retries": 1, "fleet_memory_bytes": 1,
                         "query_memory_bytes": 10})
    try:
        with pytest.raises(FleetMemoryExceeded) as ei:
            coord.execute("select count(*) from region")
        assert ei.value.kind == "cluster_memory"
    finally:
        coord.httpd.server_close()
        coord.task_spool.close()


def test_distributed_prewarm(ft_cluster):
    """prewarm_sql on a WORKER topology fans out to every worker's
    /v1/prewarm (no more 'workers start cold'): the aggregate report
    carries per-worker compile counts and each worker's /v1/info
    serves its own."""
    from presto_tpu.server.node import http_get
    from presto_tpu.server.coordinator import Coordinator
    _, urls, _ = ft_cluster
    coord = Coordinator(urls, "tpch", "tiny",
                        prewarm_sql=["select count(*) from region"])
    try:
        coord.start()
        rep = coord.prewarm_report
        assert rep["statements"] == 1 and rep["failed"] == []
        assert set(rep["workers"]) == set(urls)
        for url in urls:
            assert rep["workers"][url]["statements"] == 1
            info = json.loads(http_get(f"{url}/v1/info"))
            assert info["prewarm"]["statements"] == 1
            assert info["prewarm"]["failed"] == []
    finally:
        coord.stop()


def test_check_workers_concurrent_degradation(ft_cluster):
    """check_workers probes concurrently and starts with the live
    majority: dead members are REPORTED, not fatal — unless nobody
    is active at all."""
    from presto_tpu.server.coordinator import Coordinator
    _, urls, _ = ft_cluster
    bogus = "http://127.0.0.1:1"
    coord = Coordinator([urls[0], bogus], "tpch", "tiny")
    try:
        report = coord.check_workers(timeout=3)
        assert report[urls[0]] == "active"
        assert report[bogus].startswith("unreachable")
        with pytest.raises(RuntimeError, match="not active"):
            coord.check_workers(require_all=True, timeout=3)
    finally:
        coord.httpd.server_close()
        coord.task_spool.close()
    dead_only = Coordinator([bogus], "tpch", "tiny")
    try:
        with pytest.raises(RuntimeError, match="no active workers"):
            dead_only.check_workers(timeout=3)
    finally:
        dead_only.httpd.server_close()
        dead_only.task_spool.close()


def test_coordinator_info_serves_membership(ft_cluster):
    """GET /v1/info on the coordinator exposes the live membership
    view, spool stats, and per-worker load/memory feedback."""
    from presto_tpu.server.node import http_get
    coord, urls, _ = ft_cluster
    # let at least one heartbeat round land
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        info = json.loads(http_get(f"{coord.url}/v1/info"))
        if all(w.get("last_error") is None
               and w["state"] == "active"
               for w in info.get("workers", [])) \
                and len(info.get("workers", [])) == 2:
            break
        time.sleep(0.1)
    assert info["membership"] == {"active": 2}
    assert {w["url"] for w in info["workers"]} == set(urls)
    for w in info["workers"]:
        assert "memory" in w and "load" in w
    assert "spool" in info
