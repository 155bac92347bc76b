import pytest
from presto_tpu.execution.access_control import (
    AccessControlManager, AccessRule,
)

def test_access_control():
    from presto_tpu.runner import LocalRunner
    from presto_tpu.runner.local import QueryError
    ac = AccessControlManager([
        AccessRule(user="intern", table="orders",
                   allow_select=False, allow_write=False),
        AccessRule(user="intern", catalog="memory",
                   allow_select=True, allow_write=False),
    ])
    r = LocalRunner("tpch", "tiny", user="intern", access_control=ac)
    # unmatched tables default-allow
    assert r.execute("select count(*) from nation").rows() == [(25,)]
    with pytest.raises(QueryError, match="cannot select"):
        r.execute("select count(*) from orders")
    with pytest.raises(QueryError, match="cannot write"):
        r.execute("create table memory.default.x as select 1 a")
    # another user is unaffected
    r2 = LocalRunner("tpch", "tiny", user="admin", access_control=ac)
    assert r2.execute("select count(*) from orders").rows()[0][0] > 0


def test_coordinator_enforces_identity():
    """The X-Presto-User identity gates access at the coordinator,
    where analysis runs (workers only execute authorized fragments)."""
    import json, os, signal, subprocess, sys
    from presto_tpu.server.coordinator import Coordinator, StatementClient
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    proc = subprocess.Popen(
        [sys.executable, "-m", "presto_tpu.server.node", "--port", "0"],
        cwd="/root/repo", env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    url = json.loads(proc.stdout.readline())["url"]
    ac = AccessControlManager([
        AccessRule(user="intern", table="orders", allow_select=False)])
    c = Coordinator([url], "tpch", "tiny", access_control=ac)
    c.start()
    try:
        _, rows = StatementClient(c.url, user="intern").execute(
            "select count(*) from nation")
        assert rows == [[25]]
        with pytest.raises(RuntimeError, match="cannot select"):
            StatementClient(c.url, user="intern").execute(
                "select count(*) from orders")
        _, rows = StatementClient(c.url, user="analyst").execute(
            "select count(*) from orders")
        assert rows[0][0] > 0
    finally:
        c.stop()
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_single_node_coordinator_enforces_per_user_access():
    """The shared single-node runner must evaluate access control as
    the REQUESTING user (X-Presto-User), not the runner's default
    identity — and the plan cache must not leak an allowed user's
    plan to a denied one."""
    from presto_tpu.cache import reset_cache_manager
    from presto_tpu.server.coordinator import (
        Coordinator, StatementClient,
    )
    reset_cache_manager()
    ac = AccessControlManager([
        AccessRule(user="intruder", table="nation",
                   allow_select=False),
        AccessRule(),
    ])
    coord = Coordinator([], "tpch", "tiny", single_node=True,
                        access_control=ac)
    coord.start()
    try:
        sql = "select count(*) from nation"
        ok = StatementClient(coord.url, user="analyst")
        assert ok.execute(sql)[1] == [[25]]
        assert ok.execute(sql)[1] == [[25]]  # warm the plan cache
        denied = StatementClient(coord.url, user="intruder")
        with pytest.raises(RuntimeError, match="cannot select"):
            denied.execute(sql)
    finally:
        coord.stop()
    reset_cache_manager()
