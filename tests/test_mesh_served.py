"""The four-worker deployment on the served path: a single-node
Coordinator whose properties carry `mesh_devices` answers over
POST /v1/statement through a MeshRunner on the first N devices (the
suite's 8 virtual CPU devices stand for the chips), one statement's
collectives at a time, its scans resident on the chip that reads them.

Every statement is sent with a client timeout of its own
(STATEMENT_TIMEOUT_S), and every thread is joined with one."""

import os
import sys
import threading

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))

import baseline_proxy  # noqa: E402
import chip_smoke  # noqa: E402 — the reference's row shapes
from tpch_queries import QUERIES  # noqa: E402

from presto_tpu.cache import get_cache_manager, reset_cache_manager
from presto_tpu.runner import LocalRunner, MeshRunner, runner_for
from presto_tpu.runner.local import QueryError
from presto_tpu.server.coordinator import Coordinator, StatementClient
from presto_tpu.telemetry.metrics import METRICS

SCHEMA = "tiny"
STATEMENT_TIMEOUT_S = 300.0
RTOL = 1e-9
PROPS = {"fragment_result_cache_enabled": False}
TRANSFER = "presto_tpu_transfer_bytes_total"


def _moved() -> float:
    return METRICS.get(TRANSFER, direction="h2d") \
        + METRICS.get(TRANSFER, direction="d2d")


def _assert_rows(got, want, what):
    """Equal as sets of rows; floats within RTOL of the reference's."""
    def exact(r):
        return tuple(str(v) for v in r if not isinstance(v, float))
    got = sorted(map(tuple, got), key=exact)
    want = sorted(map(tuple, want), key=exact)
    assert len(got) == len(want), what
    for g, w in zip(got, want):
        assert len(g) == len(w), (what, g, w)
        for gv, wv in zip(g, w):
            if isinstance(wv, float):
                assert abs(gv - wv) <= RTOL * abs(wv), (what, g, w)
            else:
                assert gv == wv, (what, g, w)


@pytest.fixture(scope="module")
def served():
    coord = Coordinator([], "tpch", SCHEMA, single_node=True,
                        properties={**PROPS, "mesh_devices": 4})
    coord.start()
    try:
        yield coord, StatementClient(coord.url, user="mesh-test")
    finally:
        coord.stop()


@pytest.fixture(scope="module")
def local():
    return LocalRunner("tpch", SCHEMA, dict(PROPS))


@pytest.fixture(scope="module")
def reference(local):
    gen = local.catalogs.connector("tpch")._gens[SCHEMA]
    tables = baseline_proxy.load_tables(gen, baseline_proxy.TABLES)
    return chip_smoke.reference_rows(gen, tables)


def _ask(client, q):
    columns, data = client.execute(QUERIES[q],
                                   timeout=STATEMENT_TIMEOUT_S)
    return chip_smoke._engine_rows(columns, data)


@pytest.mark.parametrize("q", [1, 3, 6])
def test_served_mesh_answers_as_one_chip_and_the_reference(
        q, served, local, reference):
    got = _ask(served[1], q)
    _assert_rows(got, local.execute(QUERIES[q]).rows(),
                 f"q{q} mesh vs LocalRunner")
    _assert_rows(got, reference[q], f"q{q} mesh vs Acero")


def test_the_coordinators_runner_is_a_mesh_over_the_first_devices(served):
    runner = served[0]._runner()
    assert isinstance(runner, MeshRunner)
    assert runner._devices == jax.devices()[:4]


@pytest.mark.parametrize("props", [{}, {"mesh_devices": 1}],
                         ids=["absent", "one"])
def test_without_a_mesh_the_runner_is_the_local_one(props):
    coord = Coordinator([], "tpch", SCHEMA, single_node=True,
                        properties=props)
    assert type(coord._runner()) is LocalRunner
    assert type(runner_for("tpch", SCHEMA, props)) is LocalRunner


def test_more_chips_than_are_visible_fails_at_start():
    coord = Coordinator([], "tpch", SCHEMA, single_node=True,
                        properties={"mesh_devices": 16})
    with pytest.raises(ValueError, match=r"16\b.*\b8\b"):
        coord.start()
    assert coord._embedded_runner is None


@pytest.mark.parametrize("statement", [
    "set session mesh_devices = 2", "reset session mesh_devices"])
def test_a_statement_cannot_change_the_layout(statement, local):
    with pytest.raises(QueryError, match="deployment's layout"):
        local.execute(statement)
    assert "mesh_devices" not in local.session.properties
    listing = [r[0] for r in local.execute("show session").rows()]
    assert any(r.startswith("mesh_devices=1") for r in listing)


def test_q3_grows_the_exchange_and_mesh_counters(served):
    names = ["presto_tpu_exchange_all_to_all_waves_total",
             "presto_tpu_exchange_all_to_all_rows_total"]
    before = [METRICS.total(n) for n in names]
    ok = METRICS.get("presto_tpu_mesh_queries_total", status="ok")
    _ask(served[1], 3)
    assert all(METRICS.total(n) > b for n, b in zip(names, before))
    assert METRICS.get("presto_tpu_mesh_queries_total",
                       status="ok") == ok + 1


def test_a_warm_scan_moves_no_byte_and_lives_where_it_is_read():
    # sf0_01 at 16k rows a batch: two splits a table, so chips 0 and 1
    # each read (and keep) one
    runner = runner_for("tpch", "sf0_01", {
        **PROPS, "mesh_devices": 4, "batch_rows": 16384})
    first = runner.execute(QUERIES[6]).rows()
    page = get_cache_manager().page
    hits, moved = page.stats.hits, _moved()
    assert runner.execute(QUERIES[6]).rows() == first
    assert page.stats.hits > hits and _moved() == moved
    homes = {}
    for key, entry in list(page._entries.items()):
        if key[3:5] != ("sf0_01", "lineitem"):
            continue
        keyed = key[-1][1] if isinstance(key[-1], tuple) \
            and key[-1][:1] == ("device",) else jax.devices()[0].id
        for batch in entry.value:
            for leaf in jax.tree_util.tree_leaves(batch):
                assert {d.id for d in leaf.devices()} == {keyed}, key
        homes[keyed] = homes.get(keyed, 0) + 1
    assert len(homes) >= 2, homes


def test_two_statements_at_once_take_the_mesh_in_turn(
        served, reference, monkeypatch):
    coord, client = served
    runner = coord._runner()
    inside, most = [0], [0]
    guard = threading.Lock()
    real = runner._run_fragments

    def watched(*args, **kw):
        with guard:
            inside[0] += 1
            most[0] = max(most[0], inside[0])
        try:
            return real(*args, **kw)
        finally:
            with guard:
                inside[0] -= 1

    monkeypatch.setattr(runner, "_run_fragments", watched)
    waited = METRICS.total("presto_tpu_mesh_lock_wait_ns_total")
    answers = {}

    def send(q):
        answers[q] = _ask(client, q)

    threads = [threading.Thread(target=send, args=(q,), daemon=True)
               for q in (3, 6)]
    # the mesh is taken while both arrive, so both wait for it
    assert runner._mesh_lock.acquire(timeout=STATEMENT_TIMEOUT_S)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(0.5)
    finally:
        runner._mesh_lock.release()
    for t in threads:
        t.join(STATEMENT_TIMEOUT_S)
        assert not t.is_alive()
    for q in (3, 6):
        _assert_rows(answers[q], reference[q], f"q{q} sent beside another")
    assert most[0] == 1
    assert METRICS.total("presto_tpu_mesh_lock_wait_ns_total") > waited


def test_a_one_device_runner_keeps_the_one_chip_keys_and_bytes():
    def entries(runner):
        reset_cache_manager()
        runner.execute(QUERIES[6])
        return {k: e.nbytes
                for k, e in get_cache_manager().page._entries.items()}

    plain = entries(LocalRunner("tpch", SCHEMA, dict(PROPS)))
    one = entries(MeshRunner("tpch", SCHEMA, dict(PROPS), n_workers=1))
    reset_cache_manager()
    assert plain and one == plain
    # ("page", table version, catalog, schema, table, split, columns,
    # batch_rows, constraint): the key as it was before chips had names
    assert all(len(k) == 9 for k in one)


@pytest.mark.parametrize("source, direction", [
    ("same", None), ("other", "d2d"), ("host", "h2d")])
def test_a_placement_is_charged_as_what_it_is(source, direction):
    from presto_tpu.batch import Batch
    from presto_tpu.execution.memory import batch_bytes
    from presto_tpu.parallel.mesh import place
    from presto_tpu.types import BIGINT
    here, there = jax.devices()[:2]
    batch = Batch.from_numpy({"x": np.arange(1024)}, {"x": BIGINT})
    if source == "host":
        batch = jax.device_get(batch)
    else:
        batch = jax.device_put(batch, there if source == "same" else here)
    before = {d: METRICS.get(TRANSFER, direction=d)
              for d in ("h2d", "d2d")}
    placed = place(batch, there)
    for leaf in jax.tree_util.tree_leaves(placed):
        assert leaf.devices() == {there} and leaf.committed
    for d, b in before.items():
        grew = METRICS.get(TRANSFER, direction=d) - b
        assert grew == (batch_bytes(batch) if d == direction else 0), \
            (d, grew)
