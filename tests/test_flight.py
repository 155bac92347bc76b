"""Always-on flight recorder (telemetry/flight.py): ring mechanics,
the failure-payload snapshot riding an injected fault, and the
/v1/flight + error-payload surfaces on the coordinator."""

import json

import pytest


@pytest.fixture(autouse=True)
def _clean_ring():
    from presto_tpu.telemetry import flight
    flight.reset()
    yield
    flight.reset()


def test_ring_is_bounded_and_ordered():
    from presto_tpu.telemetry import flight
    for i in range(flight.RING_SIZE + 50):
        flight.record("query", "FINISHED", i)
    st = flight.stats()
    assert st["size"] == flight.RING_SIZE
    assert st["total"] == flight.RING_SIZE + 50
    assert st["dropped"] == 50
    evs = flight.snapshot(limit=10)
    assert len(evs) == 10
    # oldest-first within the window; the first 50 fell off the ring
    assert [e[3] for e in evs] == list(
        range(flight.RING_SIZE + 40, flight.RING_SIZE + 50))


def test_disabled_gate_is_noop():
    from presto_tpu.telemetry import flight
    flight.ENABLED = False
    try:
        flight.record("query", "FINISHED")
        assert flight.stats()["total"] == 0
    finally:
        flight.ENABLED = True


def test_a_statement_answers_the_same_with_the_recorder_off():
    """The recorder observes and decides nothing: the same statement
    gives the same rows with the gate off, and leaves no event."""
    from presto_tpu.runner import LocalRunner
    from presto_tpu.telemetry import flight
    sql = ("select returnflag, count(*), sum(quantity) from lineitem "
           "group by returnflag order by returnflag")
    props = {"fragment_result_cache_enabled": False}
    on = LocalRunner("tpch", "tiny", dict(props)).execute(sql).rows()
    assert flight.stats()["total"] > 0
    flight.reset()
    flight.ENABLED = False
    try:
        off = LocalRunner("tpch", "tiny", dict(props)).execute(sql).rows()
        assert flight.stats()["total"] == 0
    finally:
        flight.ENABLED = True
    assert off == on


def test_injected_fault_snapshot_rides_error_payload():
    """The satellite contract: a query failed by an injected fault
    carries the recorder's recent window on its exception — the fault
    event AND the failure edge are in it, no pre-arming needed."""
    from presto_tpu.runner import LocalRunner
    r = LocalRunner("tpch", "tiny",
                    {"fault_injection": "operator.add_input:once"})
    with pytest.raises(Exception) as ei:
        r.execute("select count(*) from region")
    evs = getattr(ei.value, "flight_events", None)
    assert evs, "failure must carry the flight window"
    kinds = {e["kind"] for e in evs}
    assert "fault" in kinds
    assert any(e["kind"] == "query" and e["a"] == "FAILED"
               for e in evs)
    # hygiene: disarm the session-property spec for later tests
    from presto_tpu.execution import faults
    faults.disarm()


def test_sampling_lever_keeps_one_in_n_and_counts_losses():
    from presto_tpu.telemetry import flight
    from presto_tpu.telemetry.metrics import METRICS
    before = METRICS.by_label("presto_tpu_flight_dropped_total",
                              "reason").get("sampled", 0)
    prev = flight.set_sampling({"retry": 4})
    try:
        for i in range(12):
            flight.record("retry", "task", i)
        for i in range(5):
            flight.record("query", "FINISHED", i)  # unsampled kind
        st = flight.stats()
        # 12 retry events at 1-in-4 -> 3 kept, 9 sampled out; the
        # query class is untouched
        assert st["sampled_out"] == 9
        assert st["total"] == 17
        assert st["size"] == 8
        assert st["sampling"] == {"retry": 4}
        kept = [e for e in flight.snapshot() if e[1] == "retry"]
        assert [e[3] for e in kept] == [0, 4, 8]
        assert sum(1 for e in flight.snapshot()
                   if e[1] == "query") == 5
        after = METRICS.by_label("presto_tpu_flight_dropped_total",
                                 "reason")["sampled"]
        assert after == before + 9
        # rates survive a ring reset (configuration, not state) and
        # set_sampling returns the previous rates for restore
        flight.reset()
        assert flight.stats()["sampling"] == {"retry": 4}
        assert flight.set_sampling(prev) == {"retry": 4}
    finally:
        flight.set_sampling(prev)


def test_ring_full_loss_reason_is_counted():
    from presto_tpu.telemetry import flight
    from presto_tpu.telemetry.metrics import METRICS
    before = METRICS.by_label("presto_tpu_flight_dropped_total",
                              "reason").get("ring_full", 0)
    for i in range(flight.RING_SIZE + 7):
        flight.record("query", "FINISHED", i)
    after = METRICS.by_label("presto_tpu_flight_dropped_total",
                             "reason")["ring_full"]
    assert after == before + 7
    # n <= 1 sampling entries mean "keep everything" and are dropped
    prev = flight.set_sampling({"query": 1, "task": 0})
    assert flight.stats()["sampling"] == {}
    flight.set_sampling(prev)


def test_coordinator_flight_surfaces():
    """GET /v1/flight serves the live ring; a FAILED query's flight
    window rides GET /v1/query/{id} AND the client-protocol error
    payload itself."""
    import time
    from presto_tpu.server.coordinator import Coordinator
    from presto_tpu.server.node import http_get, http_post
    coord = Coordinator(
        [], "tpch", "tiny", single_node=True,
        properties={"fault_injection": "operator.add_input:once"})
    coord.start()
    try:
        resp = json.loads(http_post(
            f"{coord.url}/v1/statement",
            b"select count(*) from nation"))
        qid = resp["id"]
        deadline = time.monotonic() + 30
        state = None
        while time.monotonic() < deadline:
            state = json.loads(http_get(resp["nextUri"]))
            if state["stats"]["state"] in ("FAILED", "FINISHED"):
                break
            time.sleep(0.05)
        assert state["stats"]["state"] == "FAILED", state
        err = state["error"]
        assert err.get("flight"), err
        assert any(e["kind"] == "fault" for e in err["flight"])
        detail = json.loads(http_get(f"{coord.url}/v1/query/{qid}"))
        assert detail["flight"]
        ring = json.loads(http_get(f"{coord.url}/v1/flight"))
        assert ring["size"] > 0
        assert any(e["kind"] == "fault" for e in ring["events"])
    finally:
        coord.stop()
        from presto_tpu.execution import faults
        faults.disarm()
