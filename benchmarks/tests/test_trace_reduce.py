"""The trace reduction gives the hand-computed busy and idle shares
and top operations, on hand-made events and on the small trace
recorded on the chip (record_trace.py); the bytes-needed function
gives the hand-computed values for Q1/Q3/Q6 at sf1."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.harness import bytes_needed as bn  # noqa: E402
from benchmarks.harness import trace_reduce as tr  # noqa: E402

RECORDED = os.path.join(HERE, "data", "small.xplane.pb")


def _events():
    # one device, times in ns. Window = marks' span = [1000, 11000].
    return {
        "ops": {0: [(0, 500, "before.window"),         # outside
                    (1000, 3000, "fusion.1"),
                    (2000, 4000, "fusion.2"),          # overlaps fusion.1
                    (6000, 7000, "copy.3"),
                    (10500, 12000, "fusion.4")]},      # clipped at 11000
        "modules": {0: [(900, 4100, "jit_join(123)"),
                        (5900, 7100, "jit_agg(77)"),
                        (10400, 12100, "jit_join(123)")]},
        "marks": [(1000, 5000, "bench:q3#0"), (5000, 11000, "bench:q1#1")],
        "lines": {},
    }


def test_busy_idle_and_top_operations_by_hand():
    r = tr.reduce(_events())
    # busy: [1000,4000] + [6000,7000] + [10500,11000] = 3000+1000+500
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["busy_s"] == pytest.approx(4500e-9)
    assert r["devices"] == 1
    assert r["by_module"] == [["jit_join", pytest.approx(3500e-9)],
                              ["jit_agg", pytest.approx(1000e-9)]]
    # gaps: [4000,6000] mid 5000 -> q1 at 0%; [7000,10500] mid 8750 ->
    # q1 at 60% (3750/6000)
    assert r["idle_gaps"] == [["q1@60%", pytest.approx(3500e-9)],
                              ["q1@0%", pytest.approx(2000e-9)]]
    assert [m[2] for m in r["marks"]] == ["q3#0", "q1#1"]


def test_two_devices_average_and_gap_without_statement():
    ev = _events()
    ev["ops"][1] = [(1000, 2000, "fusion.9")]
    ev["marks"] = [(1000, 2000, "bench:q6#0"), (9000, 11000, "bench:q6#1")]
    r = tr.reduce(ev)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((4500 + 1000) / 2 * 1e-9)
    labels = dict(map(tuple, r["idle_gaps"]))
    # no statement in flight at the middle of any gap: device 0's
    # [4000,6000] and [7000,10500], device 1's [2000,11000]; per device
    assert labels == {"no_statement_in_flight": pytest.approx(
        (2000 + 3500 + 9000) / 2 * 1e-9)}


def test_nothing_on_the_device_reads_nothing():
    assert tr.reduce({"ops": {}, "modules": {}, "marks": [
        (0, 10, "bench:q6#0")], "lines": {}}) is None


def test_recorded_trace_by_hand():
    """small.xplane.pb, recorded on a TPU v5e by record_trace.py: two
    runs of jit__lambda (a matrix product: copy-start, copy-done,
    fusion) before the first mark on the device's clock, one
    multiply_add fusion of 50,323 ns inside mark qa. (The device's
    clock reads about 1.4 ms behind the host's here: both products
    were issued inside qa.)"""
    ev = tr.load(RECORDED)
    assert ev["lines"]["/device:TPU:0"][:2] == ["XLA Modules", "XLA Ops"]
    assert [len(ev[k][0]) for k in ("ops", "modules")] == [7, 3]
    assert [m[2] for m in ev["marks"]] == [
        "bench:qa#0", "bench:qb#1", "bench:qc#2"]
    r = tr.reduce(ev)
    # window = marks' span; the one operation inside it
    assert r["window_s"] == pytest.approx((78150152 - 43758505) * 1e-9)
    assert r["busy_s"] == pytest.approx(50323e-9)
    assert r["by_module"] == [["jit__lambda", pytest.approx(50323e-9)]]
    # idle before it (328,574 ns, 9% into qa) and after it (34,012,750
    # ns, its middle 72% into qb)
    assert r["idle_gaps"] == [["qb@70%", pytest.approx(34012750e-9)],
                              ["qa@0%", pytest.approx(328574e-9)]]
    # without marks the window is the span of the device's operations:
    # 22,545 + 89,952 and 17 + 89,975 ns for the products, 50,323 ns
    ev["marks"] = []
    r = tr.reduce(ev)
    assert r["window_s"] == pytest.approx((44137402 - 42385697) * 1e-9)
    assert r["busy_s"] == pytest.approx(252812e-9)
    assert r["by_module"] == [["jit__lambda", pytest.approx(252812e-9)]]
    assert r["idle_gaps"][0][0] == "no_statement_in_flight"
    assert 100 * (1 - r["busy_s"] / r["window_s"]) == pytest.approx(
        85.5677, abs=1e-3)


def test_bytes_needed_by_hand():
    # widths: bigint/double 8+1, integer/date/dictionary code 4+1
    q = {n: json.load(open(os.path.join(ROOT, "benchmarks", "queries",
                                        f"{n}.json")))["scans"]
         for n in ("q1", "q3", "q6")}
    assert bn.row_bytes("lineitem", q["q6"]["lineitem"]) == 5 + 3 * 9
    assert bn.row_bytes("lineitem", q["q1"]["lineitem"]) == 3 * 5 + 4 * 9
    assert bn.row_bytes("orders", q["q3"]["orders"]) == 9 + 9 + 5 + 5
    assert bn.row_bytes("customer", q["q3"]["customer"]) == 9 + 5
    # sf1 with no pushdown: lineitem 6,001,215 rows, orders 1,500,000,
    # customer 150,000
    assert bn.bytes_needed(q["q6"], {"lineitem": 6001215}) == 192038880
    assert bn.bytes_needed(q["q1"], {"lineitem": 6001215}) == 306061965
    assert bn.bytes_needed(q["q3"], {
        "lineitem": 6001215, "orders": 1500000, "customer": 150000}) \
        == 6001215 * 32 + 1500000 * 28 + 150000 * 14
    stats = {"tasks": [{"pipelines": [[
        {"name": "scan:lineitem", "output_rows": 10},
        {"name": "filter_project", "output_rows": 4}], [
        {"name": "scan:lineitem", "output_rows": 5}]]}]}
    assert bn.scan_rows(stats) == {"lineitem": 15}
