"""Verifier and test-budget tooling (reference: presto-verifier
AbstractVerification checksum comparison)."""

import json

import pytest

from presto_tpu.tools import test_budget
from presto_tpu.tools.verifier import (
    result_checksum, row_checksum, verify_queries,
)


def test_checksum_order_insensitive():
    a = [(1, "x", 2.5), (None, "y", -1.0)]
    b = [(None, "y", -1.0), (1, "x", 2.5)]
    assert result_checksum(a) == result_checksum(b)


def test_checksum_distinguishes_null_and_zero():
    assert row_checksum((None,)) != row_checksum((0,))
    assert row_checksum((None,)) != row_checksum(("",))


def test_checksum_float_tolerance():
    assert row_checksum((1.0 + 1e-12,)) == row_checksum((1.0,))
    assert row_checksum((1.0 + 1e-3,)) != row_checksum((1.0,))


def test_verify_match_and_mismatch():
    control = {"q1": [(1,), (2,)], "q2": [(3,)], "q3": [(9,)]}
    test = {"q1": [(2,), (1,)], "q2": [(4,)], "q3": [(9,)]}
    results = verify_queries(
        lambda sql: control[sql], lambda sql: test[sql],
        {"q1": "q1", "q2": "q2", "q3": "q3"})
    by_name = {v.name: v.status for v in results}
    assert by_name == {"q1": "match", "q2": "mismatch", "q3": "match"}


def test_verify_error_recorded():
    def boom(sql):
        raise RuntimeError("nope")
    results = verify_queries(lambda sql: [(1,)], boom, {"q": "q"})
    assert results[0].status == "test_error"
    assert "nope" in results[0].detail


@pytest.mark.slow
def test_verifier_local_vs_mesh_cli(capsys):
    """End-to-end: a 3-query slice of the TPC-H suite verified
    local vs mesh through the CLI entry point."""
    from presto_tpu.tools import verifier
    queries = {k: v for k, v in verifier.load_suite("tpch").items()
               if k in ("q1", "q6", "q14")}
    import presto_tpu.tools.verifier as V
    orig = V.load_suite
    V.load_suite = lambda name: queries
    try:
        rc = verifier.main(["--control", "local", "--test", "mesh",
                            "--schema", "tiny"])
    finally:
        V.load_suite = orig
    assert rc == 0
    out = capsys.readouterr().out
    assert out.count("match") == 3


# -- test_budget -------------------------------------------------------

DURATIONS = """\
============= slowest 50 durations =============
12.34s call     tests/test_serving.py::test_warm_mix
3.21s call     tests/test_fleet.py::test_churn[2]
0.45s setup    tests/test_serving.py::test_warm_mix
0.10s teardown tests/test_serving.py::test_warm_mix
(142 durations < 0.005s hidden.  Use -vv to show these durations.)
= 900 passed in 700.00s =
"""


def test_budget_parses_and_sorts():
    rows = test_budget.parse_durations(DURATIONS)
    assert rows[0] == (12.34, "call", "tests/test_serving.py::"
                                      "test_warm_mix")
    assert [r[1] for r in rows] == ["call", "call", "setup",
                                    "teardown"]


def test_budget_ceiling_counts_call_phase_only():
    rows = test_budget.parse_durations(DURATIONS)
    # the 0.45s setup shares a fixture — never double-charged
    assert test_budget.over_ceiling(rows, 10.0) == \
        [(12.34, "call", "tests/test_serving.py::test_warm_mix")]
    assert test_budget.over_ceiling(rows, 20.0) == []
    text = test_budget.report(rows)
    assert "test_warm_mix" in text and "15.6s total" in text


def test_budget_cli(tmp_path, capsys):
    f = tmp_path / "durations.txt"
    f.write_text(DURATIONS)
    assert test_budget.main(["--file", str(f), "--ceiling",
                             "20"]) == 0
    capsys.readouterr()  # drain the plain-text report
    assert test_budget.main(["--file", str(f), "--ceiling", "5",
                             "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["tests_measured"] == 2
    assert [b["test"] for b in doc["breaches"]] == \
        ["tests/test_serving.py::test_warm_mix"]
