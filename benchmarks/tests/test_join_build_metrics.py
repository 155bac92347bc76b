"""The join build side as the benchmark reads it since PR 34: how many
rows a statement's builds index (`join_build_rows_per_query`), how
much of the merged batches' lanes they fill
(`join_build_live_lane_share`) and how long the build barrier holds
the host (`join_build_finish_ms_per_query`). Each reads nothing
(None) from a program without its counter, which every program before
PR 34 is; then on the new cell's own rehearsal, traced and untraced.

    python -m pytest benchmarks/tests        (not part of tier-1)
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.harness.window import Statement  # noqa: E402
from benchmarks.tests.test_device_families import _reader, _run  # noqa: E402
from benchmarks.tests.test_rehearsal import _run as _command  # noqa: E402

ROWS = 'presto_tpu_join_build_rows_total{layout="%s"}'
LANES = 'presto_tpu_join_build_lanes_total{layout="%s"}'
FINISH = "presto_tpu_join_build_finish_ns_total"
NAMES = ("join_build_rows_per_query", "join_build_live_lane_share",
         "join_build_finish_ms_per_query")
CELL = "sf10_join_serial"


def _record(statements):
    run = _run(None)
    run.statements = [Statement("q3", 0, i, float(i), i + 1.0, True,
                                correct=True) for i in range(statements)]
    # what any program since PR 27 counts beside them
    run.counters = {'presto_tpu_join_builds_total{layout="direct"}':
                    2.0 * statements}
    return run


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_against_the_parent(name):
    read = _reader(name)
    assert read(_record(3)) is None         # no such counter
    run = _record(0)                        # no statement completed
    run.counters.update({ROWS % "direct": 0.0, LANES % "direct": 0.0,
                         FINISH: 0.0})
    assert read(run) is None


def test_q3_at_sf10_a_window_of_three_statements():
    # orders 7,779,499 rows on the 16,777,216-lane rung, customer
    # 299,255 on the 1,048,576-lane one (rehearsal, ISSUE 34)
    run = _record(3)
    run.counters.update({
        ROWS % "direct": 3.0 * (7779499 + 299255),
        LANES % "direct": 3.0 * (16777216 + 1048576),
        FINISH: 3 * 4.5e9})
    assert _reader(NAMES[0])(run) == 8078754
    assert _reader(NAMES[1])(run) == pytest.approx(45.32, abs=0.01)
    assert _reader(NAMES[2])(run) == 4500.0
    # a build that stayed sorted counts like any other
    run.counters.update({ROWS % "sorted": 3.0 * 1000,
                         LANES % "sorted": 3.0 * 4096})
    assert _reader(NAMES[0])(run) == 8079754
    assert _reader(NAMES[1])(run) == pytest.approx(
        100 * 8079754 / (16777216 + 1048576 + 4096))


def test_a_window_that_built_no_join():
    run = _record(2)
    run.counters.update({ROWS % "direct": 0.0, LANES % "direct": 0.0,
                         FINISH: 0.0})
    assert _reader(NAMES[0])(run) == 0.0
    assert _reader(NAMES[1])(run) is None   # nothing to divide by
    assert _reader(NAMES[2])(run) == 0.0


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cells_rehearsal_reports_them_when_traced(trace):
    p = _command("--workload", CELL, "--seed", "3400000019", "--seconds",
                 "2", "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] > 0
    got = {n: line["metrics"][n]["value"] for n in NAMES
           if n in line["metrics"]}
    if not trace:
        assert got == {}                    # per-layer: traced runs only
        assert {"qps", "setup_s"} <= set(line["metrics"])
        assert "latency_p95_ms" not in line["metrics"]
        return
    assert set(got) == set(NAMES)
    # tiny: orders' and customer's live rows land on the 4,096-lane
    # floor rung each; both builds direct
    assert 0 < got[NAMES[0]] <= 2 * 4096
    assert got[NAMES[1]] == pytest.approx(
        100 * got[NAMES[0]] / (2 * 4096))
    assert got[NAMES[2]] > 0
    assert line["metrics"]["join_direct_build_share"]["value"] == 100.0
