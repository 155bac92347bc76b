"""The join build's pack as the benchmark reads it since PR 35: how many
lanes of a window's merged build batches a pack passed over
(`join_build_packed_lane_share`). It reads nothing (None) from a
program without its counter, which every program before PR 35 is;
then on the sf10 cell's own rehearsal, traced and untraced.

    python -m pytest benchmarks/tests        (not part of tier-1)
"""

import json

import pytest

from benchmarks.tests.test_device_families import _reader
from benchmarks.tests.test_join_build_metrics import (
    CELL, FINISH, LANES, ROWS, _command, _record)

PACKED = 'presto_tpu_join_build_packed_lanes_total{layout="%s"}'
NAME = "join_build_packed_lane_share"


def test_nothing_to_read_against_the_parent():
    read = _reader(NAME)
    assert read(_record(3)) is None         # no such counter
    run = _record(0)                        # no statement completed
    run.counters.update({ROWS % "direct": 0.0, LANES % "direct": 0.0,
                         PACKED % "direct": 0.0, FINISH: 0.0})
    assert read(run) is None


def test_packed_share_of_a_window():
    read = _reader(NAME)
    run = _record(3)
    run.counters.update({ROWS % "direct": 3.0 * (7779499 + 299255),
                         LANES % "direct": 3.0 * (16777216 + 1048576)})
    assert read(run) is None                # PR 34's program: no counter
    # both of Q3's builds fit their rung: counted, by 0
    run.counters[PACKED % "direct"] = 0.0
    assert read(run) == 0.0
    # a sorted build whose 16 x 4,096 input lanes shrank onto 4,096
    run.counters.update({LANES % "sorted": 3.0 * 4096,
                         PACKED % "sorted": 3.0 * 4096})
    assert read(run) == pytest.approx(
        100 * 4096 / (16777216 + 1048576 + 4096))


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cells_rehearsal_reports_it_when_traced(trace):
    p = _command("--workload", CELL, "--seed", "3500000021", "--seconds",
                 "2", "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] > 0
    if not trace:
        assert NAME not in line["metrics"]  # per-layer: traced runs only
        return
    # both of Q3's builds fit their 4,096-lane floor rung: none packed
    assert line["metrics"][NAME]["value"] == 0.0
    assert line["metrics"]["join_direct_build_share"]["value"] == 100.0
