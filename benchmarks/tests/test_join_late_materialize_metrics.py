"""The late-materialized join probe as the benchmark reads it: the
back program's device time lands in the `join_probe` group (the
reader sums the family, and `join_probe_materialize` is of it), and
`join_probe_materialized_lane_share` is the growth of the lanes
counter's `materialized` stage over its `searched` stage (None
without the counter, which is every program before PR 33)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.tests.test_device_families import _reader, _run  # noqa: E402

LANES = 'presto_tpu_join_probe_lanes_total{stage="%s"}'


def test_materialize_program_lands_in_the_join_probe_group():
    # building a probe kernel names its programs, the back among
    # them; the fronts' names are the chip's (the CPU stages them)
    from presto_tpu.operators.join_ops import make_probe_kernel
    from presto_tpu.telemetry import kernels
    make_probe_kernel(("k",), "inner", ("k",), ("v",), ("k",))
    kernels.jit(lambda x: x, "fragment", "join_probe")
    kernels.jit(lambda x: x, "join_probe")
    assert kernels.family_of_module(
        "jit_join_probe_materialize(5)") == "join_probe"
    trace = {
        "busy_s": 2.0, "window_s": 4.0, "devices": 1,
        "by_module": [["jit_fragment_join_probe(77)", 0.30],
                      ["jit_join_probe_materialize(5)", 0.12],
                      ["jit_join_probe", 0.02],
                      ["jit_compact_shrink", 0.01],
                      ["jit_compact", 0.36]],
        "idle_gaps": [],
        "marks": [(0.0, 2.0, "q3#0"), (2.0, 4.0, "q3#1")],
    }
    probe = _reader("join_probe_device_ms_per_query")(_run(trace))
    assert probe == pytest.approx(1e3 * (0.30 + 0.12 + 0.02) / 2)
    pack = _reader("compact_pad_device_ms_per_query")(_run(trace))
    assert pack == pytest.approx(1e3 * (0.01 + 0.36) / 2)
    assert _reader("unnamed_device_share")(_run(trace)) == 0.0


def test_materialized_lane_share_of_the_windows_probes():
    read = _reader("join_probe_materialized_lane_share")
    run = _run(None)
    run.counters = {'presto_tpu_kernel_calls_total{kernel="join_probe"}': 9,
                    'presto_tpu_join_builds_total{layout="direct"}': 2.0}
    assert read(run) is None            # a program without the counter
    run.counters[LANES % "searched"] = 0.0
    assert read(run) is None            # a window that probed no join
    # Q3 at sf1, a statement: 4 x (1M + 256K + 64K + 16K) searched,
    # 4 x (64K + 16K + 16K + 4K) materialized
    run.counters[LANES % "searched"] = 4.0 * (
        1048576 + 262144 + 65536 + 16384)
    run.counters[LANES % "materialized"] = 4.0 * (
        65536 + 16384 + 16384 + 4096)
    assert read(run) == pytest.approx(7.353, abs=1e-3)
    run.counters[LANES % "materialized"] = run.counters[
        LANES % "searched"]
    assert read(run) == 100.0           # nothing materialized late
