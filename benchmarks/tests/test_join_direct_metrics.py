"""The direct join layout as the benchmark reads it: its build
programs land in the `join_build` group of the per-family device
times, and `join_direct_build_share` is the growth of the direct
layout's counter over all layouts' (None without the counter)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.tests.test_device_families import _reader, _run  # noqa: E402


def test_direct_build_programs_land_in_the_join_build_group():
    # importing the kernels' modules names their programs; the fused
    # probes are named when a plan builds them
    import presto_tpu.execution.dynamic_filters  # noqa: F401
    import presto_tpu.ops.join  # noqa: F401
    from presto_tpu.telemetry import kernels
    kernels.jit(lambda x: x, "fragment", "join_probe")
    kernels.jit(lambda x: x, "join_probe")
    trace = {
        "busy_s": 2.0, "window_s": 4.0, "devices": 1,
        "by_module": [["jit_fragment_join_probe(77)", 1.2],
                      ["jit_join_build_direct(12)", 0.5],
                      ["jit_dynamic_filter_distinct_set", 0.1],
                      ["jit_join_build_stats", 0.004],
                      ["jit_join_probe", 0.1]],
        "idle_gaps": [],
        "marks": [(0.0, 2.0, "q3#0"), (2.0, 4.0, "q3#1")],
    }
    build = _reader("join_build_device_ms_per_query")(_run(trace))
    assert build == pytest.approx(1e3 * (0.5 + 0.1 + 0.004) / 2)
    probe = _reader("join_probe_device_ms_per_query")(_run(trace))
    assert probe == pytest.approx(1e3 * (1.2 + 0.1) / 2)
    assert _reader("unnamed_device_share")(_run(trace)) == 0.0


def test_direct_build_share_of_the_windows_builds():
    read = _reader("join_direct_build_share")
    run = _run(None)
    run.counters = {'presto_tpu_kernel_calls_total{kernel="join_build"}': 9}
    assert read(run) is None            # a program without the counter
    run.counters['presto_tpu_join_builds_total{layout="sorted"}'] = 0.0
    assert read(run) is None            # a window that built no join
    run.counters['presto_tpu_join_builds_total{layout="direct"}'] = 16.0
    assert read(run) == 100.0
    run.counters['presto_tpu_join_builds_total{layout="sorted"}'] = 48.0
    run.counters['presto_tpu_join_direct_fallback_total'
                 '{reason="duplicate"}'] = 48.0
    assert read(run) == 25.0
