"""The per-family readers on a hand-made reduced trace (None without
one), the protocol readers on hand-made counters, and
record_host_spans.py's gap charging and clock offset on hand-made
tuples."""

import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.harness import device_families as df  # noqa: E402
from benchmarks.harness.window import RunRecord, Statement  # noqa: E402
from benchmarks.tests import record_host_spans as rhs  # noqa: E402
from presto_tpu.telemetry import kernels  # noqa: E402


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.metrics.{name}",
        os.path.join(ROOT, "benchmarks", "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(trace):
    run = RunRecord("cell", {}, {}, {})
    run.trace = trace
    return run


@pytest.fixture(scope="module")
def trace():
    # the names the program would have registered by the time a trace
    # exists (kernels.jit records them when a kernel is built)
    for family, part in (("join_build", "sorted"), ("fragment", "agg_step"),
                         ("fragment", "join_probe"), ("join_probe", None),
                         ("compact", "shrink"), ("pad", None),
                         ("dynamic_filter", "apply"),
                         ("agg_finalize", None)):
        kernels.jit(lambda x: x, family, part)
    # window 8 s; the second mark lies half outside it, so 1.5
    # statements were traced
    return {
        "busy_s": 6.0, "window_s": 8.0, "devices": 1,
        "by_module": [["jit_fragment_join_probe", 2.4],
                      ["jit_join_build_sorted", 1.5],
                      ["jit_fragment_agg_step", 0.9],
                      ["jit_join_probe", 0.3],
                      ["jit_compact_shrink", 0.24],
                      ["jit_dynamic_filter_apply", 0.15],
                      ["jit_pad", 0.06],
                      ["jit_agg_finalize", 0.03],
                      ["jit__reduce_sum", 0.09],
                      ["(no module)", 0.03]],
        "idle_gaps": [],
        "marks": [(0.0, 4.0, "q3#0"), (4.0, 12.0, "q3#1")],
    }


def test_traced_statements_count_by_their_share_inside_the_window(trace):
    assert df.traced_statements(trace) == pytest.approx(1.5)


@pytest.mark.parametrize("metric, seconds", [
    ("join_build_device_ms_per_query", 1.5 + 0.15),
    ("join_probe_device_ms_per_query", 2.4 + 0.3),
    ("aggregation_device_ms_per_query", 0.9 + 0.03),
    ("compact_pad_device_ms_per_query", 0.24 + 0.06),
])
def test_device_ms_of_a_group_per_traced_statement(trace, metric, seconds):
    read = _reader(metric)
    assert read(_run(trace)) == pytest.approx(1e3 * seconds / 1.5)
    assert read(_run(None)) is None
    assert read(_run(dict(trace, marks=[]))) is None


def test_unnamed_share_is_what_no_family_named(trace):
    read = _reader("unnamed_device_share")
    assert read(_run(trace)) == pytest.approx(100 * 0.12 / 6.0)
    assert read(_run(None)) is None


def test_without_the_programs_lookup_every_reader_reads_nothing(
        trace, monkeypatch):
    monkeypatch.setattr(df, "_lookup", lambda: None)
    for metric in ("join_build_device_ms_per_query",
                   "unnamed_device_share"):
        assert _reader(metric)(_run(trace)) is None


def test_protocol_readers_per_completed_statement():
    run = _run(None)
    run.statements = [Statement("q6", 0, i, 0.0, 0.1, True, correct=True)
                      for i in range(4)]
    wait, serve = (_reader("protocol_result_wait_ms_per_query"),
                   _reader("protocol_serve_ms_per_query"))
    assert wait(run) is None and serve(run) is None   # no such counter
    run.counters = {
        'presto_tpu_protocol_ns_total{phase="result_wait"}': 20e6,
        'presto_tpu_protocol_ns_total{phase="accept"}': 6e6,
        'presto_tpu_protocol_ns_total{phase="encode"}': 2e6}
    assert wait(run) == pytest.approx(5.0)
    assert serve(run) == pytest.approx(2.0)


def test_xla_compiles_in_window_reads_the_counters_growth():
    read = _reader("xla_compiles_in_window")
    run = _run(None)
    run.counters = {'presto_tpu_kernel_compiles_total{kernel="pad"}': 2.0}
    assert read(run) is None            # a program without the counter
    run.counters.update({
        'presto_tpu_xla_compiles_total{family="pad"}': 0.0,
        'presto_tpu_xla_compiles_total{family="(unnamed)"}': 3.0,
        'presto_tpu_xla_compile_seconds_total{family="pad"}': 0.5})
    assert read(run) == 3.0


def _threads():
    return {
        "driver": [(0, 100, "ledger:driver.quantum"),
                   (10, 60, "ledger:driver.step"),
                   (20, 30, "kernel:fragment"),
                   (70, 90, "ledger:scan")],
        "handler": [(40, 50, "ledger:planning")],
    }


def test_gaps_go_to_the_innermost_span_open_at_their_middle():
    gaps = [(22, 28),     # middle 25: kernel:fragment (in step, quantum)
            (40, 50),     # middle 45: step on one thread; planning on
                          # the other opened later (40 > 10) and takes it
            (62, 66),     # middle 64: quantum alone
            (100, 120)]   # middle 110: nothing open
    charged = rhs.charge_gaps(gaps, _threads())
    assert charged == {
        "kernel:fragment": 6, "ledger:planning": 10,
        "ledger:driver.quantum": 4, rhs.NO_SPAN: 20}
    assert sum(charged.values()) == sum(e - s for s, e in gaps)
    # a device clock 15 ahead of the host's: middle 25 is host time 10,
    # where driver.step has just opened
    assert rhs.charge_gaps([(22, 28)], _threads(), offset_ns=15) == {
        "ledger:driver.step": 6}
    # after a nested span closes, its parent is the innermost again
    assert rhs.charge_gaps([(31, 35)], _threads()) == {
        "ledger:driver.step": 4}


def test_idle_gaps_are_the_windows_complement():
    ops = [(5, 20, "a"), (15, 30, "b"), (50, 60, "c")]
    assert rhs.idle_gaps(ops, 0, 70) == [(0, 5), (30, 50), (60, 70)]
    assert rhs.idle_gaps(ops, 10, 55) == [(30, 50)]


def test_clock_offset_pairs_a_kernel_span_with_its_familys_next_module():
    threads = {"t": [(100, 110, "kernel:fragment"),
                     (200, 210, "kernel:pad"),
                     (300, 310, "kernel:window")]}   # never ran
    modules = [(90, 95, "jit_fragment_agg_step(1)"),   # before the span
               (103, 150, "jit_fragment_agg_step(1)"),
               (207, 209, "jit_pad(2)")]
    assert sorted(rhs.clock_offsets(
        threads, modules, kernels.family_of_module)) == [3, 7]
