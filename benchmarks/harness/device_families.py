"""Device time by kernel family, from a reduced trace.

The program names every device program after its kernel family
(presto_tpu/telemetry/kernels.py: `jit(fn, family, part)` makes the XLA
module `jit_<family>[_<part>]`), and `trace_reduce.reduce` groups device
time by XLA module (`by_module`, the ten largest). The readers under
metrics/ sum that list through the program's own lookup, so no table
of names is kept here.

A group is a set of names; a module belongs to it when its family or
its device name is in the set (`fragment` is one family for several
programs, so `fragment_agg_step` and `fragment_join_probe` are asked
for by device name).

Against a program without the lookup (before PR 26) every reader here
returns None."""

from __future__ import annotations


def _lookup():
    try:
        from presto_tpu.telemetry.kernels import (
            device_name_of, family_of_module)
    except ImportError:
        return None
    return device_name_of, family_of_module


def traced_statements(trace: dict) -> float:
    """Statements the traced window holds: each mark counts by the
    share of its length that lies inside the window."""
    window_s = trace["window_s"]
    n = 0.0
    for start_s, end_s, _ in trace["marks"]:
        if end_s > start_s:
            inside = min(end_s, window_s) - max(start_s, 0.0)
            n += max(inside, 0.0) / (end_s - start_s)
    return n


def family_seconds(trace: dict, names) -> float | None:
    """Device seconds, inside the traced window, of the modules of
    `by_module` whose family or device name is in `names`."""
    lookup = _lookup()
    if lookup is None:
        return None
    device_name_of, family_of_module = lookup
    names = set(names)
    return sum(seconds for module, seconds in trace["by_module"]
               if family_of_module(module) in names
               or device_name_of(module) in names)


def device_ms_per_statement(run, names) -> float | None:
    """ms of device time of a group per traced statement; None without
    a trace, a traced statement or the program's lookup."""
    if run.trace is None:
        return None
    seconds = family_seconds(run.trace, names)
    n = traced_statements(run.trace)
    if seconds is None or not n:
        return None
    return 1e3 * seconds / n


def unnamed_share(run) -> float | None:
    """% of the device's busy time spent in modules (of the ten
    largest) that no kernel family named: eager jnp ops, each a device
    program of its own, or a jax.jit that bypassed kernels.jit."""
    if run.trace is None or not run.trace["busy_s"]:
        return None
    lookup = _lookup()
    if lookup is None:
        return None
    _, family_of_module = lookup
    unnamed = sum(seconds for module, seconds in run.trace["by_module"]
                  if family_of_module(module) is None)
    return 100.0 * unnamed / run.trace["busy_s"]
