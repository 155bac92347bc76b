"""What the ledger's detailed frames and the driver's pass counters
say about a window (presto_tpu_ledger_detail_ns_total{category,
detail}, presto_tpu_driver_passes_total{moved}): both None where the
program has no such series."""

from __future__ import annotations

DETAIL = "presto_tpu_ledger_detail_ns_total"
PASSES = "presto_tpu_driver_passes_total"


def detail_ms_per_statement(run, category: str) -> float | None:
    """ms per completed statement that the detailed frames of one
    ledger category took for themselves, over every detail."""
    prefix = f'{DETAIL}{{category="{category}",'
    ns = [v for k, v in run.counters.items() if k.startswith(prefix)]
    if not ns or not run.completed:
        return None
    return sum(ns) / 1e6 / run.completed


def passes(run) -> tuple[float, float] | None:
    """(moved, not moved): the window's passes of every driver over
    its operator chain, by whether one moved a batch."""
    moved, idle = (f'{PASSES}{{moved="{m}"}}' for m in ("yes", "no"))
    if moved not in run.counters and idle not in run.counters:
        return None
    return run.counters.get(moved, 0.0), run.counters.get(idle, 0.0)
