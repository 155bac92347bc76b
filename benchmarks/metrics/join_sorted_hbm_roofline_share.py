from benchmarks.harness.device_families import (
    family_seconds, traced_statements)
from benchmarks.metrics.join_sorted_device_ms_per_query import NAMES

COUNTER = "presto_tpu_join_sorted_bytes_total"


def read(run):
    if run.trace is None or not run.peaks \
            or COUNTER not in run.counters or not run.completed:
        return None
    device_s = family_seconds(run.trace, NAMES)
    if not device_s:
        return None
    least = run.counters[COUNTER] / run.completed \
        * traced_statements(run.trace)
    least_s = least / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
