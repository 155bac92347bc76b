from benchmarks.harness.device_families import (
    family_seconds, traced_statements)
from benchmarks.harness.files import read_json
from benchmarks.metrics.exchange_device_ms_per_query import NAMES

COUNTER = "presto_tpu_exchange_all_to_all_bytes_total"


def read(run):
    n = int(run.config.get("properties", {}).get("mesh_devices", 1))
    if run.trace is None or not run.peaks or n < 2 \
            or COUNTER not in run.counters or not run.completed:
        return None
    device_s = family_seconds(run.trace, NAMES)
    if not device_s:
        return None
    wire = run.counters[COUNTER] / run.completed \
        * traced_statements(run.trace)
    rate = read_json("metrics", "ici_roofline_share.json")["ici_bytes_per_s"]
    least_s = wire * (n - 1) / n / n / rate
    return 100.0 * least_s / device_s
