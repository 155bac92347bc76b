PACKED = "presto_tpu_join_build_packed_lanes_total"
LANES = "presto_tpu_join_build_lanes_total"


def read(run):
    if not any(k.startswith(PACKED + "{") for k in run.counters):
        return None
    lanes = run.counter(LANES)
    if not lanes:
        return None
    return 100.0 * run.counter(PACKED) / lanes
