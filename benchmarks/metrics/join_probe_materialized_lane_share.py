COUNTER = "presto_tpu_join_probe_lanes_total"


def read(run):
    searched = run.counters.get(COUNTER + '{stage="searched"}')
    if not searched:
        return None
    return 100.0 * run.counters.get(
        COUNTER + '{stage="materialized"}', 0.0) / searched
