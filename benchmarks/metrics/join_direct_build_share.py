COUNTER = "presto_tpu_join_builds_total"


def read(run):
    if not any(k.startswith(COUNTER + "{") for k in run.counters):
        return None
    builds = run.counter(COUNTER)
    if not builds:
        return None
    return 100.0 * run.counters.get(
        COUNTER + '{layout="direct"}', 0.0) / builds
