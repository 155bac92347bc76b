COUNTER = "presto_tpu_join_build_finish_ns_total"


def read(run):
    if COUNTER not in run.counters or not run.completed:
        return None
    return run.counters[COUNTER] / 1e6 / run.completed
