COUNTER = "presto_tpu_join_build_rows_total"


def read(run):
    if not run.completed or not any(
            k.startswith(COUNTER + "{") for k in run.counters):
        return None
    return run.counter(COUNTER) / run.completed
