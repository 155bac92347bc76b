COUNTER = "presto_tpu_semi_join_probe_rows_total"


def read(run):
    if not run.completed or COUNTER not in run.counters:
        return None
    return run.counter(COUNTER) / run.completed
