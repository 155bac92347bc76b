COUNTER = "presto_tpu_agg_stream_groups_total"


def read(run):
    if not run.completed or COUNTER not in run.counters:
        return None
    return run.counter(COUNTER) / run.completed
