COUNTER = "presto_tpu_exchange_all_to_all_rows_total"


def read(run):
    if COUNTER not in run.counters or not run.completed:
        return None
    return run.counters[COUNTER] / run.completed
