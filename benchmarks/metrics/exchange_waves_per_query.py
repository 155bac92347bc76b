COUNTER = "presto_tpu_exchange_all_to_all_waves_total"


def read(run):
    if COUNTER not in run.counters or not run.completed:
        return None
    return run.counters[COUNTER] / run.completed
