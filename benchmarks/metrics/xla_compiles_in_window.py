COUNTER = "presto_tpu_xla_compiles_total"


def read(run):
    if not any(k.startswith(COUNTER + "{") for k in run.counters):
        return None
    return run.counter(COUNTER)
