SERIES = 'presto_tpu_join_probe_rows_total{layout="sorted"}'


def read(run):
    if not run.completed or not any(
            k.startswith("presto_tpu_join_probe_rows_total{")
            for k in run.counters):
        return None
    return run.counters.get(SERIES, 0.0) / run.completed
