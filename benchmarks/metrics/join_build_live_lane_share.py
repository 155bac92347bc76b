ROWS = "presto_tpu_join_build_rows_total"
LANES = "presto_tpu_join_build_lanes_total"


def read(run):
    lanes = run.counter(LANES)
    if not lanes:
        return None
    return 100.0 * run.counter(ROWS) / lanes
