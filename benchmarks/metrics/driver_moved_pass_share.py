from benchmarks.harness.driver_detail import passes


def read(run):
    counted = passes(run)
    if counted is None or not sum(counted):
        return None
    return 100.0 * counted[0] / sum(counted)
