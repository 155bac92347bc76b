from benchmarks.harness.driver_detail import passes


def read(run):
    counted = passes(run)
    if counted is None or not run.completed:
        return None
    return sum(counted) / run.completed
