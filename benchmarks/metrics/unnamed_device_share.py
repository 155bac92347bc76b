from benchmarks.harness.device_families import unnamed_share


def read(run):
    return unnamed_share(run)
