from benchmarks.harness.device_families import device_ms_per_statement

NAMES = ("join_build", "dynamic_filter")


def read(run):
    return device_ms_per_statement(run, NAMES)
