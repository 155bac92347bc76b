from benchmarks.harness.device_families import device_ms_per_statement

NAMES = ("join_probe", "fragment_join_probe", "semi_join", "join_outer")


def read(run):
    return device_ms_per_statement(run, NAMES)
