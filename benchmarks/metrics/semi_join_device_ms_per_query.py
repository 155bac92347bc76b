from benchmarks.harness.device_families import device_ms_per_statement

NAMES = ("semi_join",)


def read(run):
    return device_ms_per_statement(run, NAMES)
