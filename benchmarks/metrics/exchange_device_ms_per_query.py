from benchmarks.harness.device_families import device_ms_per_statement

NAMES = ("spmd_shuffle", "spmd_fragment", "exchange_partition")


def read(run):
    return device_ms_per_statement(run, NAMES)
