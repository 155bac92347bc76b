from benchmarks.harness.device_families import device_ms_per_statement

NAMES = ("join_build_sorted", "join_probe_hash", "join_probe_search",
         "join_probe_sorted", "fragment_join_probe_sorted")


def read(run):
    # a program that names no sorted probe runs it inside jit_join_probe:
    # nothing to read there, not 0
    return device_ms_per_statement(run, NAMES) or None
