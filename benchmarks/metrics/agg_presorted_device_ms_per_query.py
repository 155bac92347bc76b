from benchmarks.harness.device_families import device_ms_per_statement

NAMES = ("agg_step_presorted",)


def read(run):
    # a program that gives the presorted step no device name of its own
    # runs it inside jit_agg_step: nothing to read there, not 0
    return device_ms_per_statement(run, NAMES) or None
