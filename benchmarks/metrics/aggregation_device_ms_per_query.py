from benchmarks.harness.device_families import device_ms_per_statement

NAMES = ("agg_step", "fragment_agg_step", "agg_finalize", "agg_count",
         "agg_shrink", "agg_stream", "hashagg_merge")


def read(run):
    return device_ms_per_statement(run, NAMES)
