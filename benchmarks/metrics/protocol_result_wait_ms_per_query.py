from benchmarks.harness.protocol_phases import protocol_ms_per_statement


def read(run):
    return protocol_ms_per_statement(run, ("result_wait",))
