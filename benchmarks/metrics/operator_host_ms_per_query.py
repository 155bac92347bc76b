from benchmarks.harness.driver_detail import detail_ms_per_statement


def read(run):
    return detail_ms_per_statement(run, "driver.step")
