from benchmarks.harness.driver_detail import detail_ms_per_statement


def read(run):
    operators = detail_ms_per_statement(run, "driver.step")
    if operators is None:
        return None
    step = run.ledger_ns.get("driver.step", 0.0) / 1e6 / run.completed
    return step - operators
