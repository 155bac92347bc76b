CATCH_ALL = ("ledger:driver.step", "ledger:driver.quantum")
SPANS = ("ledger:", "kernel:", "compile:")


def read(run):
    if run.trace is None:
        return None
    gaps = run.trace["idle_gaps"]
    idle = sum(seconds for _, seconds in gaps)
    if not idle:
        return None
    unnamed = sum(seconds for label, seconds in gaps
                  if label in CATCH_ALL or not label.startswith(SPANS))
    return 100.0 * unnamed / idle
