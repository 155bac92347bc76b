def read(run):
    if run.trace is None:
        return None
    busy = run.trace["busy_s_by_device"]
    if len(busy) < 2 or not sum(busy):
        return None
    return max(busy) * len(busy) / sum(busy)
