def read(run):
    if not run.memory_peak_bytes or not run.peaks:
        return None
    return 100.0 * run.memory_peak_bytes / run.peaks["hbm_bytes"]
