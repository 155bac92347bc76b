from benchmarks.harness.bytes_needed import bytes_needed, scan_rows


def read(run):
    if run.trace is None or not run.trace["busy_s"] or not run.peaks:
        return None
    by_seq = {s.seq: s for s in run.statements}
    total = 0
    for _, _, mark in run.trace["marks"]:
        s = by_seq.get(int(mark.rsplit("#", 1)[1]))
        if s is None or not s.stats:
            continue
        total += bytes_needed(run.queries[s.name]["scans"],
                              scan_rows(s.stats))
    if not total:
        return None
    least_s = total / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / run.trace["busy_s"]
