def read(run):
    q = [s.stats["queued_ms"] for s in run.statements
         if s.ok and s.stats and "queued_ms" in s.stats]
    return sum(q) / len(q) if q else None
