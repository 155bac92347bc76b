def read(run):
    both = [(s.latency_s * 1e3, s.stats["wall_ms"]) for s in run.statements
            if s.ok and s.stats and "wall_ms" in s.stats]
    if not both:
        return None
    return sum(c - w for c, w in both) / len(both)
