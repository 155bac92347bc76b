def read(run):
    n = run.page_cache.get("hits", 0) + run.page_cache.get("misses", 0)
    return 100.0 * run.page_cache["hits"] / n if n else None
