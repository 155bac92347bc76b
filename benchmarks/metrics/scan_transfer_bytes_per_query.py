COUNTER = "presto_tpu_transfer_bytes_total"
MESH = "presto_tpu_mesh_queries_total"


def read(run):
    if not run.counter(MESH) or not run.completed:
        return None
    moved = sum(run.counters.get(f'{COUNTER}{{direction="{d}"}}', 0.0)
                for d in ("h2d", "d2d"))
    return moved / run.completed
