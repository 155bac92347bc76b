def read(run):
    return run.completed / run.window_s if run.window_s else None
