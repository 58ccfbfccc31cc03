"""Queries answered per second over the whole window (closed loop)."""


def read(run, trace):
    return run.completed / run.window_s if run.window_s > 0 else None
