"""Process start to window start: data, index build, predicate pool
and warm-up (the reference check after the window is not in it)."""


def read(run, trace):
    return run.setup_s
