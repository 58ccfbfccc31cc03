"""1 - device-busy time / traced window, from the profiler trace."""
from fvsbench import trace as tr


def read(run, trace):
    return None if trace is None else tr.idle_share(trace)
