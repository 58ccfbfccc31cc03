"""Mean host time per dispatch: the dispatch span less the device-busy
time inside it, on the trace's clock."""
from fvsbench import trace as tr


def read(run, trace):
    return None if trace is None else tr.host_ms_per_dispatch(trace)
