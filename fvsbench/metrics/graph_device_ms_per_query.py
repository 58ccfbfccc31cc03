"""Device time of the graph engine's program per answered query."""
from fvsbench import readers


def read(run, trace):
    return readers.device_ms_per_query(run, trace, readers.GRAPH_PROGRAMS)
