"""The graph engine's share of its roofline, in % (roofline.py)."""
from fvsbench import readers, roofline


def read(run, trace):
    return readers.roofline_pct(run, trace, readers.GRAPH_PROGRAMS,
                                roofline.graph_batch)
