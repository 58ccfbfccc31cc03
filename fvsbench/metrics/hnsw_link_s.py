"""Seconds of set-up in the HNSW build's adjacency write, reverse-edge
fill and connectivity repair (the program's `hnsw.link` spans, summed
over levels)."""
from fvsbench import spans


def read(run, trace):
    return spans.span_seconds(run, ("hnsw.link",))
