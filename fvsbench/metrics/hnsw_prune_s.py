"""Seconds of set-up in the HNSW build's diversity pruning (the program's
`hnsw.prune` spans, summed over levels)."""
from fvsbench import spans


def read(run, trace):
    return spans.span_seconds(run, ("hnsw.prune",))
