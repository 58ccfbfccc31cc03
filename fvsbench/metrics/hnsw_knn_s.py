"""Seconds of set-up in the HNSW build's candidate kNN (self time of the
program's `hnsw.knn` spans, summed over levels)."""
from fvsbench import spans


def read(run, trace):
    return spans.span_seconds(run, ("hnsw.knn",), own=True)
