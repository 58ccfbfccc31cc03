"""Seconds of set-up moving the HNSW build's data: the vectors to the
host and the graph onto the device (`hnsw.fetch` + `hnsw.upload`)."""
from fvsbench import spans


def read(run, trace):
    return spans.span_seconds(run, ("hnsw.fetch", "hnsw.upload"))
