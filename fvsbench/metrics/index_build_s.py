"""Seconds of set-up spent in the program's index build call (the host
HNSW build for a graph configuration), on the host's clock."""


def read(run, trace):
    return run.phases.get("index")
