"""HNSW graph, built by the program's own `build_graph_blocked`.

Config keys (`index` in the config file): m, ef_construction."""
from repro.core.hnsw import build_graph_blocked


def build(store, spec: dict, seed: int) -> dict:
    return {"graph": build_graph_blocked(
        store, m=spec["m"], ef_construction=spec["ef_construction"],
        seed=seed)}


def describe(built: dict) -> dict:
    return {"max_degree": int(built["graph"].neighbors.shape[2])}
