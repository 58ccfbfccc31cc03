"""Paper Table 3: index build time and size, HNSW vs ScaNN per dataset."""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import BENCH_DATASETS, emit, get_dataset
from repro.core import build_graph, build_scann


def _tree_bytes(tree) -> int:
    return sum(np.asarray(x).nbytes for x in jax.tree.leaves(tree))


def run(datasets=("sift10m", "openai5m")) -> list[dict]:
    rows = []
    for name in datasets:
        store, _ = get_dataset(name)
        t0 = time.perf_counter()
        g = build_graph(store, m=16, ef_construction=64, seed=0)
        t_h = time.perf_counter() - t0
        t0 = time.perf_counter()
        s = build_scann(store, num_leaves=max(64, store.n // 128), levels=2,
                        seed=0)
        t_s = time.perf_counter() - t0
        rows.append({"name": f"table3/{name}/hnsw",
                     "us_per_call": t_h * 1e6,
                     "build_s": round(t_h, 2),
                     "size_mb": round(_tree_bytes(g) / 1e6, 1)})
        rows.append({"name": f"table3/{name}/scann",
                     "us_per_call": t_s * 1e6,
                     "build_s": round(t_s, 2),
                     "size_mb": round(_tree_bytes(s) / 1e6, 1)})
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    emit(run(), "table3")
