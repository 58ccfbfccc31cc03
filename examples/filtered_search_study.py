"""End-to-end mini-reproduction of the paper's core experiment: the
selectivity × correlation grid on one dataset, all methods, with the
system-tax cost model — a small Fig. 9 + Fig. 12 in one run.

    PYTHONPATH=src python examples/filtered_search_study.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (LIBRARY, SYSTEM, SearchParams, WorkloadSpec,
                        build_graph, build_scann, cycle_breakdown,
                        filtered_knn, generate_bitmaps, make_executor,
                        modeled_qps, recall_at_k)
from repro.data import DatasetSpec, make_dataset

SELS = (0.05, 0.2, 0.5)
CORRS = ("high_pos", "none", "negative")
METHODS = ("navix", "sweeping", "iterative_scan", "scann", "adaptive")


def main() -> None:
    spec = DatasetSpec("study", 12_000, 128, "l2", clusters=48)
    store, queries = make_dataset(spec, num_queries=8)
    queries = jnp.asarray(queries)
    graph = build_graph(store, m=16, ef_construction=64, seed=0)
    scann = build_scann(store, num_leaves=96, levels=2, seed=0)

    print(f"{'corr':9s} {'sel':>5s} {'method':15s} {'recall':>6s} "
          f"{'sysQPS':>8s} {'libQPS':>8s}")
    for corr in CORRS:
        for sel in SELS:
            bm = generate_bitmaps(store, queries,
                                  WorkloadSpec(sel, corr), seed=7)
            _, tid = filtered_knn(store, queries, bm, 10)
            p = SearchParams(k=10, ef_search=96, beam_width=512,
                             max_hops=2048, num_leaves_to_search=24)
            for m in METHODS:
                ex = make_executor(m, store, graph=graph, index=scann)
                res = ex.search(queries, bm, p)
                rec = float(np.mean(np.asarray(jax.vmap(
                    lambda f, t: recall_at_k(f, t, 10))(res.ids, tid))))
                qs = modeled_qps(res.stats, store.dim, SYSTEM)
                ql = modeled_qps(res.stats, store.dim, LIBRARY)
                tag = m if m != "adaptive" else f"adaptive>{res.strategy}"
                print(f"{corr:9s} {sel:5.2f} {tag:15s} {rec:6.3f} "
                      f"{qs:8.0f} {ql:8.0f}")
    print("\nThe SYSTEM/LIBRARY QPS columns reproduce Fig. 1's point: the "
          "method ranking differs between the two regimes.")


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
