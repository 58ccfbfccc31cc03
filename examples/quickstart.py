"""Quickstart: filtered vector search, five strategies, one page.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (SYSTEM, SearchParams, WorkloadSpec, build_graph,
                        build_scann, cycle_breakdown, filtered_knn,
                        generate_bitmaps, make_executor, recall_at_k,
                        stats_table_row)
from repro.data import DatasetSpec, make_dataset


def main() -> None:
    print("== 1. dataset (clustered, Table-2-shaped) ==")
    spec = DatasetSpec("quickstart", 10_000, 96, "l2", clusters=32)
    store, queries = make_dataset(spec, num_queries=8)
    queries = jnp.asarray(queries)
    print(f"   {store.n} vectors, d={store.dim}, {queries.shape[0]} queries")

    print("== 2. indexes ==")
    graph = build_graph(store, m=16, ef_construction=64, seed=0)
    scann = build_scann(store, num_leaves=96, levels=2, seed=0)
    print(f"   HNSW: {graph.num_levels} levels | ScaNN: "
          f"{scann.num_leaves} leaves")

    print("== 3. workload: 10% selectivity, medium positive correlation ==")
    ws = WorkloadSpec(selectivity=0.10, correlation="med_pos")
    bitmaps = generate_bitmaps(store, queries, ws, seed=1)
    _, true_ids = filtered_knn(store, queries, bitmaps, 10)

    print("== 4. strategies behind the one executor API ==")
    p = SearchParams(k=10, ef_search=96, beam_width=512, max_hops=2048,
                     num_leaves_to_search=24, reorder_factor=4)
    print(f"   {'method':16s} {'recall':>6s} {'dist':>7s} {'filter':>8s} "
          f"{'hops':>6s} {'pages':>7s} {'Mcycles':>8s}")
    for method in ("sweeping", "acorn", "navix", "iterative_scan", "scann",
                   "bruteforce"):
        ex = make_executor(method, store, graph=graph, index=scann)
        res = ex.search(queries, bitmaps, p)
        rec = float(np.mean(np.asarray(jax.vmap(
            lambda f, t: recall_at_k(f, t, 10))(res.ids, true_ids))))
        row = stats_table_row(res.stats)
        cyc = cycle_breakdown(res.stats, store.dim, SYSTEM)["total"] / 1e6
        print(f"   {method:16s} {rec:6.3f} {row['distance_comps']:7.0f} "
              f"{row['filter_checks']:8.0f} {row['hops']:6.0f} "
          f"{row['page_accesses_index']+row['page_accesses_heap']:7.0f}"
              f" {cyc:8.2f}")
    print("\nNote the paper's Table-6 pattern: filter-first (acorn/navix) "
          "trades filter checks for distance computations; ScaNN batches "
          "both.")

    print("== 5. the system-aware adaptive planner ==")
    planner = make_executor("adaptive", store, graph=graph, index=scann)
    for sel in (0.01, 0.10, 0.8):
        bm = generate_bitmaps(store, queries, WorkloadSpec(sel, "none"),
                              seed=2)
        res = planner.search(queries, bm, p)
        preds = {m: round(c / 1e6, 2)
                 for m, c in res.plan.predicted_cycles.items()}
        print(f"   sel={sel:<5} -> chose {res.strategy:15s} "
              f"(predicted Mcycles: {preds})")
    print("\nThe planner picks the cheapest recall-feasible strategy per "
          "batch from bitmap popcounts + a leaf-probe correlation proxy "
          "(DESIGN.md \u00a76).")


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
