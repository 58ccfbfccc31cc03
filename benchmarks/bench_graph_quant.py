"""Microbenchmark: SQ8 quantized graph traversal vs full-precision
(DESIGN.md §9) — the measured version of the paper's Table 4 question.

The paper argues (Table 4) that quantization barely helps HNSW in a
page-based engine because neighbor-page traffic dominates; our repro used
to *model* that claim by rescaling counters.  This bench measures it on
the repo's own storage engine: at every (selectivity × batch) grid point
the same sweeping search runs under graph_quant ∈ {none, sq8} with a cold
full-capacity buffer pool, and we record

  * measured heap-page traffic — physical page reads of the traversal's
    row fetches (the f32 "heap" segment vs the 4×-denser SQ8 "qheap"
    shadow, plus the exact rerank's full-width fetches) straight from the
    pool's StorageStats;
  * recall-qualified modeled QPS — SYSTEM cycles from the measured
    counters (quant-aware materialization + rerank surcharge, frontier
    engine_scale) plus the measured miss penalty, with the sq8 point only
    credited when its recall@10 stays within 0.02 of f32 (the rerank's
    recall bound, asserted);
  * wall time per batch, for orientation (CPU interpret mode).

The interesting regime is heap-traffic-bound: traversal touches many more
distinct rows than the rerank re-fetches (low selectivity, small-to-mid
batch), where the 4× page density shows up as a ≥2× physical-read cut and
the miss-side modeled QPS follows.  At large Q the rerank's full-width
fetches claw much of it back — exactly the paper's Table 4 shape.

Emits one JSON record to BENCH_graph_quant.json; `--tiny` (CI smoke,
tools/smoke.sh) uses a fresh small dataset and writes the gitignored
.tiny variant.

    PYTHONPATH=src python benchmarks/bench_graph_quant.py [--tiny]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import (_cache, heap_read_misses,
                               measured_graph_cycles, mean_recall)
from repro.core import (SearchParams, WorkloadSpec, build_graph,
                        filtered_knn, generate_bitmaps, make_executor,
                        quantize_store)
from repro.core.hnsw import HNSWGraph
from repro.data import DatasetSpec, make_dataset
from repro.storage import make_storage_engine

SELS = (0.02, 0.1, 0.3)
BATCHES = (1, 8, 32)
RECALL_SLACK = 0.02              # the rerank's recall bound (DESIGN.md §9)
TRAFFIC_TARGET = 2.0             # ≥2× physical heap-read cut somewhere
QPS_TARGET = 1.5                 # ≥1.5× modeled-QPS gain somewhere
REPS = 2


def _setup(tiny: bool):
    if tiny:
        spec = DatasetSpec("graphquant-tiny", 6_000, 64, "l2", clusters=32)
        store, queries = make_dataset(spec, num_queries=8, seed=0)
        graph = build_graph(store, m=8, ef_construction=48, seed=0)
        return store, jnp.asarray(queries), graph
    spec = DatasetSpec("graphquant-bench", 40_000, 128, "l2", clusters=96)
    store, queries = make_dataset(spec, num_queries=32, seed=0)

    def build():
        g = build_graph(store, m=16, ef_construction=64, seed=0)
        return (np.asarray(g.neighbors), np.asarray(g.node_level),
                np.asarray(g.entry_point))

    nb, lv, ep = _cache("graph_graphquant_bench_40k", build)
    graph = HNSWGraph(neighbors=jnp.asarray(nb), node_level=jnp.asarray(lv),
                      entry_point=jnp.asarray(ep), m=16)
    return store, jnp.asarray(queries), graph


def run(tiny: bool = False) -> dict:
    store, queries, graph = _setup(tiny)
    store = quantize_store(store)
    sels = (SELS[1],) if tiny else SELS
    batches = (queries.shape[0],) if tiny else BATCHES
    max_hops = 500 if tiny else 3000
    base = SearchParams(k=10, ef_search=64, beam_width=256,
                        strategy="sweeping", max_hops=max_hops)
    clock = 3.0e9
    out = {"bench": "graph_quant", "backend": jax.default_backend(),
           "tiny": tiny, "n": store.n, "dim": store.dim,
           "params": {"k": base.k, "ef_search": base.ef_search,
                      "beam_width": base.beam_width, "max_hops": max_hops},
           "points": []}
    executors = {}
    for quant in ("none", "sq8"):
        method = "sweeping" if quant == "none" else "sweeping_sq8"
        eng = make_storage_engine(store, graph=graph, capacity_frac=1.0)
        executors[quant] = (make_executor(method, store, graph=graph,
                                          storage=eng), eng)
    for sel in sels:
        bm_full = generate_bitmaps(store, queries, WorkloadSpec(sel, "none"),
                                   seed=3)
        _, tid = filtered_knn(store, queries, bm_full, base.k)
        for q in batches:
            qs, bs, tq = queries[:q], bm_full[:q], tid[:q]
            point = {"sel": sel, "batch": q}
            cyc, rec = {}, {}
            for quant in ("none", "sq8"):
                ex, eng = executors[quant]
                p = dataclasses.replace(base, graph_quant=quant)
                eng.reset_cold()
                res = ex.search(qs, bs, p)
                jax.block_until_ready(res.ids)
                ts = []
                for _ in range(REPS):        # timed reps: accounting off
                    ex_t = make_executor(
                        "sweeping" if quant == "none" else "sweeping_sq8",
                        store, graph=graph)
                    t0 = time.perf_counter()
                    r2 = ex_t.search(qs, bs, p)
                    jax.block_until_ready(r2.ids)
                    ts.append(time.perf_counter() - t0)
                rec[quant] = mean_recall(res.ids, tq, base.k)
                cyc[quant] = measured_graph_cycles(res, p, q, store.dim)
                point[quant] = {
                    "recall": round(rec[quant], 4),
                    "wall_ms": round(min(ts) * 1e3, 1),
                    "heap_reads": heap_read_misses(res),
                    "heap_logical": int(
                        res.storage.logical.get("heap", 0)
                        + res.storage.logical.get("qheap", 0)),
                    "reorder_rows": int(
                        np.asarray(res.stats.reorder_rows).sum()),
                    "mcycles_per_query": round(cyc[quant] / 1e6, 3),
                    "modeled_qps": round(clock / cyc[quant], 1),
                }
            point["heap_read_reduction"] = round(
                point["none"]["heap_reads"]
                / max(point["sq8"]["heap_reads"], 1), 2)
            point["qps_gain"] = round(cyc["none"] / cyc["sq8"], 2)
            point["recall_qualified"] = bool(
                rec["sq8"] >= rec["none"] - RECALL_SLACK)
            out["points"].append(point)
            print(f"# sel={sel} Q={q}: heap reads "
                  f"{point['none']['heap_reads']}→"
                  f"{point['sq8']['heap_reads']} "
                  f"({point['heap_read_reduction']}x), modeled QPS gain "
                  f"{point['qps_gain']}x, recall "
                  f"{point['none']['recall']}→{point['sq8']['recall']}")
    qualified = [p for p in out["points"] if p["recall_qualified"]]
    out["all_recall_qualified"] = len(qualified) == len(out["points"])
    out["best_heap_read_reduction"] = max(
        (p["heap_read_reduction"] for p in qualified), default=0.0)
    out["best_qps_gain"] = max(
        (p["qps_gain"] for p in qualified), default=0.0)
    out["heap_bound_points"] = [
        {"sel": p["sel"], "batch": p["batch"],
         "heap_read_reduction": p["heap_read_reduction"],
         "qps_gain": p["qps_gain"]}
        for p in qualified
        if p["heap_read_reduction"] >= TRAFFIC_TARGET
        and p["qps_gain"] >= QPS_TARGET]
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="small fresh-built dataset, 1 grid point (CI)")
    args = ap.parse_args()
    result = run(tiny=args.tiny)
    line = json.dumps(result)
    # --tiny (CI smoke) must not clobber the tracked full-grid record
    name = "BENCH_graph_quant.tiny.json" if args.tiny \
        else "BENCH_graph_quant.json"
    path = os.path.join(os.path.dirname(__file__), "..", name)
    with open(path, "w") as f:
        f.write(line + "\n")
    print(line)
    assert result["all_recall_qualified"], (
        "sq8+rerank recall fell more than "
        f"{RECALL_SLACK} below f32 at some grid point")
    if not result["tiny"]:
        assert result["heap_bound_points"], (
            "no recall-qualified grid point reached "
            f"{TRAFFIC_TARGET}x measured heap-read reduction AND "
            f"{QPS_TARGET}x modeled-QPS gain: best "
            f"{result['best_heap_read_reduction']}x traffic, "
            f"{result['best_qps_gain']}x QPS")


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
