"""Microbenchmark: batch-synchronous frontier engine vs legacy vmapped path.

Measures the tentpole claim directly: the frontier engine advances the
whole query batch one superstep at a time — packed uint32 visited bitsets
(8× less in-flight state than the legacy (Q, n) bool arrays), need-only
chunked candidate scoring with lazy 2-hop expansion for filter-first
strategies, visited-probe dedup instead of a per-hop argsort over the full
2-hop block, and fold-the-pop queue merges — while the vmapped path pays
all of those per query per hop.  Every point is verified **bit-identical**
(ids, dists, all 7 SearchStats counters) before its timing is reported;
a mismatch fails the run.

The full sweep runs on a dedicated container-scale dataset (n=100k — big
enough that the legacy engine's (Q, n) visited state is a real cost, the
regime the paper's 5–10M-row tables live in) with the `SearchParams`
default search knobs (ef=64, beam=64, k=10) at selectivity 0.2.  The
first run builds and caches the graph (benchmarks/.cache, several
minutes); `--tiny` uses a freshly built 8k-row set for CI smoke.

Emits one JSON record to BENCH_frontier.json so the perf trajectory is
tracked run-over-run.

    PYTHONPATH=src python benchmarks/bench_frontier.py [--tiny]
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

from benchmarks.common import _cache
from repro.core import (SearchParams, WorkloadSpec, build_graph,
                        generate_bitmaps, search_batch)
from repro.core.hnsw import HNSWGraph
from repro.core.types import VectorStore
from repro.data import DatasetSpec, make_dataset

STRATEGIES = ("sweeping", "acorn")
BATCHES = (1, 8, 32, 128)
REPS = 3
STAT_FIELDS = ("distance_comps", "filter_checks", "hops",
               "page_accesses_index", "page_accesses_heap", "tmap_lookups",
               "reorder_rows")


def _setup(tiny: bool):
    if tiny:
        spec = DatasetSpec("frontier-tiny", 8_000, 64, "l2", clusters=32)
        store, queries = make_dataset(spec, num_queries=16, seed=0)
        graph = build_graph(store, m=8, ef_construction=48, seed=0)
        return store, jnp.asarray(queries), graph
    spec = DatasetSpec("frontier-bench", 100_000, 128, "l2", clusters=128)
    store, queries = make_dataset(spec, num_queries=128, seed=0)

    def build():
        g = build_graph(store, m=16, ef_construction=64, seed=0)
        return (np.asarray(g.neighbors), np.asarray(g.node_level),
                np.asarray(g.entry_point))

    nb, lv, ep = _cache("graph_frontier_bench_100k", build)
    graph = HNSWGraph(neighbors=jnp.asarray(nb), node_level=jnp.asarray(lv),
                      entry_point=jnp.asarray(ep), m=16)
    return store, jnp.asarray(queries), graph


def _run_point(graph, store, queries, bm, params):
    d, ids, st = search_batch(graph, store, queries, bm, params)
    jax.block_until_ready(ids)                  # compile + warm
    ts = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        d, ids, st = search_batch(graph, store, queries, bm, params)
        jax.block_until_ready(ids)
        ts.append(time.perf_counter() - t0)
    return min(ts), np.asarray(ids), np.asarray(d), st


def run(tiny: bool = False) -> dict:
    store, queries, graph = _setup(tiny)
    sel = 0.2
    bm = generate_bitmaps(store, queries, WorkloadSpec(sel, "none"), seed=1)
    batches = (1, 8) if tiny else BATCHES
    max_hops = 300 if tiny else 3000
    out = {"bench": "frontier", "backend": jax.default_backend(),
           "tiny": tiny, "n": store.n, "dim": store.dim, "sel": sel,
           "params": {"k": 10, "ef_search": 64, "beam_width": 64,
                      "max_hops": max_hops},
           "points": []}
    ok_all = True
    for strat in STRATEGIES:
        base = SearchParams(k=10, strategy=strat, max_hops=max_hops)
        for q in batches:
            qs, bs = queries[:q], bm[:q]
            tv, iv, dv, sv = _run_point(
                graph, store, qs, bs,
                dataclasses.replace(base, graph_exec_mode="vmapped"))
            tf, iff, df, sf = _run_point(
                graph, store, qs, bs,
                dataclasses.replace(base, graph_exec_mode="frontier"))
            identical = bool(
                (iv == iff).all()
                and np.array_equal(dv, df, equal_nan=True)
                and all((np.asarray(getattr(sv, f))
                         == np.asarray(getattr(sf, f))).all()
                        for f in STAT_FIELDS))
            ok_all &= identical
            pt = {"strategy": strat, "batch": q,
                  "vmapped_ms": round(tv * 1e3, 1),
                  "frontier_ms": round(tf * 1e3, 1),
                  "speedup": round(tv / tf, 2),
                  "steps": int(np.asarray(sv.hops).max()),
                  "identical": identical}
            out["points"].append(pt)
            print(f"# {strat} Q={q}: vmapped {pt['vmapped_ms']}ms "
                  f"frontier {pt['frontier_ms']}ms "
                  f"speedup {pt['speedup']}x identical={identical}")
    big = [p["speedup"] for p in out["points"] if p["batch"] >= 32]
    out["min_speedup_q32plus"] = min(big) if big else None
    out["all_identical"] = ok_all
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="small fresh-built dataset, Q ∈ {1, 8} (CI smoke)")
    args = ap.parse_args()
    result = run(tiny=args.tiny)
    line = json.dumps(result)
    # --tiny (CI smoke) must not clobber the tracked full-sweep record:
    # tiny runs write the gitignored .tiny variant
    name = "BENCH_frontier.tiny.json" if args.tiny else "BENCH_frontier.json"
    path = os.path.join(os.path.dirname(__file__), "..", name)
    with open(path, "w") as f:
        f.write(line + "\n")
    print(line)
    assert result["all_identical"], \
        "frontier engine diverged from the vmapped oracle"
    if not result["tiny"]:
        assert result["min_speedup_q32plus"] and \
            result["min_speedup_q32plus"] >= 3.0, (
                "frontier engine under the 3x bar at Q>=32: "
                f"{result['min_speedup_q32plus']}")


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
