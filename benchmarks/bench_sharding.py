"""Benchmark: mesh-sharded graph + storage tiers (DESIGN.md §13) — the
paper's single-node engine scaled out by partitioning adjacency, heap
pages, and the SQ8 shadow store by row range across shards.

The shard-count sweep runs the SAME sweeping search at S ∈ {1, 2, 4, 8}
lockstep shards (beam_exchange_interval=1) over a streamed 1M×768 ip
dataset (the paper's openai operating point rescaled to the ≥1M-row
floor) and records, per point:

  * recall@10 against exact filtered kNN — lockstep results are
    bit-identical across shard counts by construction (owner-masked
    pmin/pmax reductions SELECT the owner's value), asserted on ids;
  * aggregated modeled QPS from `costmodel.sharded_cycle_summary`: the
    single-device cycle total parallelizes 1/S, plus the beam-exchange
    collective-roofline term (bytes × collective_per_byte) and the
    straggler term (max−mean of per-shard measured miss penalties);
  * beam-exchange collective bytes per query (lockstep: 8 B per scored
    candidate moved ~2·(S−1)/S times by the ring all-reduce);
  * per-shard buffer-pool hit rates from the ShardedStorageAccountant
    replay (each shard pools capacity_frac/S — the aggregate page budget
    stays fixed as S sweeps).

A drift-mode sweep (S=4, E ∈ {1, 2, 4, 8}) records how recall decays and
collective bytes shrink as supersteps between top-ef beam exchanges grow.

Acceptance (asserted on the full grid): ≥2.5× aggregated modeled QPS at
8 shards vs 1 at equal recall (equal is free — the ids are identical).

`--tiny` (CI smoke, tools/smoke.sh) runs the openai5m container dataset
through the cached `get_sharded_executor` path and writes the gitignored
.tiny variant.  `--xl` is the paper-scale 5M×768 point: the serving
store is built f32-free (`make_dataset_streamed(..., f32=False)` — only
the int8 shadow is materialized; traversal is SQ8-only with
sq8_rerank=False), but the graph build and the exact ground truth still
materialize f32 rows transiently, so it is NOT run in CI —
document-and-run-by-hand only.

    PYTHONPATH=src python benchmarks/bench_sharding.py [--tiny|--xl]
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

from benchmarks.common import (_cache, get_bitmaps, get_dataset, get_graph,
                               get_sharded_executor, get_sharded_storage,
                               mean_recall)
from repro.core import (SearchParams, WorkloadSpec, filtered_knn,
                        generate_bitmaps)
from repro.core import costmodel
from repro.core.distributed import (ShardedGraphExecutor,
                                    make_sharded_storage)
from repro.core.hnsw import HNSWGraph, build_graph_blocked
from repro.data import DatasetSpec, make_dataset_streamed
from repro.storage import make_storage_engine

SHARDS = (1, 2, 4, 8)
E_SWEEP = (1, 2, 4, 8)           # drift-mode exchange intervals at S=4
QPS_TARGET = 2.5                 # ≥2.5× modeled QPS at 8 shards vs 1
CAPACITY_FRAC = 0.5              # aggregate pool budget over the sweep

FULL_SPEC = DatasetSpec("openai1m", 1_000_000, 768, "ip", clusters=64)
XL_SPEC = DatasetSpec("openai5m_xl", 5_000_000, 768, "ip", clusters=64)


def _full_setup(spec: DatasetSpec, num_queries: int, f32: bool = True):
    """Streamed dataset + blocked-built graph (graph disk-cached)."""
    t0 = time.perf_counter()
    store, queries = make_dataset_streamed(spec, num_queries=num_queries,
                                           seed=0, f32=f32)
    print(f"# dataset {spec.name} {spec.n}x{spec.dim} streamed in "
          f"{time.perf_counter() - t0:.0f}s (f32={f32})")

    def build():
        src = store
        if not f32:
            # the builder needs real f32 rows; materialize them once,
            # transiently (this is why --xl never runs in CI)
            src, _ = make_dataset_streamed(spec, num_queries=1, seed=0,
                                           f32=True, quantize=False)
        g = build_graph_blocked(src, m=16, ef_construction=32, seed=0)
        return (np.asarray(g.neighbors), np.asarray(g.node_level),
                np.asarray(g.entry_point))

    t0 = time.perf_counter()
    nb, lv, ep = _cache(f"graph_{spec.name}_stream_m16", build)
    print(f"# graph ready in {time.perf_counter() - t0:.0f}s")
    graph = HNSWGraph(neighbors=jnp.asarray(nb), node_level=jnp.asarray(lv),
                      entry_point=jnp.asarray(ep), m=16)
    return store, jnp.asarray(queries), graph


def _shadow_ground_truth(store, queries, bm, k: int):
    """Exact filtered kNN over the DEQUANTIZED shadow, blockwise — the
    f32-free (--xl) ground truth, never materializing the (n, d) f32."""
    q = np.asarray(queries, np.float32)
    scale = np.asarray(store.q_scale)
    mean = np.asarray(store.q_mean)
    qv = np.asarray(store.q_vectors)
    words = np.asarray(bm)
    n, block = store.n, 262_144
    best_d = np.full((q.shape[0], k), np.inf, np.float32)
    best_i = np.full((q.shape[0], k), -1, np.int64)
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        x = qv[lo:hi].astype(np.float32) * scale + mean
        if store.metric == "ip":
            d = -(q @ x.T)
        else:
            d = ((x * x).sum(-1)[None, :] - 2.0 * (q @ x.T)
                 + (q * q).sum(-1)[:, None])
        ids = np.arange(lo, hi)
        passing = (words[:, ids // 32] >> (ids % 32)) & 1
        d = np.where(passing.astype(bool), d, np.inf)
        cat_d = np.concatenate([best_d, d], axis=1)
        cat_i = np.concatenate(
            [best_i, np.broadcast_to(ids, d.shape)], axis=1)
        top = np.argpartition(cat_d, k - 1, axis=1)[:, :k]
        best_d = np.take_along_axis(cat_d, top, axis=1)
        best_i = np.take_along_axis(cat_i, top, axis=1)
    order = np.argsort(best_d, axis=1)
    return jnp.asarray(np.take_along_axis(best_i, order, axis=1))


def _point(ex, accountant, queries, bm, tid, p, num_shards):
    """One cold-pool measured grid point → bench record."""
    if accountant is not None:
        accountant.reset_cold()
    t0 = time.perf_counter()
    res = ex.search(queries, bm, p)
    jax.block_until_ready(res.ids)
    wall = time.perf_counter() - t0
    q = int(queries.shape[0])
    per_shard = accountant.last_per_shard if accountant is not None else None
    summary = costmodel.sharded_cycle_summary(
        res.stats, p, ex.store.dim, num_shards,
        graph_quant=p.graph_quant, per_shard_storage=per_shard, batch_q=q)
    rec = {"shards": num_shards, "E": p.beam_exchange_interval,
           "recall": round(mean_recall(res.ids, tid, p.k), 4),
           "wall_ms": round(wall * 1e3, 1),
           "hops": round(float(np.asarray(res.stats.hops).mean()), 1),
           "distance_comps": round(
               float(np.asarray(res.stats.distance_comps).mean()), 1),
           "collective_bytes_per_query": round(
               summary["collective_bytes"], 1),
           "mcycles_per_query": round(
               summary["cycles_per_query"] / 1e6, 3),
           "modeled_qps": round(summary["modeled_qps"], 1),
           "straggler_mcycles": round(
               summary["straggler_cycles"] / 1e6, 4)}
    if per_shard is not None:
        rec["pool_hit_rates"] = [round(s.hit_rate, 4) for s in per_shard]
        rec["pool_miss_pages"] = [int(s.miss_total) for s in per_shard]
    return rec, np.asarray(res.ids)


def _shard_sweep(store, graph, queries, bm, tid, p, shards,
                 capacity_frac, f32=True) -> list[dict]:
    """Lockstep shard-count sweep; asserts bit-identical ids across S."""
    rows, ref_ids = [], None
    for S in shards:
        engines = [make_storage_engine(store, graph=graph,
                                       capacity_frac=capacity_frac / S)
                   for _ in range(S)]
        acct = make_sharded_storage(engines, store.n)
        ex = ShardedGraphExecutor(graph, store, S, strategy=p.strategy,
                                  graph_quant=p.graph_quant, storage=acct,
                                  f32=f32)
        rec, ids = _point(ex, acct, queries, bm, tid, p, S)
        if ref_ids is None:
            ref_ids = ids
        else:
            assert np.array_equal(ids, ref_ids), (
                f"S={S} ids diverge from S={shards[0]} — lockstep "
                "shard-count invariance broken")
        rec["ids_match_base"] = True
        rows.append(rec)
        print(f"# S={S}: recall {rec['recall']}, modeled QPS "
              f"{rec['modeled_qps']}, collective "
              f"{rec['collective_bytes_per_query']} B/q, pool hit rates "
              f"{rec.get('pool_hit_rates')}")
        del ex, acct, engines
    return rows


def _drift_sweep(store, graph, queries, bm, tid, p, f32=True) -> list[dict]:
    """E-sweep at S=4: recall decay vs collective-byte savings."""
    S = 4
    ex = ShardedGraphExecutor(graph, store, S, strategy=p.strategy,
                              graph_quant=p.graph_quant, f32=f32)
    rows = []
    for E in E_SWEEP:
        pe = dataclasses.replace(p, beam_exchange_interval=E)
        rec, _ = _point(ex, None, queries, bm, tid, pe, S)
        rows.append(rec)
        print(f"# drift S={S} E={E}: recall {rec['recall']}, collective "
              f"{rec['collective_bytes_per_query']} B/q")
    del ex
    return rows


def run(tiny: bool = False, xl: bool = False) -> dict:
    if tiny:
        name = "openai5m"
        store, queries = get_dataset(name)
        graph = get_graph(name)
        bm = get_bitmaps(name, 0.1, "none")
        _, tid = filtered_knn(store, queries, bm, 10)
        p = SearchParams(k=10, ef_search=64, beam_width=256,
                         strategy="sweeping", max_hops=500)
        rows, ref_ids = [], None
        for S in (1, 2, 4):
            # the cached-executor satellite path: storage-free instance
            # is cached per (dataset, S, strategy, quant), the pooled one
            # rides a fresh accountant
            get_sharded_executor(name, S)
            acct = get_sharded_storage(name, S,
                                       capacity_frac=CAPACITY_FRAC)
            ex = get_sharded_executor(name, S, storage=acct)
            rec, ids = _point(ex, acct, queries, bm, tid, p, S)
            if ref_ids is None:
                ref_ids = ids
            else:
                assert np.array_equal(ids, ref_ids), \
                    f"S={S} ids diverge (lockstep invariance)"
            rec["ids_match_base"] = True
            rows.append(rec)
            print(f"# S={S}: recall {rec['recall']}, modeled QPS "
                  f"{rec['modeled_qps']}")
        drift = _drift_sweep(store, graph, queries, bm, tid, p)
    else:
        spec = XL_SPEC if xl else FULL_SPEC
        f32 = not xl            # --xl: f32-free store, SQ8-only traversal
        store, queries, graph = _full_setup(spec, num_queries=8, f32=f32)
        bm = generate_bitmaps(store, queries, WorkloadSpec(0.2, "none"),
                              seed=11)
        if f32:
            _, tid = filtered_knn(store, queries, bm, 10)
        else:
            tid = _shadow_ground_truth(store, queries, bm, 10)
        p = SearchParams(k=10, ef_search=128, beam_width=512,
                         strategy="sweeping", max_hops=1500,
                         graph_quant="sq8", sq8_rerank=f32)
        rows = _shard_sweep(store, graph, queries, bm, tid, p, SHARDS,
                            CAPACITY_FRAC, f32=f32)
        drift = _drift_sweep(store, graph, queries, bm, tid, p, f32=f32)

    qps = {r["shards"]: r["modeled_qps"] for r in rows}
    gain = qps[max(qps)] / qps[min(qps)]
    out = {"bench": "sharding", "backend": jax.default_backend(),
           "tiny": tiny, "xl": xl, "n": store.n, "dim": store.dim,
           "params": {"k": p.k, "ef_search": p.ef_search,
                      "beam_width": p.beam_width, "max_hops": p.max_hops,
                      "strategy": p.strategy, "graph_quant": p.graph_quant,
                      "sel": 0.1 if tiny else 0.2},
           "capacity_frac": CAPACITY_FRAC,
           "shard_sweep": rows, "drift_sweep": drift,
           "max_shards": max(qps),
           "qps_gain_at_max_shards": round(gain, 2),
           "all_ids_match_base": all(r["ids_match_base"] for r in rows)}
    print(f"# modeled QPS gain at S={out['max_shards']}: "
          f"{out['qps_gain_at_max_shards']}x (target {QPS_TARGET}x on "
          "the full grid)")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="container dataset, 3 shard points (CI smoke)")
    ap.add_argument("--xl", action="store_true",
                    help="5M x 768 f32-free point (not run in CI; the "
                         "graph build transiently materializes f32 rows)")
    args = ap.parse_args()
    result = run(tiny=args.tiny, xl=args.xl)
    line = json.dumps(result)
    # --tiny (CI smoke) must not clobber the tracked full-grid record
    name = "BENCH_sharding.tiny.json" if args.tiny else (
        "BENCH_sharding.xl.json" if args.xl else "BENCH_sharding.json")
    path = os.path.join(os.path.dirname(__file__), "..", name)
    with open(path, "w") as f:
        f.write(line + "\n")
    print(line)
    assert result["all_ids_match_base"], "shard-count invariance broken"
    if not result["tiny"]:
        assert result["qps_gain_at_max_shards"] >= QPS_TARGET, (
            f"modeled QPS gain at {result['max_shards']} shards "
            f"{result['qps_gain_at_max_shards']}x < {QPS_TARGET}x")


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
