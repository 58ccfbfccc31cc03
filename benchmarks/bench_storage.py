"""Storage-engine bench: the paper's buffer-manager claims, measured
(DESIGN.md §8).

Four sections over the fig_planner workload (sift10m-shaped):

  cold_warm     — identical batch served twice through each executor with
                  a pooled StorageEngine: cold pass misses every
                  first-touch page, warm pass must hit ~100 %.
  capacity      — pool-capacity sweep under the centroid-routed queue:
                  hit rate vs capacity fraction (the shared-buffers
                  sizing curve).
  counters      — measured vs predicted page counters at one grid point:
                  analytic SearchStats vs `predict_counters` vs the
                  pool-measured logical accesses.
  routing       — the serving-layer batch policy (ROADMAP item): a
                  64-request queue dispatched in batches of 16, FIFO
                  arrival order vs clustered by nearest ScaNN centroid
                  (serving/rag.py policy).  Reports the buffer-pool
                  hit-rate lift; asserts warm centroid-routed hit rate
                  > 0.5.
  planner       — fig_planner's regret sweep re-run in the warm-serving
                  regime with warm-cache-aware costs on BOTH sides:
                  predictions carry `cache_miss_penalty(pool_state)`,
                  measured cycles carry `measured_miss_penalty` from the
                  pools' observed misses.  Asserts planner regret ≤ 1.5
                  at recall ≥ 0.9 at every grid point.

Emits one JSON record to BENCH_storage.json.

    PYTHONPATH=src python benchmarks/bench_storage.py [--tiny] [--ds sift10m]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import (BENCH_DATASETS, emit, get_bitmaps,
                               get_dataset, get_executor, get_scann,
                               get_storage_engine, ground_truth, mean_recall)
from repro.core import (SYSTEM, SearchParams, WorkloadSpec, cycle_breakdown,
                        engine_scale, generate_bitmaps, index_shape,
                        measured_miss_penalty, predict_counters,
                        stats_table_row)
from repro.data import make_dataset
from repro.serving.rag import nearest_centroid

SELS = (0.01, 0.05, 0.2, 0.5, 0.9)
FIXED = ("bruteforce", "sweeping", "navix", "iterative_scan", "scann")
RECALL_FLOOR = 0.9
REGRET_TARGET = 1.5
WARM_HIT_TARGET = 0.5


def _params(k: int = 10) -> SearchParams:
    # fig_planner's balanced config (benchmarks/fig_planner.py)
    return SearchParams(k=k, ef_search=128, beam_width=512, max_hops=3000,
                        num_leaves_to_search=32, reorder_factor=4,
                        scann_page_accounting="batch",
                        batch_tuples=max(64, k * 8), max_rounds=16)


def _per_query_params(k: int = 10) -> SearchParams:
    import dataclasses
    return dataclasses.replace(_params(k),
                               scann_page_accounting="per_query")


# ---------------------------------------------------------------------------
# cold vs warm
# ---------------------------------------------------------------------------

def bench_cold_warm(ds: str, rows: list) -> dict:
    store, queries = get_dataset(ds)
    bm = get_bitmaps(ds, 0.2, "none")
    p = _params()
    out = {}
    for m in ("scann", "sweeping", "bruteforce"):
        eng = get_storage_engine(ds, m, capacity_frac=1.0)
        ex = get_executor(ds, m, storage=eng)
        cold = ex.search(queries, bm, p).storage
        warm = ex.search(queries, bm, p).storage
        eng.reset_cold()
        recold = ex.search(queries, bm, p).storage
        out[m] = {"cold_hit_rate": round(cold.hit_rate, 4),
                  "warm_hit_rate": round(warm.hit_rate, 4),
                  "cold_misses": cold.miss_total,
                  "warm_misses": warm.miss_total,
                  "recold_misses": recold.miss_total}
        rows.append({"name": f"bench_storage/{ds}/cold_warm/{m}",
                     "us_per_call": 0.0, **out[m]})
        assert warm.miss_total == 0, (m, "warm pass must be fully resident")
        assert recold.miss_total == cold.miss_total, (m, "cold reset")
    return out


# ---------------------------------------------------------------------------
# serving-layer batch routing (centroid vs FIFO) + capacity sweep
# ---------------------------------------------------------------------------

def _routed_queue(ds: str, nreq: int, sel: float, seed: int = 1,
                  copies: int = 4):
    """A request queue larger than one batch, with hot-topic structure:
    nreq/copies base queries, each arriving `copies` times with small
    jitter (heavy-traffic serving — many users ask similar things), in a
    shuffled arrival order.  FIFO batching interleaves the topics;
    centroid routing regroups them.  Returns (queries, bitmaps,
    nearest-centroid keys, arrival order)."""
    spec = BENCH_DATASETS[ds]
    nbase = max(1, nreq // copies)
    # seed=0 reproduces EXACTLY the store the cached executors/index were
    # built on (make_dataset's store draw precedes and is independent of
    # num_queries), so the queue is clustered w.r.t. the indexed centroids
    store, base = make_dataset(spec, num_queries=nbase, seed=0)
    rng = np.random.RandomState(seed)
    reps = [np.asarray(base)]
    scale = 0.05 * float(np.abs(np.asarray(base)).mean())
    for _ in range(copies - 1):
        reps.append(np.asarray(base)
                    + scale * rng.randn(*base.shape).astype(np.float32))
    queries = jnp.asarray(np.concatenate(reps, axis=0)[:nreq])
    bm = generate_bitmaps(store, queries, WorkloadSpec(sel, "none"),
                          seed=seed + 7)
    idx = get_scann(ds)
    keys = np.asarray(nearest_centroid(idx, queries))
    order = rng.permutation(queries.shape[0])             # arrival order
    return queries, bm, keys, order


def _run_queue(ds: str, queries, bm, dispatch: np.ndarray, batch: int,
               capacity_frac: float, p: SearchParams) -> dict:
    """Dispatch the queue through a pooled ScannExecutor in `batch`-sized
    groups (two epochs: cold, then warm) and return pool telemetry."""
    eng = get_storage_engine(ds, "scann", capacity_frac=capacity_frac)
    ex = get_executor(ds, "scann", storage=eng)
    epochs = []
    for _ in range(2):
        h = m = 0
        for s in range(0, len(dispatch), batch):
            sel_ids = jnp.asarray(dispatch[s:s + batch])
            st = ex.search(queries[sel_ids], bm[sel_ids], p).storage
            h += sum(st.hits.values())
            m += sum(st.misses.values())
        epochs.append({"hits": h, "misses": m,
                       "hit_rate": round(h / max(h + m, 1), 4)})
    return {"cold_epoch": epochs[0], "warm_epoch": epochs[1],
            "capacity_pages": eng.pool.capacity,
            "total_pages": eng.total_pages}


def bench_routing(ds: str, rows: list, nreq: int, batch: int = 16,
                  capacity_frac: float = 0.25) -> dict:
    queries, bm, keys, order = _routed_queue(ds, nreq, sel=0.2)
    p = _per_query_params()       # pool sees every query's opens (§5)
    fifo = _run_queue(ds, queries, bm, order, batch, capacity_frac, p)
    routed = np.argsort(keys[order], kind="stable")
    cent = _run_queue(ds, queries, bm, order[routed], batch, capacity_frac,
                      p)
    lift = {
        "cold": round(cent["cold_epoch"]["hit_rate"]
                      - fifo["cold_epoch"]["hit_rate"], 4),
        "warm": round(cent["warm_epoch"]["hit_rate"]
                      - fifo["warm_epoch"]["hit_rate"], 4),
    }
    out = {"nreq": nreq, "batch": batch, "capacity_frac": capacity_frac,
           "fifo": fifo, "centroid": cent, "hit_rate_lift": lift}
    rows.append({"name": f"bench_storage/{ds}/routing/centroid_vs_fifo",
                 "us_per_call": 0.0,
                 "fifo_warm": fifo["warm_epoch"]["hit_rate"],
                 "centroid_warm": cent["warm_epoch"]["hit_rate"],
                 "lift_warm": lift["warm"], "lift_cold": lift["cold"]})
    assert cent["warm_epoch"]["hit_rate"] > WARM_HIT_TARGET, (
        f"warm centroid-routed hit rate "
        f"{cent['warm_epoch']['hit_rate']} <= {WARM_HIT_TARGET}")
    assert lift["cold"] > 0, "centroid routing must lift cold hit rate"
    return out


def bench_capacity(ds: str, rows: list, nreq: int,
                   fracs=(0.05, 0.15, 0.3, 0.6, 1.0)) -> list[dict]:
    queries, bm, keys, order = _routed_queue(ds, nreq, sel=0.2)
    p = _per_query_params()
    dispatch = order[np.argsort(keys[order], kind="stable")]
    sweep = []
    for frac in fracs:
        r = _run_queue(ds, queries, bm, dispatch, 16, frac, p)
        sweep.append({"capacity_frac": frac,
                      "capacity_pages": r["capacity_pages"],
                      "cold_hit_rate": r["cold_epoch"]["hit_rate"],
                      "warm_hit_rate": r["warm_epoch"]["hit_rate"]})
        rows.append({"name": f"bench_storage/{ds}/capacity/frac={frac}",
                     "us_per_call": 0.0, **sweep[-1]})
    # hit rate is monotone-ish in capacity; assert the envelope
    assert sweep[-1]["warm_hit_rate"] >= sweep[0]["warm_hit_rate"]
    return sweep


# ---------------------------------------------------------------------------
# measured vs predicted page counters
# ---------------------------------------------------------------------------

def bench_counters(ds: str, rows: list, sel: float = 0.2) -> dict:
    store, queries = get_dataset(ds)
    bm = get_bitmaps(ds, sel, "none")
    p = _per_query_params()
    shape = index_shape(store, get_scann(ds), graph_m=16)
    out = {}
    for m in ("scann", "sweeping", "bruteforce"):
        eng = get_storage_engine(ds, m, capacity_frac=1.0)
        ex = get_executor(ds, m, storage=eng)
        res = ex.search(queries, bm, p)
        srow = stats_table_row(res.stats)
        pred = predict_counters(m, shape, p, sel)
        q = queries.shape[0]
        meas = {"page_accesses_index": float(res.storage.index_pages.mean()),
                "page_accesses_heap": float(res.storage.heap_pages.mean())}
        out[m] = {
            "analytic_index": srow["page_accesses_index"],
            "analytic_heap": srow["page_accesses_heap"],
            "measured_index": meas["page_accesses_index"],
            "measured_heap": meas["page_accesses_heap"],
            "predicted_index": round(pred["page_accesses_index"], 1),
            "predicted_heap": round(pred["page_accesses_heap"], 1),
            "pool_hit_rate": round(res.storage.hit_rate, 4),
        }
        rows.append({"name": f"bench_storage/{ds}/counters/{m}",
                     "us_per_call": 0.0, **out[m]})
        # measured logical never exceeds analytic; exact for scann/seqscan
        assert meas["page_accesses_heap"] <= srow["page_accesses_heap"] + 1e-9
        if m in ("scann", "bruteforce"):
            assert meas["page_accesses_heap"] == srow["page_accesses_heap"]
            assert meas["page_accesses_index"] == srow["page_accesses_index"]
    return out


# ---------------------------------------------------------------------------
# warm-cache-aware planner regret (fig_planner grid, storage-aware)
# ---------------------------------------------------------------------------

def bench_planner(ds: str, rows: list, sels=SELS,
                  capacity_frac: float = 0.5) -> dict:
    store, queries = get_dataset(ds)
    p = _params()
    q_batch = queries.shape[0]
    execs = {}
    for m in FIXED:
        execs[m] = get_executor(ds, m, storage=get_storage_engine(
            ds, m, capacity_frac=capacity_frac))
    execs["adaptive"] = get_executor(ds, "adaptive",
                                     storage=get_storage_engine(
                                         ds, "adaptive",
                                         capacity_frac=capacity_frac))
    # steady-state warm serving: every pool is warmed once before the
    # measured sweep (the cold transient is the cold_warm section's story)
    warm_bm = get_bitmaps(ds, sels[0], "none")
    for ex in execs.values():
        jax.block_until_ready(ex.search(queries, warm_bm, p).ids)
    grid = []
    for sel in sels:
        bm = get_bitmaps(ds, sel, "none")
        _, tid = ground_truth(ds, sel, "none", p.k)
        cyc, rec, chosen = {}, {}, {}
        for m, ex in execs.items():
            t0 = time.perf_counter()
            res = ex.search(queries, bm, p)
            jax.block_until_ready(res.ids)
            wall = (time.perf_counter() - t0) / q_batch * 1e6
            # warm-cache-aware currency: engine-scaled modeled cycles +
            # the pool's MEASURED miss penalty for this batch
            cyc[m] = cycle_breakdown(
                res.stats, store.dim, SYSTEM,
                engine_scale(res.strategy, p, q_batch))["total"] + \
                measured_miss_penalty(res.storage, q_batch, SYSTEM)
            rec[m] = mean_recall(res.ids, tid, p.k)
            chosen[m] = res.strategy
            if m == "adaptive":
                rows.append({
                    "name": f"bench_storage/{ds}/planner/sel={sel}",
                    "us_per_call": wall, "chosen": res.strategy,
                    "recall": round(rec[m], 3),
                    "mcycles": round(cyc[m] / 1e6, 3)})
        qualified = {m: cyc[m] for m in FIXED if rec[m] >= RECALL_FLOOR}
        pool = qualified or {m: cyc[m] for m in FIXED}
        best = min(pool, key=pool.get)
        point = {"sel": sel, "best_fixed": best,
                 "chosen": chosen["adaptive"],
                 "recall": {m: round(rec[m], 3) for m in rec},
                 "regret": {}}
        for m in (*FIXED, "adaptive"):
            r = cyc[m] / cyc[best]
            point["regret"][m] = round(r, 3) if rec[m] >= RECALL_FLOOR \
                else "inf"
        grid.append(point)
    regrets = [pt["regret"]["adaptive"] for pt in grid]
    max_regret = math.inf if "inf" in regrets else max(regrets)
    out = {"grid": grid, "max_regret_adaptive":
           (round(max_regret, 3) if math.isfinite(max_regret) else "inf"),
           "recall_floor": RECALL_FLOOR, "regret_target": REGRET_TARGET}
    assert all(pt["recall"]["adaptive"] >= RECALL_FLOOR for pt in grid), \
        "planner fell below the recall floor under warm-cache-aware costs"
    assert math.isfinite(max_regret) and max_regret <= REGRET_TARGET, (
        f"warm-cache-aware planner regret {max_regret} > {REGRET_TARGET}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="2-point CI configuration (smoke.sh)")
    ap.add_argument("--ds", default="sift10m")
    args = ap.parse_args()
    nreq = 32 if args.tiny else 64
    sels = (0.05, 0.5) if args.tiny else SELS
    fracs = (0.15, 1.0) if args.tiny else (0.05, 0.15, 0.3, 0.6, 1.0)
    rows: list[dict] = []
    rec = {"bench": "storage", "dataset": args.ds, "tiny": args.tiny,
           "cold_warm": bench_cold_warm(args.ds, rows),
           "capacity": bench_capacity(args.ds, rows, nreq, fracs),
           "counters": bench_counters(args.ds, rows),
           "routing": bench_routing(args.ds, rows, nreq),
           "planner": bench_planner(args.ds, rows, sels)}
    # --tiny (CI smoke) must not clobber the tracked full-grid record
    name = "BENCH_storage.tiny.json" if args.tiny else "BENCH_storage.json"
    path = os.path.join(os.path.dirname(__file__), "..", name)
    with open(path, "w") as f:
        f.write(json.dumps(rec) + "\n")
    emit(rows, "bench_storage")
    print(f"# warm centroid-routed hit rate: "
          f"{rec['routing']['centroid']['warm_epoch']['hit_rate']} "
          f"(lift over FIFO: {rec['routing']['hit_rate_lift']['warm']}); "
          f"warm-cache-aware planner max regret: "
          f"{rec['planner']['max_regret_adaptive']}")


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
