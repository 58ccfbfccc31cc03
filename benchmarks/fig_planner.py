"""Planner regret sweep (companion to paper Fig. 1): selectivity ×
correlation grid, all fixed strategies vs the AdaptivePlanner.

At every grid point each fixed executor runs with the SAME balanced params,
its measured SearchStats are converted to SYSTEM-modeled cycles under the
accounting of the engine that actually executed it — ScaNN's batched
union-scan pipeline uses "batch" page accounting (DESIGN.md §5), graph
strategies on the frontier engine get the `engine_scale` page-cost
amortization (DESIGN.md §7) — and the "best fixed" is the cheapest
strategy meeting the recall floor (the paper's QPS-at-recall framing: a
strategy that can't hit recall doesn't get to be called fast).  Regret =
own cycles / best-fixed cycles; a strategy below the recall floor at a
point scores regret = inf there.  (For the paper's standalone-query
Fig. 10/13 semantics see fig10_breakdown.py / fig13_tmap.py, which keep
per-query accounting and unscaled weights.)

The paper's Fig. 1 finding is that no fixed strategy stays near-optimal
across the grid; the planner's job is to track the per-point best within
1.5x everywhere.  Emits one JSON record to BENCH_planner.json with the
full grid + the max-regret summary so the trajectory is tracked
run-over-run.  This sweep stays buffer-pool-blind (no StorageEngine
attached) so its currency is reproducible run-over-run; the warm-serving
variant — same grid, pooled executors, warm-cache-aware costs on both
sides — lives in benchmarks/bench_storage.py (`bench_planner`).

    PYTHONPATH=src python benchmarks/fig_planner.py [--tiny] [--ds sift10m]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

from benchmarks.common import (emit, get_bitmaps, get_dataset, get_exclusion,
                               get_executor, get_partitions, ground_truth,
                               mean_recall)
from repro.core import SYSTEM, SearchParams, cycle_breakdown, engine_scale

SELS = (0.01, 0.05, 0.2, 0.5, 0.9)
CORRS = ("none", "high_pos", "negative")
FIXED = ("bruteforce", "sweeping", "navix", "iterative_scan", "scann")
# the full planner menu: the six PR-4 candidates plus the two
# selectivity-aware tiers (DESIGN.md §14).  This grid's bitmaps are
# per-query and never family-match, so the planner must keep the new
# candidates honest — partitioned is batch-infeasible everywhere here and
# sweeping_excl falls back to ladder radii (prunes ~nothing, cost ≈
# sweeping); neither may cost the planner regret.
MENU = ("bruteforce", "scann", "sweeping", "sweeping_sq8", "navix",
        "iterative_scan", "sweeping_excl", "partitioned")
RECALL_FLOOR = 0.9
REGRET_TARGET = 1.5


def _params(k: int = 10) -> SearchParams:
    return SearchParams(k=k, ef_search=128, beam_width=512, max_hops=3000,
                        num_leaves_to_search=32, reorder_factor=4,
                        scann_page_accounting="batch",
                        batch_tuples=max(64, k * 8), max_rounds=16)


def run(ds: str = "sift10m", sels=SELS, corrs=CORRS,
        methods=FIXED) -> tuple[list[dict], dict]:
    store, queries = get_dataset(ds)
    p = _params()
    executors = {m: get_executor(ds, m) for m in methods}
    executors["adaptive"] = get_executor(
        ds, "adaptive", exclusion=get_exclusion(ds, 0.05),
        partitions=get_partitions(ds, 0.05), planner_candidates=MENU)
    # warm the jit caches once per executor (shapes/params are identical
    # across grid points) so timed rows exclude compile time
    warm_bm = get_bitmaps(ds, sels[0], corrs[0])
    for ex in executors.values():
        jax.block_until_ready(ex.search(queries, warm_bm, p).ids)
    rows, grid = [], []
    for corr in corrs:
        for sel in sels:
            bm = get_bitmaps(ds, sel, corr)
            _, tid = ground_truth(ds, sel, corr, p.k)
            cyc, rec, wall, chosen = {}, {}, {}, {}
            for m, ex in executors.items():
                t0 = time.perf_counter()
                res = ex.search(queries, bm, p)
                jax.block_until_ready(res.ids)
                wall[m] = (time.perf_counter() - t0) / queries.shape[0] * 1e6
                # engine-mode-aware currency (DESIGN.md §7): graph
                # strategies ran on the frontier engine, whose batched
                # fetches amortize page costs — the same scale the
                # planner's predictions use
                cyc[m] = cycle_breakdown(
                    res.stats, store.dim, SYSTEM,
                    engine_scale(res.strategy, p, queries.shape[0]))["total"]
                rec[m] = mean_recall(res.ids, tid, p.k)
                chosen[m] = res.strategy
            qualified = {m: cyc[m] for m in methods
                         if rec[m] >= RECALL_FLOOR}
            best_pool = qualified or {m: cyc[m] for m in methods}
            best = min(best_pool, key=best_pool.get)
            point = {"sel": sel, "corr": corr, "best_fixed": best,
                     "chosen": chosen["adaptive"], "regret": {}, "recall": {},
                     "mcycles": {}}
            for m in (*methods, "adaptive"):
                r = cyc[m] / cyc[best]
                if rec[m] < RECALL_FLOOR:
                    r = math.inf
                point["regret"][m] = round(r, 3) if math.isfinite(r) \
                    else "inf"
                point["recall"][m] = round(rec[m], 3)
                point["mcycles"][m] = round(cyc[m] / 1e6, 3)
            grid.append(point)
            rows.append({
                "name": f"fig_planner/{ds}/{corr}/sel={sel}",
                "us_per_call": wall["adaptive"],
                "chosen": chosen["adaptive"], "best_fixed": best,
                "regret_adaptive": point["regret"]["adaptive"],
                "recall_adaptive": point["recall"]["adaptive"],
                "best_mcycles": round(cyc[best] / 1e6, 3),
            })

    def max_regret(m):
        vals = [pt["regret"][m] for pt in grid]
        return math.inf if "inf" in vals else max(vals)

    summary = {
        "bench": "planner", "dataset": ds, "recall_floor": RECALL_FLOOR,
        "regret_target": REGRET_TARGET, "planner_menu": list(MENU),
        "grid": grid,
        "max_regret": {m: (round(v, 3) if math.isfinite(v) else "inf")
                       for m in (*methods, "adaptive")
                       for v in [max_regret(m)]},
        "planner_within_target": max_regret("adaptive") <= REGRET_TARGET,
        "fixed_within_target": sorted(
            m for m in methods if max_regret(m) <= REGRET_TARGET),
    }
    return rows, summary


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="2-point CI grid (smoke.sh)")
    ap.add_argument("--ds", default="sift10m")
    args = ap.parse_args()
    sels = (0.05, 0.5) if args.tiny else SELS
    corrs = ("none",) if args.tiny else CORRS
    rows, summary = run(args.ds, sels, corrs)
    # --tiny (CI smoke) must not clobber the tracked full-grid record
    name = "BENCH_planner.tiny.json" if args.tiny else "BENCH_planner.json"
    path = os.path.join(os.path.dirname(__file__), "..", name)
    with open(path, "w") as f:
        f.write(json.dumps(summary) + "\n")
    emit(rows, "fig_planner")
    print(f"# planner max regret: {summary['max_regret']['adaptive']}, "
          f"fixed strategies within {REGRET_TARGET}x everywhere: "
          f"{summary['fixed_within_target'] or 'none'}")
    # the frontier-engine recalibration contract: the planner must stay
    # within the regret target at recall ≥ RECALL_FLOOR at every point
    # (recall checked first — a floor miss also scores regret = inf)
    assert all(pt["recall"]["adaptive"] >= RECALL_FLOOR for pt in
               summary["grid"]), "planner fell below the recall floor"
    assert summary["planner_within_target"], (
        f"planner regret exceeded {REGRET_TARGET}x: "
        f"{summary['max_regret']['adaptive']}")


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
