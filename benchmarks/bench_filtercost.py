"""Filter-cost sweep for the selectivity-aware tiers (DESIGN.md §14).

The paper's Table 6 shows filter checks dominating graph strategies at low
selectivity; FAVOR-style exclusion radii and JAG-style attribute
partitioning both attack exactly that term.  This bench measures the
attack on the workload the tiers are built for — clustered predicate
*families* shared by many queries — and on the workload they are NOT
built for (per-query uncorrelated bitmaps), at each selectivity:

  sweeping        — the filter-agnostic baseline (PR-1 engine)
  sweeping_excl   — exclusion-pruned sweeping, family-exact radii +
                    "prune_exact" accounting (FAVOR's eliminated probes),
                    margin 0.3: the aggressive end of the heuristic
                    margin knob (< 1.0 trades recall for pruning, >= 1.0
                    is provably inert) — reported as the tradeoff
                    diagnostic, not the gate carrier
  partitioned     — per-family subgraph, traversed unfiltered (carries
                    the >= GATE_FC_RATIO x gate)

Every row reports measured SearchStats counters, recall against exact
filtered KNN, and the physical page story through a cold StorageEngine
(heap + index pool misses = distinct pages actually read).  Gates
(ISSUE 10 acceptance):

  * at every family point with sel <= GATE_SEL, the best selectivity-
    aware tier must measure >= GATE_FC_RATIO x fewer filter checks AND
    fewer physical heap+index pages than sweeping, at recall within
    GATE_RECALL_SLACK of it;
  * on the uncorrelated control the tiers stay recall-safe (the
    exclusion ladder prunes ~nothing by design — no signal, no savings).

Emits BENCH_filtercost.json (tracked) or BENCH_filtercost.tiny.json
(--tiny, gitignored; wired into tools/smoke.sh).

    PYTHONPATH=src python benchmarks/bench_filtercost.py [--tiny]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np

from benchmarks.common import (emit, family_ground_truth, get_bitmaps,
                               get_dataset, get_exclusion, get_executor,
                               get_family_bitmaps, get_graph, get_partitions,
                               get_storage_engine, ground_truth, mean_recall)
from repro.core import SearchParams

DATASET = "sift10m"
SELS = (0.02, 0.05, 0.1)
TINY_SELS = (0.05,)
METHODS = ("sweeping", "sweeping_excl", "partitioned")
EXCL_MARGIN = 0.3
GATE_SEL = 0.05
GATE_FC_RATIO = 3.0
GATE_RECALL_SLACK = 0.02


def _params(k: int = 10) -> SearchParams:
    return SearchParams(k=k, ef_search=96, beam_width=512, max_hops=3000,
                        strategy="sweeping", exclusion_margin=EXCL_MARGIN)


def _measure(ex, queries, bm, tid, k):
    t0 = time.perf_counter()
    res = ex.search(queries, bm, _params(k))
    jax.block_until_ready(res.ids)
    wall = (time.perf_counter() - t0) / queries.shape[0] * 1e6
    st = res.stats
    pages = res.storage
    return {
        "recall": mean_recall(res.ids, tid, k),
        "fc": float(np.mean(np.asarray(st.filter_checks))),
        "dc": float(np.mean(np.asarray(st.distance_comps))),
        "hops": float(np.mean(np.asarray(st.hops))),
        # gate carrier = physical reads (pool misses): each method gets its
        # own cold full-capacity pool, so misses = distinct pages actually
        # fetched from storage; logical accesses reported alongside
        "pages_heap": int(pages.misses.get("heap", 0)
                          + pages.misses.get("qheap", 0)),
        "pages_index": int(pages.misses.get("graph", 0)),
        "pages_heap_logical": int(pages.logical.get("heap", 0)
                                  + pages.logical.get("qheap", 0)),
        "pages_index_logical": int(pages.logical.get("graph", 0)),
        "us_per_call": wall,
    }


def _executor(ds, method, sel):
    # every run gets its own cold engine so the physical page story is a
    # per-(method, sel) measurement, not an artifact of pool history
    eng = get_storage_engine(ds, "sweeping", capacity_frac=1.0)
    if method == "sweeping":
        return get_executor(ds, method, storage=eng)
    if method == "sweeping_excl":
        return get_executor(ds, method, storage=eng,
                            exclusion=get_exclusion(ds, sel))
    if method == "partitioned":
        return get_executor(ds, method, storage=eng,
                            partitions=get_partitions(ds, sel))
    raise ValueError(method)


def run(ds: str = DATASET, sels=SELS, k: int = 10):
    store, queries = get_dataset(ds)
    get_graph(ds)                                   # warm the shared cache
    rows, grid = [], []
    for sel in sels:
        # --- clustered-family workload: the tiers' home regime ---------
        bm, _ = get_family_bitmaps(ds, sel)
        _, tid = family_ground_truth(ds, sel, k=k)
        point = {"sel": sel, "workload": "family", "methods": {}}
        for m in METHODS:
            r = _measure(_executor(ds, m, sel), queries, bm, tid, k)
            point["methods"][m] = {kk: round(v, 4) if isinstance(v, float)
                                   else v for kk, v in r.items()}
            rows.append({"name": f"bench_filtercost/{ds}/family/"
                                 f"sel={sel}/{m}",
                         "us_per_call": r["us_per_call"],
                         "recall": round(r["recall"], 3),
                         "fc": round(r["fc"], 1),
                         "pages": r["pages_heap"] + r["pages_index"]})
        base = point["methods"]["sweeping"]
        for m in METHODS[1:]:
            t = point["methods"][m]
            t["fc_ratio"] = round(base["fc"] / max(t["fc"], 1e-9), 2)
            t["page_ratio"] = round(
                (base["pages_heap"] + base["pages_index"])
                / max(t["pages_heap"] + t["pages_index"], 1), 2)
            t["page_ratio_logical"] = round(
                (base["pages_heap_logical"] + base["pages_index_logical"])
                / max(t["pages_heap_logical"]
                      + t["pages_index_logical"], 1), 2)
        grid.append(point)
        # --- uncorrelated control: no family signal, safety only -------
        cbm = get_bitmaps(ds, sel, "none")
        _, ctid = ground_truth(ds, sel, "none", k)
        ctrl = {"sel": sel, "workload": "uncorrelated", "methods": {}}
        for m in ("sweeping", "sweeping_excl"):
            r = _measure(_executor(ds, m, sel), queries, cbm, ctid, k)
            ctrl["methods"][m] = {kk: round(v, 4) if isinstance(v, float)
                                  else v for kk, v in r.items()}
        grid.append(ctrl)

    gates = []
    for pt in grid:
        if pt["workload"] != "family" or pt["sel"] > GATE_SEL:
            continue
        base = pt["methods"]["sweeping"]
        best = {}
        for m in METHODS[1:]:
            t = pt["methods"][m]
            ok = (t["fc_ratio"] >= GATE_FC_RATIO
                  and t["page_ratio"] > 1.0
                  and t["recall"] >= base["recall"] - GATE_RECALL_SLACK)
            if ok and (not best or t["fc_ratio"] > best["fc_ratio"]):
                best = {"method": m, "fc_ratio": t["fc_ratio"],
                        "page_ratio": t["page_ratio"],
                        "recall": t["recall"]}
        gates.append({"sel": pt["sel"], "passed": bool(best), **best})
    summary = {"bench": "filtercost", "dataset": ds,
               "excl_margin": EXCL_MARGIN, "gate_sel": GATE_SEL,
               "gate_fc_ratio": GATE_FC_RATIO,
               "gate_recall_slack": GATE_RECALL_SLACK,
               "grid": grid, "gates": gates,
               "all_gates_passed": all(g["passed"] for g in gates)}
    return rows, summary


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="1-point CI sweep (smoke.sh)")
    ap.add_argument("--ds", default=DATASET)
    args = ap.parse_args()
    rows, summary = run(args.ds, TINY_SELS if args.tiny else SELS)
    name = "BENCH_filtercost.tiny.json" if args.tiny \
        else "BENCH_filtercost.json"
    path = os.path.join(os.path.dirname(__file__), "..", name)
    with open(path, "w") as f:
        f.write(json.dumps(summary) + "\n")
    emit(rows, "bench_filtercost")
    print(f"# filtercost gates: {summary['gates']}")
    assert summary["all_gates_passed"], (
        f"selectivity-aware tier gate failed: {summary['gates']}")


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
