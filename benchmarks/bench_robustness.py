"""Robustness bench: the graceful-degradation ladder vs fail-stop serving
under seeded storage faults and per-request deadlines (DESIGN.md §10).

One request queue is served twice through identical fault schedules
(same FaultPlan seed, fresh pools — the determinism contract makes the
comparison exact):

  fail-stop — the primary executor only (a one-rung ladder).  A request
              whose batch hits a failed page read, or whose deadline
              budget the primary plan exhausts, stays flagged: that is
              the pre-ladder serving behavior, and every flagged request
              counts against goodput.
  ladder    — the full ladder (f32 graph -> sq8-no-rerank -> scann-lite
              -> partial scan): faulted requests retry once, then
              descend rung by rung until one serves them cleanly or the
              last rung's flagged partial answer is returned.

Goodput counts a request good when it was admitted, returned at least
one valid id, and carries no unresolved fault.  Modeled per-request
latency walks the priced rungs (`price_ladder`): each request pays every
rung it visited (plus the primary again when retried), plus its share of
the fault penalty (`costmodel.fault_penalty`) — so the ladder's goodput
win is priced honestly against the extra rungs it runs.  Deadlines are a
mix of generous, tight (between the admission floor and the primary's
price — the band where degradation pays), and impossible (below the
admission floor — rejected at admission in BOTH modes).

Emits one JSON record to BENCH_robustness.json; `--tiny` (CI smoke)
writes the gitignored .tiny variant.

    PYTHONPATH=src python benchmarks/bench_robustness.py [--tiny]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (SearchParams, WorkloadSpec, build_graph,
                        build_scann, generate_bitmaps, quantize_store)
from repro.core import costmodel
from repro.core.executor import (BruteForceExecutor, GraphExecutor,
                                 ScannExecutor)
from repro.data import DatasetSpec, make_dataset
from repro.serving.rag import (LadderRung, RetrievalAugmentedServer,
                               admission_floor, price_ladder)
from repro.storage import FaultPlan, make_storage_engine

SELECTIVITY = 0.3
# per-ATTEMPT failure 0.1 with 2 retries -> ~1e-3 failed reads per miss:
# a few queries per batch see an unrecoverable read, most retry clean
FAULTS = dict(read_fail_prob=0.1, max_retries=2, latency_spike_prob=0.05,
              pressure_prob=0.002, pressure_len=512, pressure_frac=0.25)


def _setup(tiny: bool):
    if tiny:
        spec = DatasetSpec("robust-tiny", 4_000, 32, "l2", clusters=16)
        nreq, batch, leaves = 32, 8, 16
    else:
        spec = DatasetSpec("robust-bench", 20_000, 64, "l2", clusters=64)
        nreq, batch, leaves = 128, 16, 32
    store, queries = make_dataset(spec, num_queries=nreq, seed=0)
    store = quantize_store(store)
    graph = build_graph(store, m=8, ef_construction=48, seed=0)
    index = build_scann(store, num_leaves=leaves, levels=1, seed=0)
    return store, jnp.asarray(queries), graph, index, nreq, batch


def _components(store, graph, index, seed: int):
    """Executors sharing one faulted storage engine (one pool, one
    deterministic fault schedule)."""
    eng = make_storage_engine(store, index=index, graph=graph,
                              capacity_frac=0.25,
                              faults=FaultPlan(seed=seed, **FAULTS))
    gex = GraphExecutor(graph, store, strategy="sweeping", storage=eng,
                        graph_quant="none")
    sq8 = GraphExecutor(graph, store, strategy="sweeping", storage=eng,
                        graph_quant="sq8")
    sc = ScannExecutor(index, store, storage=eng)
    bf = BruteForceExecutor(store, storage=eng)
    return eng, gex, sq8, sc, bf


def _full_ladder(gex, sq8, sc, bf, store):
    from repro.core.types import heap_pages_per_vector
    ppv = heap_pages_per_vector(store.dim)

    def _partial(p):
        if p.page_budget > 0 or p.deadline_cycles > 0:
            return p
        return dataclasses.replace(
            p, page_budget=max(p.k, store.n // 10) * ppv)

    return [
        LadderRung("primary", gex),
        LadderRung("sq8_norerank", sq8,
                   lambda p: dataclasses.replace(p, sq8_rerank=False)),
        LadderRung("scann_lite", sc,
                   lambda p: dataclasses.replace(
                       p, num_leaves_to_search=max(
                           1, p.num_leaves_to_search // 2))),
        LadderRung("partial_scan", bf, _partial),
    ]


def _server(store, executor, params, qtable):
    # pure-retrieval server: prompts are (B, 1) indices into a
    # precomputed query table, no LM in the loop
    docs = np.zeros((store.n, 4), np.int32)
    return RetrievalAugmentedServer(
        bundle=None, params=None, executor=executor,
        search_params=params, doc_tokens=docs, chunk_len=4,
        embed_fn=lambda p, tok: qtable[tok[:, 0]])


def _deadlines(nreq: int, floor: float, primary_price: float,
               seed: int) -> np.ndarray:
    """70% generous (10x primary), 20% tight (the degradation band),
    10% impossible (below the admission floor)."""
    rng = np.random.RandomState(seed)
    n_imp = max(1, nreq // 10)
    n_tight = max(1, nreq // 5)
    kinds = np.array([2] * n_imp + [1] * n_tight
                     + [0] * (nreq - n_imp - n_tight))
    rng.shuffle(kinds)
    d = np.full(nreq, 10.0 * primary_price)
    d[kinds == 1] = 0.5 * (floor + max(primary_price, floor * 1.5))
    d[kinds == 2] = 0.5 * floor
    return d


def _latency(info, prices: dict, default_price: float,
             fault_share: float) -> np.ndarray:
    """Modeled per-request cycles: every rung walked is paid (retry pays
    the primary twice), plus the request's share of the fault penalty."""
    names = info["ladder"]
    level = info["rung_level"]
    lat = np.zeros(len(level))
    for i, lv in enumerate(level):
        if lv < 0:
            continue                         # rejected: never dispatched
        walked = [prices.get(names[j], default_price)
                  for j in range(lv + 1)]
        if info["retried"][i]:
            walked.append(prices.get(names[0], default_price))
        lat[i] = sum(walked) + fault_share
    return lat


def _serve(srv, queries, bm, params, ladder, deadlines, batch,
           prices, floor):
    import types as _t
    prompts = np.arange(queries.shape[0], dtype=np.int32)[:, None]
    res, info = srv.serve_queue(prompts, bm, batch_size=batch,
                                policy="fifo", deadlines=deadlines,
                                ladder=ladder)
    adm = info["admitted"]
    served_ok = (np.asarray(res.ids) >= 0).any(axis=1)
    good = adm & served_ok & ~info["faulted"]
    pen = costmodel.fault_penalty(
        _t.SimpleNamespace(retries=info.get("pool_retries", 0),
                           spikes=info.get("pool_spikes", 0)),
        batch_q=max(int(adm.sum()), 1))
    lat = _latency(info, prices, floor, pen)
    lat_adm = lat[adm] if adm.any() else np.zeros(1)
    rungs, counts = np.unique(info["rung"].astype(str),
                              return_counts=True)
    return {
        "goodput": round(float(good.mean()), 4),
        "p99_cycles": round(float(np.percentile(lat_adm, 99)), 1),
        "mean_cycles": round(float(lat_adm.mean()), 1),
        "flagged_degraded_frac": round(float(info["degraded"].mean()), 4),
        "rejected_frac": round(float((~adm).mean()), 4),
        "retried_frac": round(float(info["retried"].mean()), 4),
        "faulted_final_frac": round(float(info["faulted"].mean()), 4),
        "budget_exhausted_frac": round(
            float(info["budget_exhausted"].mean()), 4),
        "rung_hist": {r: int(c) for r, c in zip(rungs, counts)},
        "pool_failed_reads": int(info.get("pool_failed_reads", 0)),
        "pool_retries": int(info.get("pool_retries", 0)),
        "pool_spikes": int(info.get("pool_spikes", 0)),
    }


def run(tiny: bool = False) -> dict:
    store, queries, graph, index, nreq, batch = _setup(tiny)
    params = SearchParams(k=10, ef_search=64, beam_width=128,
                          max_hops=300 if tiny else 1000,
                          num_leaves_to_search=8,
                          graph_exec_mode="frontier",
                          scann_page_accounting="per_query")
    bm = generate_bitmaps(store, queries,
                          WorkloadSpec(SELECTIVITY, "none"), seed=1)
    floor = admission_floor(store, params)
    fault_seed = 11

    # price the rungs once (fault-free components, prediction only)
    _, gex, sq8, sc, bf = _components(store, graph, index, seed=0)
    ladder = _full_ladder(gex, sq8, sc, bf, store)
    prices = price_ladder(ladder, params, SELECTIVITY, batch_q=batch)
    deadlines = _deadlines(nreq, floor, prices["primary"], seed=2)

    out = {"bench": "robustness", "backend": jax.default_backend(),
           "tiny": tiny, "n": store.n, "dim": store.dim,
           "requests": nreq, "batch": batch, "selectivity": SELECTIVITY,
           "fault_plan": dict(seed=fault_seed, **FAULTS),
           "admission_floor": round(floor, 1),
           "rung_prices": {k: round(v, 1) for k, v in prices.items()}}

    # fail-stop: primary rung only, same fault schedule
    _, gex, _, _, _ = _components(store, graph, index, seed=fault_seed)
    srv = _server(store, gex, params, queries)
    out["failstop"] = _serve(srv, queries, bm, params,
                             [LadderRung("primary", gex)], deadlines,
                             batch, prices, floor)
    print("# failstop:", json.dumps(out["failstop"]))

    # ladder: fresh engine, identical fault schedule (same seed)
    _, gex, sq8, sc, bf = _components(store, graph, index,
                                      seed=fault_seed)
    ladder = _full_ladder(gex, sq8, sc, bf, store)
    srv = _server(store, gex, params, queries)
    out["ladder"] = _serve(srv, queries, bm, params, ladder, deadlines,
                           batch, prices, floor)
    print("# ladder:  ", json.dumps(out["ladder"]))

    out["goodput_gain"] = round(
        out["ladder"]["goodput"] - out["failstop"]["goodput"], 4)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="small fresh-built dataset (CI smoke)")
    args = ap.parse_args()
    result = run(tiny=args.tiny)
    line = json.dumps(result)
    # --tiny (CI smoke) must not clobber the tracked full record
    name = "BENCH_robustness.tiny.json" if args.tiny \
        else "BENCH_robustness.json"
    path = os.path.join(os.path.dirname(__file__), "..", name)
    with open(path, "w") as f:
        f.write(line + "\n")
    print(line)
    lg, fg = result["ladder"]["goodput"], result["failstop"]["goodput"]
    assert lg >= fg, f"ladder goodput {lg} below fail-stop {fg}"
    if fg < 1.0:
        assert lg > fg, (
            f"fail-stop dropped requests (goodput {fg}) but the ladder "
            f"recovered none (goodput {lg})")


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
