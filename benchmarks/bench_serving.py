"""Open-loop serving bench: continuous batching vs batch-synchronous
dispatch on identical arrival traces (DESIGN.md §11).

The paper's Table 7 concurrency study is closed-loop — a fixed batch
enters together, so measured QPS hides head-of-line blocking.  This
bench replays an OPEN-LOOP trace (Poisson background arrivals at a swept
offered load, plus bursty hot-topic arrivals whose correlated
low-selectivity predicates make them stragglers) through the same
`SlotPool` twice:

  continuous — finished lanes retire mid-flight, queued requests are
               admitted into freed slots every tick
  batch      — the pool refills only when EMPTY and harvests only when
               every lane is done: all co-batched requests share the
               last finisher's retire tick (exactly `serve_queue`'s
               dispatch shape, measured on the same engine)

Per-lane results are bit-identical between the two modes (and to
`serve_queue` itself — the precheck asserts this BEFORE any timing);
only the clock differs.  Latency is virtual time: 1 tick = 1 stepped
hop chunk.  Reported per load point: p50/p99 tick latency, goodput
(fraction served within the SLO), slot utilization and jit compile
count.  Emits one JSON record to BENCH_serving.json; `--tiny` (CI
smoke) writes the gitignored .tiny variant.

    PYTHONPATH=src python benchmarks/bench_serving.py [--tiny]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (SearchParams, WorkloadSpec, build_graph,
                        generate_bitmaps)
from repro.core.executor import GraphExecutor
from repro.data import DatasetSpec, make_dataset
from repro.serving.continuous import (ContinuousServer, Request,
                                      results_in_order)
from repro.serving.rag import RetrievalAugmentedServer

STRAGGLER_FRAC = 0.2        # hot-topic share of arrivals (heavy tail)
SEL_FAST, SEL_SLOW = 0.5, 0.02
BURST_LEN = 4               # consecutive hot-topic arrivals per burst


def _setup(tiny: bool):
    if tiny:
        spec = DatasetSpec("serving-tiny", 4_000, 32, "l2", clusters=16)
        nreq, width, hop_chunk, max_hops = 48, 4, 8, 200
    else:
        spec = DatasetSpec("serving-bench", 20_000, 64, "l2", clusters=64)
        nreq, width, hop_chunk, max_hops = 160, 8, 8, 600
    store, queries = make_dataset(spec, num_queries=64, seed=0)
    graph = build_graph(store, m=8, ef_construction=48, seed=0)
    params = SearchParams(k=10, ef_search=64, beam_width=64,
                          max_hops=max_hops, strategy="sweeping",
                          graph_exec_mode="frontier")
    return store, np.asarray(queries), graph, params, nreq, width, hop_chunk


def make_trace(queries: np.ndarray, bm_fast, bm_slow, nreq: int,
               load: float, seed: int) -> list[Request]:
    """Open-loop trace: Poisson arrivals at `load` requests/tick.
    Background requests draw a random query with a selectivity-0.5
    uncorrelated predicate; ~STRAGGLER_FRAC of arrivals come in
    hot-topic bursts — BURST_LEN consecutive requests repeating one
    query with its correlated selectivity-0.02 predicate (the
    `workload.py` correlated family), which makes them traversal
    stragglers under the sweeping strategy."""
    rng = np.random.RandomState(seed)
    arrivals = np.floor(np.cumsum(
        rng.exponential(1.0 / load, nreq))).astype(np.int64)
    bm_fast = np.asarray(bm_fast)
    bm_slow = np.asarray(bm_slow)
    nq = queries.shape[0]
    reqs: list[Request] = []
    i = 0
    while i < nreq:
        if rng.rand() < STRAGGLER_FRAC / BURST_LEN:
            hot = rng.randint(nq)
            for _ in range(min(BURST_LEN, nreq - i)):
                reqs.append(Request(rid=i, query=queries[hot],
                                    bitmap=bm_slow[hot],
                                    arrival=int(arrivals[i])))
                i += 1
        else:
            qi = rng.randint(nq)
            reqs.append(Request(rid=i, query=queries[qi],
                                bitmap=bm_fast[qi],
                                arrival=int(arrivals[i])))
            i += 1
    return reqs


def replay(executor, params, requests: list[Request], width: int,
           hop_chunk: int, mode: str, slo_ticks: float,
           fairness=None) -> tuple[dict, dict]:
    """Trace-replay harness shared with table7_concurrency.py: run one
    trace through a `ContinuousServer` in `mode` and reduce to the
    serving metrics (p50/p99 tick latency, goodput within `slo_ticks`,
    slot utilization, compiles).  Returns (metrics, raw records)."""
    srv = ContinuousServer(executor, params, width=width,
                           hop_chunk=hop_chunk, fairness=fairness)
    t0 = time.perf_counter()
    recs, info = srv.serve(requests, mode=mode)
    wall = time.perf_counter() - t0
    served = [r for r in recs.values() if r.get("retire_tick", -1) >= 0]
    lat = np.array([r["latency_ticks"] for r in served], np.float64)
    good = sum(1 for r in served
               if (np.asarray(r["ids"]) >= 0).any()
               and r["latency_ticks"] <= slo_ticks)
    return {
        "mode": mode,
        "p50_ticks": float(np.percentile(lat, 50)),
        "p99_ticks": float(np.percentile(lat, 99)),
        "mean_ticks": round(float(lat.mean()), 2),
        "goodput": round(good / max(len(requests), 1), 4),
        "slot_utilization": round(info["slot_utilization"], 4),
        "compiles": info["compiles"],
        "ticks": info["ticks"],
        "wall_s": round(wall, 2),
    }, recs


def _precheck(store, queries, bm_fast, executor, params, width: int,
              hop_chunk: int, nreq: int) -> None:
    """Bit-identicality gate, asserted BEFORE any timing run: with all
    arrivals at t=0 and fairness off, continuous slot-retire ids/dists
    must equal `serve_queue(policy="fifo")` exactly."""
    n = min(nreq, 24)
    qt = jnp.asarray(queries)
    srv = RetrievalAugmentedServer(
        bundle=None, params=None, executor=executor, search_params=params,
        doc_tokens=np.zeros((store.n, 4), np.int32), chunk_len=4,
        embed_fn=lambda p, tok: qt[tok[:, 0]])
    prompts = np.arange(n, dtype=np.int32)[:, None]
    res, _ = srv.serve_queue(prompts, jnp.asarray(np.asarray(bm_fast)[:n]),
                             batch_size=width, policy="fifo")
    reqs = [Request(rid=i, query=queries[i],
                    bitmap=np.asarray(bm_fast)[i]) for i in range(n)]
    cs = ContinuousServer(executor, params, width=width,
                          hop_chunk=hop_chunk)
    recs, _ = cs.serve(reqs, mode="continuous")
    ids, dists = results_in_order(recs, n, params.k)
    assert np.array_equal(np.asarray(res.ids), ids), \
        "precheck failed: continuous ids differ from serve_queue"
    assert np.array_equal(np.asarray(res.dists), dists, equal_nan=True), \
        "precheck failed: continuous dists differ from serve_queue"


def _service_estimate(queries, bm_fast, bm_slow, executor, params,
                      width: int, hop_chunk: int) -> tuple[float, float]:
    """Mean service ticks of the fast and straggler classes, measured on
    an uncontended pool (arrivals at t=0, one request per slot wave)."""
    out = []
    for bm in (bm_fast, bm_slow):
        reqs = [Request(rid=i, query=queries[i],
                        bitmap=np.asarray(bm)[i]) for i in range(width)]
        cs = ContinuousServer(executor, params, width=width,
                              hop_chunk=hop_chunk)
        recs, _ = cs.serve(reqs, mode="continuous")
        out.append(float(np.mean([recs[i]["latency_ticks"]
                                  for i in range(width)])))
    return out[0], out[1]


def run(tiny: bool = False) -> dict:
    store, queries, graph, params, nreq, width, hop_chunk = _setup(tiny)
    executor = GraphExecutor(graph, store, strategy="sweeping")
    qj = jnp.asarray(queries)
    bm_fast = generate_bitmaps(store, qj, WorkloadSpec(SEL_FAST, "none"),
                               seed=1)
    bm_slow = generate_bitmaps(store, qj,
                               WorkloadSpec(SEL_SLOW, "high_pos"), seed=2)

    _precheck(store, queries, bm_fast, executor, params, width, hop_chunk,
              nreq)
    s_fast, s_slow = _service_estimate(queries, bm_fast, bm_slow,
                                       executor, params, width, hop_chunk)
    s_mean = (1 - STRAGGLER_FRAC) * s_fast + STRAGGLER_FRAC * s_slow
    capacity = width / max(s_mean, 1e-9)        # requests/tick
    # tight enough that head-of-line-blocked fast requests miss it, wide
    # enough that an uncontended straggler (s_slow) meets it
    slo_ticks = 1.5 * s_slow

    out = {"bench": "serving", "backend": jax.default_backend(),
           "tiny": tiny, "n": store.n, "dim": store.dim,
           "requests": nreq, "width": width, "hop_chunk": hop_chunk,
           "straggler_frac": STRAGGLER_FRAC, "burst_len": BURST_LEN,
           "sel_fast": SEL_FAST, "sel_slow": SEL_SLOW,
           "precheck_bit_identical": True,
           "service_ticks": {"fast": round(s_fast, 2),
                             "slow": round(s_slow, 2),
                             "mean": round(s_mean, 2)},
           "capacity_req_per_tick": round(capacity, 4),
           "slo_ticks": round(slo_ticks, 1), "sweep": []}

    fracs = (0.5, 0.9) if tiny else (0.35, 0.6, 0.9)
    for frac in fracs:
        load = frac * capacity
        trace = make_trace(queries, bm_fast, bm_slow, nreq, load, seed=7)
        point = {"frac_capacity": frac,
                 "offered_load": round(load, 4)}
        for mode in ("continuous", "batch"):
            point[mode], _ = replay(executor, params, trace, width,
                                    hop_chunk, mode, slo_ticks)
        point["p99_ratio"] = round(
            point["batch"]["p99_ticks"]
            / max(point["continuous"]["p99_ticks"], 1e-9), 3)
        out["sweep"].append(point)
        print(f"# load={frac:.2f}c cont p99={point['continuous']['p99_ticks']:.0f} "
              f"goodput={point['continuous']['goodput']:.3f} | "
              f"batch p99={point['batch']['p99_ticks']:.0f} "
              f"goodput={point['batch']['goodput']:.3f} "
              f"(p99 ratio {point['p99_ratio']:.2f})")

    # the knee is the highest swept load — the operating point where
    # batch-synchronous dispatch saturates (its effective service time is
    # the per-batch max, so its capacity knee arrives first)
    knee = out["sweep"][-1]
    out["knee"] = {"frac_capacity": knee["frac_capacity"],
                   "p99_ratio": knee["p99_ratio"],
                   "goodput_continuous": knee["continuous"]["goodput"],
                   "goodput_batch": knee["batch"]["goodput"]}
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="small fresh-built dataset (CI smoke)")
    args = ap.parse_args()
    result = run(tiny=args.tiny)
    line = json.dumps(result)
    # --tiny (CI smoke) must not clobber the tracked full record
    name = "BENCH_serving.tiny.json" if args.tiny else "BENCH_serving.json"
    path = os.path.join(os.path.dirname(__file__), "..", name)
    with open(path, "w") as f:
        f.write(line + "\n")
    print(line)
    knee = result["knee"]
    assert knee["p99_ratio"] >= 1.5, (
        f"continuous p99 win {knee['p99_ratio']}x at the knee is below "
        f"the 1.5x bar")
    assert knee["goodput_continuous"] > knee["goodput_batch"], (
        f"continuous goodput {knee['goodput_continuous']} not strictly "
        f"better than batch {knee['goodput_batch']} at the knee")


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
