"""Paper Table 7: concurrency effects. The PG side is modeled (cycle
amplification at 16T); the TPU-native side is MEASURED: per-query wall
time at batch 1 vs batch 16 (vmap) — batching amortizes weight traffic,
the opposite sign of PG's contention (DESIGN.md §3 'what does not
transfer').

Beyond the paper's aggregate-QPS view, the closed-loop batch is also
replayed through the SAME trace-replay harness as bench_serving.py
(`benchmarks.bench_serving.replay`): all requests arrive at t=0 and are
served batch-synchronously vs continuously on one `SlotPool`, so the
closed-loop table and the open-loop curves report p50/p99 per-query
latency through one measurement path (DESIGN.md §11)."""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.bench_serving import replay
from benchmarks.common import (emit, get_bitmaps, get_dataset, get_graph,
                               run_method)
from repro.core import (SYSTEM, GraphExecutor, SearchParams, SearchStats,
                        cycle_breakdown)
from repro.serving.continuous import Request


def _latency_rows(ds, store, queries, graph, bm, nreq: int = 16,
                  width: int = 4, hop_chunk: int = 8) -> list[dict]:
    """Closed-loop trace (all arrivals t=0) through the shared replay
    harness: per-query p50/p99 tick latency, batch-synchronous vs
    continuous on the same slot pool."""
    p = SearchParams(k=10, ef_search=64, beam_width=64, max_hops=600,
                     strategy="sweeping", graph_exec_mode="frontier")
    ex = GraphExecutor(graph, store, strategy="sweeping")
    bm_np = np.asarray(bm)
    q_np = np.asarray(queries)
    reqs = [Request(rid=i, query=q_np[i % q_np.shape[0]],
                    bitmap=bm_np[i % bm_np.shape[0]])
            for i in range(nreq)]
    rows = []
    for mode in ("batch", "continuous"):
        m, _ = replay(ex, p, reqs, width, hop_chunk, mode,
                      slo_ticks=float("inf"))
        rows.append({"name": f"table7/{ds}/sweeping/closed_loop/{mode}",
                     "us_per_call": 0.0, "mode": mode,
                     "p50_ticks": m["p50_ticks"],
                     "p99_ticks": m["p99_ticks"],
                     "mean_ticks": m["mean_ticks"],
                     "slot_utilization": m["slot_utilization"]})
    return rows


def run(ds="openai5m", sel=0.1) -> list[dict]:
    store, queries = get_dataset(ds)
    graph = get_graph(ds)
    bm = get_bitmaps(ds, sel, "none")
    rows = []
    # modeled PG-side 1T vs 16T
    rec, srow, _, _ = run_method(ds, "navix", sel, "none")
    z = lambda v: jnp.asarray(round(v), jnp.int32)
    stats = SearchStats(z(srow["distance_comps"]), z(srow["filter_checks"]),
                        z(srow["hops"]), z(srow["page_accesses_index"]),
                        z(srow["page_accesses_heap"]),
                        z(srow["tmap_lookups"]), z(srow["reorder_rows"]))
    br = cycle_breakdown(stats, store.dim, SYSTEM)
    sysoh = br["index_page_access"] + br["vector_retrieval"]
    rows.append({"name": f"table7/{ds}/navix/modeled",
                 "us_per_call": 0.0,
                 "total_mcycles_1t": round(br["total"] / 1e6, 1),
                 "total_mcycles_16t": round(br["total"] * 1.5 / 1e6, 1),
                 "sysoh_share": round(sysoh / br["total"], 3)})
    # measured TPU-native batching effect
    p = SearchParams(k=10, ef_search=128, beam_width=512,
                     strategy="sweeping", max_hops=2048)
    ex = GraphExecutor(graph, store, strategy="sweeping")
    for b in (1, 16):
        q, m = queries[:b], bm[:b]
        jax.block_until_ready(ex.search(q, m, p).ids)
        t0 = time.perf_counter()
        ids = ex.search(q, m, p).ids
        jax.block_until_ready(ids)
        us = (time.perf_counter() - t0) / b * 1e6
        rows.append({"name": f"table7/{ds}/sweeping/batch={b}",
                     "us_per_call": us, "batch": b})
    # per-query latency distribution via the shared serving harness
    rows.extend(_latency_rows(ds, store, queries, graph, bm))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    emit(run(), "table7")
