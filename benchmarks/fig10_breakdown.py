"""Paper Fig. 10: per-component modeled cycle breakdown (SYSTEM regime)
at 1/10/50/80% selectivity on the OpenAI-5M-shaped dataset.

With --storage, rows gain `total_cold`: the per-query total with the
MEASURED cold buffer-pool miss penalty added (DESIGN.md §8) — the
standalone-query cost when nothing is resident, vs the warm `total` the
classic bars model."""
from __future__ import annotations

import sys

import jax.numpy as jnp

from benchmarks.common import (NUM_QUERIES, emit, get_dataset, run_method,
                               run_storage_measured)
from repro.core import (SYSTEM, SearchStats, cycle_breakdown,
                        measured_miss_penalty)

SELS = (0.01, 0.1, 0.5, 0.8)
# scann_distributed: mesh-path counters now cross the all-gather, so its
# Fig. 10 bars come from the same cycle_breakdown as the local methods
METHODS = ("navix", "acorn", "sweeping", "scann", "scann_distributed")


def _cold_penalty(ds: str, m: str, sel: float, params) -> float:
    res = run_storage_measured(ds, m, sel, params)
    return measured_miss_penalty(res.storage, NUM_QUERIES, SYSTEM)


def run(ds="openai5m", storage=False) -> list[dict]:
    store, _ = get_dataset(ds)
    rows = []
    for sel in SELS:
        for m in METHODS:
            # per-query page accounting: Fig. 10 models one standalone query
            rec, srow, wall, params = run_method(ds, m, sel, "none",
                                                 page_accounting="per_query")
            z = lambda v: jnp.asarray(round(v), jnp.int32)
            stats = SearchStats(z(srow["distance_comps"]),
                                z(srow["filter_checks"]), z(srow["hops"]),
                                z(srow["page_accesses_index"]),
                                z(srow["page_accesses_heap"]),
                                z(srow["tmap_lookups"]),
                                z(srow["reorder_rows"]))
            br = cycle_breakdown(stats, store.dim, SYSTEM)
            row = {"name": f"fig10/{ds}/{m}/sel={sel}", "us_per_call": wall,
                   "recall": round(rec, 3)}
            row.update({k: round(v / 1e6, 2) for k, v in br.items()})
            if storage and m != "scann_distributed":
                # the mesh path carries counters, not page traces
                pen = _cold_penalty(ds, m, sel, params)
                row["total_cold"] = round((br["total"] + pen) / 1e6, 2)
            rows.append(row)
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    emit(run(storage="--storage" in sys.argv[1:]), "fig10")
