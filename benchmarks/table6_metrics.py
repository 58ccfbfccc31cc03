"""Paper Table 6: internal index metrics across selectivities on the
OpenAI-5M-shaped dataset (no correlation).

With --storage, each chosen config is re-run through a cold paged
StorageEngine (DESIGN.md §8) and the row gains the MEASURED page
accounting: pool-logical page accesses (exact == the analytic counters
for scann; ≤ for graph strategies — zoom-in revisit delta) and the cold
buffer-pool hit rate."""
from __future__ import annotations

import sys

from benchmarks.common import emit, run_method, run_storage_measured

SELECTIVITIES = (0.01, 0.02, 0.05, 0.1, 0.2, 0.3, 0.5, 0.8, 0.9)
# scann_distributed: the mesh-sharded path, included since its per-query
# SearchStats ride the all-gather (core/distributed.py)
METHODS = ("navix", "acorn", "sweeping", "scann", "scann_distributed")


def _measured(ds: str, m: str, sel: float, params) -> dict:
    res = run_storage_measured(ds, m, sel, params)
    return {
        "pages_measured": round(float(res.storage.index_pages.mean()
                                      + res.storage.heap_pages.mean())),
        "pool_hit_rate_cold": round(res.storage.hit_rate, 3),
    }


def run(ds="openai5m", sels=SELECTIVITIES, storage=False) -> list[dict]:
    rows = []
    for sel in sels:
        for m in METHODS:
            # Table 6 tabulates per-query counters; keep legacy accounting
            rec, srow, wall, params = run_method(
                ds, m, sel, "none", page_accounting="per_query")
            row = {
                "name": f"table6/{ds}/{m}/sel={sel}",
                "us_per_call": wall,
                "recall": round(rec, 3),
                "dist_comps": round(srow["distance_comps"]),
                "filter_checks": round(srow["filter_checks"]),
                "hops_or_leaves": round(srow["hops"], 1),
                "reorder": round(srow["reorder_rows"]),
                "page_accesses": round(srow["page_accesses_index"]
                                       + srow["page_accesses_heap"]),
            }
            if storage and m != "scann_distributed":
                # the mesh path carries counters, not page traces
                row.update(_measured(ds, m, sel, params))
            rows.append(row)
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    emit(run(storage="--storage" in sys.argv[1:]), "table6")
