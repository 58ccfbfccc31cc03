"""Paper Fig. 9: QPS at 95% Recall@10 across selectivities, 4 datasets,
no correlation — all five methods, with SYSTEM-modeled QPS (cost model)
plus measured per-query wall time and raw counters."""
from __future__ import annotations

from benchmarks.common import (ALL_METHODS, BENCH_DATASETS, emit, get_dataset,
                               run_method)
from repro.core import SYSTEM, modeled_qps, SearchStats
import jax.numpy as jnp

SELECTIVITIES = (0.01, 0.05, 0.1, 0.3, 0.5, 0.8)


def _row_to_stats(row):
    z = lambda v: jnp.asarray(round(v), jnp.int32)
    return SearchStats(z(row["distance_comps"]), z(row["filter_checks"]),
                       z(row["hops"]), z(row["page_accesses_index"]),
                       z(row["page_accesses_heap"]), z(row["tmap_lookups"]),
                       z(row["reorder_rows"]))


def run(datasets=("sift10m", "openai5m"), sels=SELECTIVITIES) -> list[dict]:
    rows = []
    for ds in datasets:
        store, _ = get_dataset(ds)
        for sel in sels:
            for method in ALL_METHODS:
                # batch page accounting: QPS under concurrent load, where
                # the batched ScaNN pipeline amortizes leaf fetches
                rec, srow, wall, p = run_method(ds, method, sel, "none",
                                                page_accounting="batch")
                qps = modeled_qps(_row_to_stats(srow), store.dim, SYSTEM)
                rows.append({
                    "name": f"fig9/{ds}/{method}/sel={sel}",
                    "us_per_call": wall,
                    "recall": round(rec, 3),
                    "modeled_qps": round(qps, 1),
                    "dc": round(srow["distance_comps"]),
                    "fc": round(srow["filter_checks"]),
                    "hops": round(srow["hops"], 1),
                    "pages": round(srow["page_accesses_index"]
                                   + srow["page_accesses_heap"]),
                })
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    emit(run(), "fig9")
