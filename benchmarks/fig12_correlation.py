"""Paper Fig. 12: vector-predicate correlation effects on the OpenAI-5M-
shaped dataset (QPS + recall per correlation x selectivity)."""
from __future__ import annotations

import jax.numpy as jnp

from benchmarks.common import emit, get_dataset, run_method
from repro.core import SYSTEM, SearchStats, modeled_qps

CORRS = ("high_pos", "low_pos", "negative")
SELS = (0.01, 0.1, 0.5)
METHODS = ("navix", "sweeping", "scann")


def run(ds="openai5m") -> list[dict]:
    store, _ = get_dataset(ds)
    rows = []
    for corr in CORRS:
        for sel in SELS:
            for m in METHODS:
                rec, srow, wall, _ = run_method(ds, m, sel, corr)
                z = lambda v: jnp.asarray(round(v), jnp.int32)
                stats = SearchStats(z(srow["distance_comps"]),
                                    z(srow["filter_checks"]),
                                    z(srow["hops"]),
                                    z(srow["page_accesses_index"]),
                                    z(srow["page_accesses_heap"]),
                                    z(srow["tmap_lookups"]),
                                    z(srow["reorder_rows"]))
                rows.append({
                    "name": f"fig12/{ds}/{m}/{corr}/sel={sel}",
                    "us_per_call": wall, "recall": round(rec, 3),
                    "modeled_qps": round(modeled_qps(stats, store.dim,
                                                     SYSTEM), 1),
                    "hops": round(srow["hops"], 1),
                })
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    emit(run(), "fig12")
