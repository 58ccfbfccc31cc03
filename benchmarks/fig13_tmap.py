"""Paper Fig. 13: translation-map ablation — cycle breakdown with and
without the TM for the filter-first methods."""
from __future__ import annotations

import jax.numpy as jnp

from benchmarks.common import emit, get_dataset, run_method
from repro.core import SYSTEM, SearchStats, cycle_breakdown

SELS = (0.01, 0.1, 0.5, 0.8)


def run(ds="openai5m") -> list[dict]:
    store, _ = get_dataset(ds)
    rows = []
    for sel in SELS:
        for tm in (True, False):
            rec, srow, wall, _ = run_method(ds, "navix", sel, "none", tm=tm,
                                            page_accounting="per_query")
            z = lambda v: jnp.asarray(round(v), jnp.int32)
            stats = SearchStats(z(srow["distance_comps"]),
                                z(srow["filter_checks"]), z(srow["hops"]),
                                z(srow["page_accesses_index"]),
                                z(srow["page_accesses_heap"]),
                                z(srow["tmap_lookups"]),
                                z(srow["reorder_rows"]))
            br = cycle_breakdown(stats, store.dim, SYSTEM)
            rows.append({
                "name": f"fig13/{ds}/navix/tm={'on' if tm else 'off'}"
                        f"/sel={sel}",
                "us_per_call": wall, "recall": round(rec, 3),
                "total_mcycles": round(br["total"] / 1e6, 2),
                "metadata_fetch_share": round(
                    (br["index_page_access"] + br["translation_map"])
                    / br["total"], 3),
            })
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    emit(run(), "fig13")
