"""Paper Fig. 11: sensitivity to LIMIT k (k in {5, 100}) — search effort
growth per method at low selectivity."""
from __future__ import annotations

from benchmarks.common import emit, run_method

METHODS = ("navix", "sweeping", "scann")


def run(ds="sift10m", sel=0.05) -> list[dict]:
    rows = []
    effort = {}
    for k in (5, 100):
        for m in METHODS:
            rec, srow, wall, _ = run_method(ds, m, sel, "none", k=k)
            key = "hops" if m != "scann" else "hops"  # leaves for scann
            effort.setdefault(m, {})[k] = srow[key]
            rows.append({
                "name": f"fig11/{ds}/{m}/k={k}",
                "us_per_call": wall, "recall": round(rec, 3),
                "hops_or_leaves": round(srow[key], 1),
                "dist_comps": round(srow["distance_comps"]),
            })
    for m in METHODS:
        growth = effort[m][100] / max(effort[m][5], 1e-9)
        rows.append({"name": f"fig11/{ds}/{m}/growth", "us_per_call": 0.0,
                     "hops_growth_5_to_100": round(growth, 2)})
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    emit(run(), "fig11")
