"""Paper Table 2: dataset characteristics — LID, LRC, and the measured
distance-vs-filter relative cost for each benchmark dataset."""
from __future__ import annotations

from benchmarks.common import BENCH_DATASETS, emit, get_dataset
from repro.core.hardness import dist_filter_relative_cost, lid_mle, lrc


def run() -> list[dict]:
    rows = []
    for name, spec in BENCH_DATASETS.items():
        store, queries = get_dataset(name)
        rows.append({
            "name": f"table2/{name}",
            "us_per_call": 0.0,
            "n": store.n, "dims": spec.dim, "metric": spec.metric,
            "lid": round(lid_mle(store, queries), 2),
            "lrc": round(lrc(store, queries), 3),
            "dist_filt_rel_cost": round(
                dist_filter_relative_cost(spec.dim), 2),
        })
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    emit(run(), "table2")
