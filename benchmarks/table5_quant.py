"""Paper Table 5: ScaNN quantization/PCA ablation — latency speedup vs the
non-PCA index across selectivities (wall time, CPU)."""
from __future__ import annotations

import time

import jax

from benchmarks.common import (emit, get_bitmaps, get_dataset, get_scann,
                               ground_truth, mean_recall)
from repro.core import ScannExecutor, SearchParams

SELS = (0.01, 0.05, 0.2, 0.5, 0.8)


def _run_once(idx, store, queries, bm, p):
    ex = ScannExecutor(idx, store, pipeline="batched")
    jax.block_until_ready(ex.search(queries, bm, p).ids)
    t0 = time.perf_counter()
    ids = ex.search(queries, bm, p).ids
    jax.block_until_ready(ids)
    return (time.perf_counter() - t0) / queries.shape[0] * 1e6, ids


def run(ds="openai5m") -> list[dict]:
    store, queries = get_dataset(ds)
    base = get_scann(ds, pca=False)
    pca = get_scann(ds, pca=True)
    rows = []
    for sel in SELS:
        bm = get_bitmaps(ds, sel, "none")
        _, tid = ground_truth(ds, sel, "none")
        p = SearchParams(k=10, num_leaves_to_search=32, reorder_factor=6)
        t_base, ids_b = _run_once(base, store, queries, bm, p)
        t_pca, ids_p = _run_once(pca, store, queries, bm, p)
        rows.append({
            "name": f"table5/{ds}/pca_quant/sel={sel}",
            "us_per_call": t_pca,
            "speedup_vs_raw": round(t_base / max(t_pca, 1e-9), 2),
            "recall_raw": round(mean_recall(ids_b, tid), 3),
            "recall_pca": round(mean_recall(ids_p, tid), 3),
        })
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    emit(run(), "table5")
