"""Mutability bench: the crash-consistent live-ingestion tier under load
(DESIGN.md §12).

Three measured axes on one WAL-backed `MutableIndex` (storage engine
attached, so every mutation / scan / checkpoint / compaction flows
through the buffer pool):

  delta-fill sweep   at each fill level of the LSM delta tier: merged
                     search latency + per-query delta-scan counters for
                     bruteforce and graph strategies, exact-recall check
                     against the rebuild oracle (must be 1.0 — the merge
                     is bit-identical, not approximate), the modeled
                     delta-scan tax, and the `should_compact` decision
  write path         cumulative write amplification (WAL bytes + 8 KiB
                     page write-backs over user payload bytes) after the
                     ingest stream, a checkpoint, and a compaction, plus
                     compaction's own page I/O and the post-compaction
                     recall delta vs a cold rebuild (must be within 0.02)
  crash matrix       kill-at-every-record-boundary recovery over a
                     scripted op stream: counts crash points and asserts
                     recovered searches are bit-identical to the durable
                     prefix reference (the tests' harness, summarized as
                     a benchmark gate)

Emits one JSON record to BENCH_mutability.json; `--tiny` (CI smoke)
writes the gitignored .tiny variant.

    PYTHONPATH=src python benchmarks/bench_mutability.py [--tiny]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import SearchParams, filtered_knn
from repro.core import costmodel
from repro.core.mutable import MutableIndex, rebuild_oracle_store
from repro.data import DatasetSpec, make_dataset
from repro.storage import wal as W

SELECTIVITY = 0.5


def _mk(tmpdir, tag, base, **kw):
    return MutableIndex(base, os.path.join(tmpdir, f"wal_{tag}"),
                        os.path.join(tmpdir, f"ck_{tag}"), **kw)


def _oracle_ids(index, bitmaps, queries, k):
    store, live = rebuild_oracle_store(index)
    bm = np.asarray(bitmaps, np.uint32)
    w = live.shape[0]
    if bm.shape[-1] < w:
        bm = np.concatenate([bm, np.zeros(
            bm.shape[:-1] + (w - bm.shape[-1],), np.uint32)], -1)
    return np.asarray(filtered_knn(store, jnp.asarray(queries),
                                   jnp.asarray(bm & live[None]), k)[1])


def _bitmaps(rng, nq, words, sel):
    bits = rng.rand(nq, words * 32) < sel
    return np.packbits(bits, axis=-1, bitorder="little").view(np.uint32)


def _timed_search(idx, queries, bm, params, method, reps=3):
    res = idx.search(queries, bm, params, method=method)   # warm compile
    jax.block_until_ready(res.dists)
    t0 = time.perf_counter()
    for _ in range(reps):
        res = idx.search(queries, bm, params, method=method)
        jax.block_until_ready(res.dists)
    dt = (time.perf_counter() - t0) / reps
    return res, dt * 1e3


def _fill_sweep(idx, rng, queries, fills, k):
    """Ingest to each fill level; measure merged-search behavior there."""
    nq = queries.shape[0]
    p_bf = SearchParams(k=k, strategy="bruteforce")
    p_gr = SearchParams(k=k, strategy="sweeping", ef_search=48,
                        beam_width=48, max_hops=200)
    out = []
    for fill in fills:
        target = int(round(fill * idx.delta_capacity))
        while idx.delta.count < target:
            m = min(64, target - idx.delta.count)
            idx.insert(rng.randn(m, idx.store.dim).astype(np.float32))
        if target:  # tombstone a slice of both base and delta rows
            dead = rng.choice(idx.base_n + idx.delta.count,
                              size=max(1, target // 16), replace=False)
            idx.delete(dead.astype(np.int64))
        bm = _bitmaps(rng, nq, idx.words(), SELECTIVITY)
        res, ms_bf = _timed_search(idx, jnp.asarray(queries),
                                   jnp.asarray(bm), p_bf, "bruteforce")
        oracle = _oracle_ids(idx, bm, queries, k)
        exact = bool(np.array_equal(oracle, np.asarray(res.ids)))
        _, ms_gr = _timed_search(idx, jnp.asarray(queries),
                                 jnp.asarray(bm), p_gr, "sweeping")
        n_delta = idx.delta.count
        wal_bytes = idx.wal.offset
        pw = idx.engine.pool.counters.page_writes
        out.append(dict(
            fill=round(n_delta / idx.delta_capacity, 3),
            n_delta=n_delta, tombstones=int(idx.tombstones.count),
            bruteforce_ms=round(ms_bf, 3), sweeping_ms=round(ms_gr, 3),
            oracle_exact=exact,
            delta_distance_comps=int(np.asarray(
                res.delta.stats.distance_comps).sum()),
            modeled_delta_cycles=round(costmodel.delta_scan_cycles(
                n_delta, idx.store.dim, SELECTIVITY, k=k), 1),
            should_compact=bool(costmodel.should_compact(
                n_delta, idx.delta_capacity, idx.base_n, idx.store.dim,
                SELECTIVITY)),
            write_amplification=round(costmodel.write_amplification(
                idx.user_bytes, pw, wal_bytes=wal_bytes), 3)))
        assert exact, f"merged search diverged from oracle at fill {fill}"
    return out


def _recall(ids, gt, k):
    return float(sum(len(set(gt[i]) & set(ids[i])) for i in range(len(gt)))
                 / (len(gt) * k))


def _compaction_phase(idx, rng, queries, tmpdir, k, build_kw):
    """Checkpoint + compact the swept index; compare recall against a
    cold rebuild over the same union."""
    nq = queries.shape[0]
    ck_writes = idx.engine.account_checkpoint(idx.delta.count)
    idx.checkpoint()
    union = np.concatenate([np.asarray(idx.store.vectors),
                            idx.delta.vectors[:idx.delta.count]])
    t0 = time.perf_counter()
    idx.compact()
    compact_s = time.perf_counter() - t0
    # the rebuilt engine's counters at this instant = compaction I/O
    cpw = idx.engine.pool.counters.page_writes
    cold = _mk(tmpdir, "cold", union, **build_kw)
    bm = np.full((nq, idx.words()), 0xFFFFFFFF, np.uint32)
    p = SearchParams(k=k, strategy="scann", num_leaves_to_search=4)
    gt = _oracle_ids(idx, bm, queries, k)
    got = np.asarray(idx.search(jnp.asarray(queries), jnp.asarray(bm), p,
                                method="scann").ids)
    ref = np.asarray(cold.search(
        jnp.asarray(queries),
        jnp.asarray(bm[:, :cold.words()]), p, method="scann").ids)
    r_live, r_cold = _recall(got, gt, k), _recall(ref, gt, k)
    cold.close()
    assert r_live >= r_cold - 0.02, (r_live, r_cold)
    return dict(compact_seconds=round(compact_s, 3),
                compaction_page_writes=int(cpw),
                checkpoint_page_writes=int(ck_writes["page_writes"]),
                recall_compacted=round(r_live, 4),
                recall_cold_rebuild=round(r_cold, 4),
                recall_delta=round(r_live - r_cold, 4))


def _crash_matrix(tmpdir, rng, dim, k):
    """Kill-at-every-boundary recovery sweep (bruteforce comparison)."""
    base = rng.randn(200, dim).astype(np.float32)
    queries = rng.randn(4, dim).astype(np.float32)
    kw = dict(delta_capacity=32, with_graph=False, with_scann=False)
    idx = _mk(tmpdir, "crash", base, **kw)
    bm = np.full((4, idx.words()), 0xFFFFFFFF, np.uint32)
    p = SearchParams(k=k, strategy="bruteforce")

    def snap(ix):
        r = ix.search(jnp.asarray(queries), jnp.asarray(bm), p)
        return np.asarray(r.ids).copy()

    snaps = {0: snap(idx)}
    for i in range(6):
        if i % 3 == 2:
            idx.delete(rng.randint(0, idx.base_n + idx.delta.count,
                                   size=3).astype(np.int64))
        else:
            idx.insert(rng.randn(4, dim).astype(np.float32))
        snaps[idx.applied_lsn] = snap(idx)
    recs = idx.wal.replay()
    points, prev = [(0, 0)], 0
    for r in recs:
        points.append((r.offset + r.length // 2, prev))
        points.append((r.end, r.lsn))
        prev = r.lsn
    identical = 0
    for i, (cut, lsn) in enumerate(points):
        crashed = idx.wal.crash_copy(
            os.path.join(tmpdir, f"crash_{i}"), at_bytes=cut)
        r_idx = MutableIndex.recover(
            base, crashed, os.path.join(tmpdir, f"ck_crash_{i}"), **kw)
        ok = (r_idx.applied_lsn == lsn
              and np.array_equal(snaps[lsn], snap(r_idx)))
        identical += int(ok)
        r_idx.close()
    idx.close()
    assert identical == len(points), f"{identical}/{len(points)}"
    return dict(crash_points=len(points), bit_identical=True)


def run(tiny: bool) -> dict:
    import tempfile
    if tiny:
        spec = DatasetSpec("mut-tiny", 2_000, 32, "l2", clusters=16)
        delta_cap, fills, nq, k = 128, (0.5, 1.0), 8, 10
    else:
        spec = DatasetSpec("mut-bench", 8_000, 48, "l2", clusters=32)
        delta_cap, fills, nq, k = 512, (0.25, 0.5, 0.75, 1.0), 16, 10
    store, queries = make_dataset(spec, num_queries=nq, seed=0)
    queries = np.asarray(queries, np.float32)
    rng = np.random.RandomState(1)
    tmpdir = tempfile.mkdtemp(prefix="bench_mut_")
    build_kw = dict(delta_capacity=delta_cap, num_leaves=16, graph_m=8,
                    ef_construction=48, seed=0, with_storage=True)
    idx = _mk(tmpdir, "main", np.asarray(store.vectors), **build_kw)

    out = {"bench": "mutability", "backend": jax.default_backend(),
           "tiny": tiny, "n": store.n, "dim": store.dim,
           "delta_capacity": delta_cap, "selectivity": SELECTIVITY,
           "queries": nq, "k": k,
           "wal_record_header_bytes": W.HEADER_BYTES}
    out["fill_sweep"] = _fill_sweep(idx, rng, queries, fills, k)
    print("# fill sweep:", json.dumps(out["fill_sweep"]))
    out["compaction"] = _compaction_phase(idx, rng, queries, tmpdir, k,
                                          build_kw)
    print("# compaction:", json.dumps(out["compaction"]))
    idx.close()
    out["crash_matrix"] = _crash_matrix(tmpdir, rng, store.dim, k)
    print("# crash matrix:", json.dumps(out["crash_matrix"]))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="small fresh-built dataset (CI smoke)")
    args = ap.parse_args()
    result = run(tiny=args.tiny)
    line = json.dumps(result)
    # --tiny (CI smoke) must not clobber the tracked full record
    name = "BENCH_mutability.tiny.json" if args.tiny \
        else "BENCH_mutability.json"
    path = os.path.join(os.path.dirname(__file__), "..", name)
    with open(path, "w") as f:
        f.write(line + "\n")
    print(line)
    assert result["crash_matrix"]["bit_identical"]
    assert all(r["oracle_exact"] for r in result["fill_sweep"])
    assert abs(result["compaction"]["recall_delta"]) <= 1.0  # reported
    assert result["compaction"]["recall_compacted"] >= \
        result["compaction"]["recall_cold_rebuild"] - 0.02


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
