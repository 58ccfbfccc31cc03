"""Smoke run of the filtered-vector-search serving path on a TPU.

    python chip_smoke.py [--seed S]          # one chip
    python chip_smoke.py --four-chips        # the sharded phase, 4 chips

One process owns the chip.  It refuses to run anywhere but on a TPU (no
CPU fallback, since Pallas kernels would then run in interpret mode),
builds a SIFT1M-shaped store from the seed (1,000,000 x 128, L2: the
paper's sift10m cut 10x for host build time), and drives it through the
entry points a user calls: `make_executor` for every method on both the
jnp path and the Pallas kernels, `ContinuousServer` for open-loop
serving, and `MutableIndex` for live inserts and deletes.  Every phase is
checked against the chip's own exact reference (`filtered_knn`); any
failed check raises, so the run exits non-zero.  Earlier lines report
per-phase seconds (one-chip smoke times, not benchmark numbers), recalls
and device memory; the last line is one JSON object naming the device.

`--four-chips` runs only the mesh path: lockstep sharded graph search
over a 4-device mesh against the one-chip `GraphExecutor` (ids, dists
and the seven counters must be equal), and `DistributedScannExecutor`
against its recall floor.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import resource
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

ROWS, DIM, QUERIES, K = 1_000_000, 128, 64, 10
CLUSTERS = 128
NUM_LEAVES = 1000                 # ScaNN leaves, about sqrt(ROWS)
SELECTIVITIES = (0.01, 0.1, 0.5)
CORRELATIONS = ("high_pos", "none")
GRAPH_M = 16
FOUR_CHIP_ROWS = 250_000
SHARDS = 4

# Every method through make_executor; all but bruteforce also run with
# use_pallas=True.  Floors on mean recall@10 over the six workloads x 64
# queries.  The CPU rehearsal of the jnp path at 250,000 rows gave
# sweeping 0.908, sweeping_sq8 0.907, navix 0.812, iterative_scan 0.893,
# scann 1.0, adaptive 0.872; a v5e run at 1,000,000 rows gave 0.839,
# 0.838, 0.685, 0.816, 1.0 and 0.691 on both paths.  The floors sit about
# 0.1 below the 1M values (adaptive lower: its choices follow the ScaNN
# index it probes for selectivity).
RECALL_FLOORS = {
    "bruteforce": 1.0,
    "sweeping": 0.7,
    "sweeping_sq8": 0.7,
    "navix": 0.55,
    "iterative_scan": 0.7,
    "scann": 0.8,
    "adaptive": 0.5,
}
DISTRIBUTED_SCANN_FLOOR = 0.8
# Tolerances of the repo's kernel parity tests (tests/test_frontier.py,
# tests/test_leaf_scan_batched.py) and of its end-to-end ScaNN parity test
# (tests/test_scann_and_hnsw.py).
KERNEL_ATOL, KERNEL_RTOL = 2e-3, 1e-3
SCANN_RTOL = 1e-4


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print(*parts, flush=True)


def require_tpu(count: int):
    """The chip, before any work: a CPU fallback would interpret every
    kernel and prove nothing."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU device(s), JAX sees "
                 f"{len(devs)} {devs[0].platform!r} device(s)")
    return devs


class Timer:
    def __init__(self):
        self.t = time.perf_counter()

    def lap(self) -> float:
        t, self.t = self.t, time.perf_counter()
        return self.t - t


def _ready(x):
    import jax
    return jax.block_until_ready(x)


# ---------------------------------------------------------------------------
# Build
# ---------------------------------------------------------------------------

def build(seed: int, rows: int, queries: int, num_leaves: int):
    """Store (with its SQ8 shadow), queries, HNSW graph, ScaNN index and
    the six (selectivity, correlation) bitmap sets, all from `seed`."""
    import jax.numpy as jnp
    from repro.core import WorkloadSpec, build_scann, generate_bitmaps
    from repro.core.hnsw import build_graph
    from repro.data import DatasetSpec, make_dataset_streamed

    t = Timer()
    spec = DatasetSpec("sift1m", rows, DIM, "l2", clusters=CLUSTERS)
    store, q = make_dataset_streamed(spec, num_queries=queries, seed=seed)
    q = jnp.asarray(q)
    _ready(store.vectors)
    say(f"[build] data {rows}x{DIM} l2 + sq8 shadow: {t.lap():.1f} s")
    g = _ready(build_graph(store, m=GRAPH_M, seed=seed))
    say(f"[build] hnsw graph {tuple(g.neighbors.shape)}: {t.lap():.1f} s")
    idx = _ready(build_scann(store, num_leaves=num_leaves, seed=seed))
    say(f"[build] scann leaves {tuple(idx.leaf_tiles.shape)}: "
        f"{t.lap():.1f} s")
    workloads = {}
    for i, (sel, corr) in enumerate(
            (s, c) for c in CORRELATIONS for s in SELECTIVITIES):
        workloads[f"sel{sel}/{corr}"] = _ready(generate_bitmaps(
            store, q, WorkloadSpec(sel, corr), seed=seed + 1 + i))
    say(f"[build] bitmaps {len(workloads)} workloads x {queries} queries: "
        f"{t.lap():.1f} s")
    return store, q, g, idx, workloads


def device_memory(tag: str) -> None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    host_peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    say(f"[{tag}] device bytes in use {stats.get('bytes_in_use')}, "
        f"peak {stats.get('peak_bytes_in_use')}; host peak rss {host_peak}")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def kernel_parity(store, q, idx, workloads, seed: int) -> None:
    """Each Pallas kernel against its jnp oracle on real shapes: pass and
    keep masks bit-identical, scores within the parity tests' tolerance."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops

    rng = np.random.RandomState(seed)
    bms = workloads[f"sel{SELECTIVITIES[1]}/none"]
    nq = q.shape[0]
    ids = rng.randint(0, store.n, (nq, 64)).astype(np.int32)
    ids[rng.rand(nq, 64) < 0.1] = -1
    ids = jnp.asarray(ids)
    safe = jnp.maximum(ids, 0)
    excl = jnp.asarray(rng.rand(nq, 64).astype(np.float32))
    tau = jnp.asarray(rng.rand(nq, 1).astype(np.float32) * 2.0)
    f32 = (store.vectors[safe], store.norms_sq[safe])
    sq8 = (store.q_vectors[safe], store.q_scale, store.q_mean,
           store.q_norms_sq[safe])
    nl = min(32, idx.num_leaves)
    u = min(256, idx.num_leaves)
    cases = {
        "frontier_scan": (ops.frontier_scan, (q, *f32, ids, bms)),
        "frontier_scan_sq8": (ops.frontier_scan_sq8, (q, *sq8, ids, bms)),
        "frontier_scan_excl": (ops.frontier_scan_excl,
                               (q, *f32, ids, bms, excl, tau)),
        "frontier_scan_excl_sq8": (ops.frontier_scan_excl_sq8,
                                   (q, *sq8, ids, bms, excl, tau)),
        "leaf_scan": (ops.leaf_scan,
                      (q[0], idx.leaf_tiles[:nl], idx.leaf_rowids[:nl],
                       idx.scale, idx.mean, bms[0])),
        "leaf_scan_batched": (ops.leaf_scan_batched,
                              (q, idx.leaf_tiles[:u], idx.leaf_rowids[:u],
                               idx.scale, idx.mean, bms,
                               idx.row_norms_sq[:u])),
        "distance_matrix": (ops.distance_matrix, (q, idx.leaf_centroids)),
    }
    for name, (fn, args) in cases.items():
        t = Timer()
        got = _ready(fn(*args, use_pallas=True))
        want = _ready(fn(*args, use_pallas=False))
        secs = t.lap()
        got = got if isinstance(got, tuple) else (got,)
        want = want if isinstance(want, tuple) else (want,)
        a, b = np.asarray(got[0]), np.asarray(want[0])
        fa, fb = np.isfinite(a), np.isfinite(b)
        check(np.array_equal(fa, fb), f"{name}: +inf pattern differs")
        err = float(np.max(np.abs(a[fa] - b[fb]) /
                           (KERNEL_ATOL + KERNEL_RTOL * np.abs(b[fb])),
                           initial=0.0))
        check(err <= 1.0, f"{name}: scores outside atol={KERNEL_ATOL} "
              f"rtol={KERNEL_RTOL} (worst {err:.3g} of the bound)")
        same_bits = bool(np.array_equal(a, b))
        for m, (x, y) in enumerate(zip(got[1:], want[1:])):
            check(np.array_equal(np.asarray(x), np.asarray(y)),
                  f"{name}: mask {m} differs")
        say(f"[kernels] {name}: masks equal, scores within tolerance "
            f"(worst {err:.3g} of the bound, bit-identical {same_bits}), "
            f"{secs:.1f} s with compile")


def query_methods(store, q, g, idx, workloads, params) -> dict:
    """Every method on both paths; returns {(method, pallas): ids}."""
    import numpy as np
    from repro.core import filtered_knn, make_executor, recall_at_k

    truth = {}
    t = Timer()
    for w, bm in workloads.items():
        truth[w] = _ready(filtered_knn(store, q, bm, K))
    say(f"[query] filtered_knn truth for {len(workloads)} workloads: "
        f"{t.lap():.1f} s with compile")
    results = {}
    for method, floor in RECALL_FLOORS.items():
        for pallas in ((False,) if method == "bruteforce" else
                       (False, True)):
            ex = make_executor(method, store, graph=g, index=idx,
                               use_pallas=pallas)
            t = Timer()
            first = None
            recalls, out = [], {}
            for w, bm in workloads.items():
                res = ex.search(q, bm, params)
                _ready((res.ids, res.dists))
                if first is None:
                    first = t.lap()
                out[w] = (np.asarray(res.ids), np.asarray(res.dists),
                          res.strategy)
                recalls.append(np.asarray(
                    recall_at_k(res.ids, truth[w][1], K)))
            steady = t.lap() / max(len(workloads) - 1, 1)
            # one more timed call on a compiled shape
            w0 = next(iter(workloads))
            t.lap()
            _ready(ex.search(q, workloads[w0], params).ids)
            again = t.lap()
            rec = float(np.mean(np.concatenate(recalls)))
            per = " ".join(f"{w}={float(np.mean(r)):.3f}"
                           for w, r in zip(workloads, recalls))
            tag = "pallas" if pallas else "jnp"
            say(f"[query] {method}/{tag}: recall@10 {rec:.4f} "
                f"(floor {floor}); first call {first:.1f} s, steady "
                f"{steady:.3f} s/batch, repeat {again:.3f} s; {per}")
            check(rec >= floor, f"{method}/{tag}: recall@10 {rec:.4f} "
                  f"below its floor {floor}")
            if method == "bruteforce":
                for w in workloads:
                    check(np.array_equal(out[w][0],
                                         np.asarray(truth[w][1])),
                          f"bruteforce ids differ from filtered_knn on {w}")
                    check(np.array_equal(out[w][1],
                                         np.asarray(truth[w][0])),
                          f"bruteforce dists differ from filtered_knn "
                          f"on {w}")
                say("[query] bruteforce ids and dists equal filtered_knn "
                    "on every workload")
            results[(method, pallas)] = out
        if method != "bruteforce":
            compare_paths(method, results[(method, False)],
                          results[(method, True)])
    return truth


def same_answer(what: str, ids, dists, want_ids, want_dists) -> str:
    """Ids must be equal, and dists equal up to f32 rounding: on the chip,
    two programs that XLA fuses differently (a 16-slot serving step and a
    64-query search loop, a 512-row delta scan and a full-table scan) may
    sum a distance in another order.  Says whether they were bit-identical
    (on the CPU they are)."""
    import numpy as np
    got, want = np.asarray(dists), np.asarray(want_dists)
    check(np.array_equal(np.asarray(ids), np.asarray(want_ids)),
          f"{what}: ids differ")
    fin = np.isfinite(want)
    check(np.array_equal(np.isfinite(got), fin),
          f"{what}: +inf pattern differs")
    worst = float(np.max(np.abs(got[fin] - want[fin]) /
                         (KERNEL_ATOL + KERNEL_RTOL * np.abs(want[fin])),
                         initial=0.0))
    check(worst <= 1.0, f"{what}: dists beyond atol={KERNEL_ATOL} "
          f"rtol={KERNEL_RTOL} (worst {worst:.3g} of the bound)")
    return (f"ids equal, dists bit-identical {np.array_equal(got, want)} "
            f"(worst {worst:.3g} of the atol={KERNEL_ATOL} "
            f"rtol={KERNEL_RTOL} bound)")


def compare_paths(method: str, jnp_out: dict, pallas_out: dict) -> None:
    """The kernel path against the jnp path, end to end."""
    import numpy as np
    agree = total = 0
    worst = 0.0
    identical = True
    for w in jnp_out:
        ia, da, _ = jnp_out[w]
        ib, db, _ = pallas_out[w]
        identical &= bool(np.array_equal(ia, ib) and np.array_equal(da, db))
        for r in range(ia.shape[0]):
            common, pa, pb = np.intersect1d(ia[r], ib[r],
                                            return_indices=True)
            keep = common >= 0
            agree += int(keep.sum())
            total += int((ia[r] >= 0).sum())
            x, y = da[r][pa[keep]], db[r][pb[keep]]
            worst = max(worst, float(np.max(
                np.abs(x - y) / (KERNEL_ATOL + KERNEL_RTOL * np.abs(y)),
                initial=0.0)))
    frac = agree / max(total, 1)
    say(f"[query] {method}: pallas vs jnp ids agree {frac:.4f}, shared ids' "
        f"dists worst {worst:.3g} of the kernel bound, bit-identical "
        f"{identical}")
    check(worst <= 1.0, f"{method}: pallas and jnp dists disagree beyond "
          f"atol={KERNEL_ATOL} rtol={KERNEL_RTOL}")
    if method == "scann":
        for w in jnp_out:
            check(np.array_equal(jnp_out[w][0], pallas_out[w][0]),
                  f"scann: pallas ids differ from jnp ids on {w}")
            fin = np.isfinite(jnp_out[w][1])
            check(np.allclose(jnp_out[w][1][fin], pallas_out[w][1][fin],
                              rtol=SCANN_RTOL, atol=0.0),
                  f"scann: pallas dists beyond rtol={SCANN_RTOL} on {w}")


def serve(store, q, g, workloads, params) -> None:
    """64 open-loop requests of mixed selectivity through ContinuousServer;
    each harvest must equal the one-shot executor's answer."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.executor import GraphExecutor
    from repro.serving.continuous import (ContinuousServer, Request,
                                          results_in_order)

    names = list(workloads)
    nq = q.shape[0]
    bms = np.stack([np.asarray(workloads[names[i % len(names)]])[i]
                    for i in range(nq)])
    qs = np.asarray(q)
    reqs = [Request(rid=i, query=qs[i], bitmap=bms[i], arrival=i // 8)
            for i in range(nq)]
    ex = GraphExecutor(g, store, strategy="sweeping")
    t = Timer()
    ref = ex.search(q, jnp.asarray(bms), params)
    _ready(ref.ids)
    one_shot = t.lap()
    server = ContinuousServer(ex, params, width=16, hop_chunk=8)
    records, info = server.serve(reqs, mode="continuous")
    first = t.lap()
    records, info = server.serve(reqs, mode="continuous")
    again = t.lap()
    ids, dists = results_in_order(records, nq, params.k)
    say(f"[serve] {nq} requests, {info['ticks']} ticks, {info['compiles']} "
        f"compiled programs, slot utilization "
        f"{info['slot_utilization']:.3f}; one-shot {one_shot:.1f} s, serve "
        f"{first:.1f} s with compile, {again:.1f} s again")
    same = same_answer("ContinuousServer vs the one-shot executor", ids,
                       dists, ref.ids, ref.dists)
    say(f"[serve] harvested answers equal the one-shot executor's: {same}")


def mutate(store, q, workloads, params, seed: int) -> None:
    """Inserts and deletes through MutableIndex; the merged bruteforce
    answer must equal filtered_knn over the rebuilt oracle store."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import filtered_knn
    from repro.core.mutable import MutableIndex, rebuild_oracle_store

    rng = np.random.RandomState(seed + 7)
    nq = q.shape[0]
    base = np.asarray(store.vectors)
    t = Timer()
    with tempfile.TemporaryDirectory() as tmp:
        mi = MutableIndex(base, os.path.join(tmp, "wal.log"),
                          os.path.join(tmp, "ckpt"), metric="l2",
                          delta_capacity=512, with_graph=False,
                          with_scann=False, seed=seed)
        build_s = t.lap()
        bm = np.zeros((nq, mi.words()), np.uint32)
        src = np.asarray(workloads[f"sel{SELECTIVITIES[1]}/none"])
        bm[:, :src.shape[1]] = src
        # delete base rows some queries rank in their top 3, so deletes
        # change answers
        hit = np.unique(np.asarray(mi.search(q, bm, params).ids)[:, :3])
        # new rows near the queries, so they compete for the top-k
        new = (np.asarray(q)[rng.randint(0, nq, 300)]
               + 0.05 * rng.randn(300, DIM)).astype(np.float32)
        new_ids = mi.insert(new)
        for i in new_ids:                       # the filter admits them
            bm[:, i >> 5] |= np.uint32(1 << (int(i) & 31))
        dead = np.unique(np.concatenate(
            [hit[hit >= 0][:100], new_ids[rng.permutation(300)[:100]]]))
        mi.delete(dead)
        res = mi.search(q, bm, params)
        _ready(res.ids)
        mut_s = t.lap()
        ostore, live = rebuild_oracle_store(mi)
        od, oi = filtered_knn(ostore, q, jnp.asarray(bm & live[None, :]),
                              params.k)
        mi.close()
    same = same_answer("MutableIndex merge vs the rebuild oracle", res.ids,
                       res.dists, oi, od)
    say(f"[mutable] base {mi.base_n} rows, inserted 300, deleted "
        f"{len(dead)} ({int(np.isin(dead, new_ids).sum())} of them new); "
        f"merged answers equal the rebuild oracle: {same}; build "
        f"{build_s:.1f} s, mutate + search {mut_s:.1f} s")


def smoke(seed: int, rows: int = ROWS, queries: int = QUERIES,
          num_leaves: int = NUM_LEAVES) -> None:
    from repro.core import SearchParams
    t = Timer()
    store, q, g, idx, workloads = build(seed, rows, queries, num_leaves)
    device_memory("build")
    params = SearchParams(k=K)
    kernel_parity(store, q, idx, workloads, seed)
    query_methods(store, q, g, idx, workloads, params)
    device_memory("query")
    serve(store, q, g, workloads, params)
    mutate(store, q, workloads, params, seed)
    device_memory("all")
    say(f"[total] {t.lap():.1f} s")


def four_chips(seed: int, rows: int = FOUR_CHIP_ROWS,
               queries: int = QUERIES, num_leaves: int | None = None) -> None:
    """Lockstep sharded graph search on a real 4-device mesh vs the
    one-chip GraphExecutor, and DistributedScannExecutor on that mesh."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import SearchParams, filtered_knn, recall_at_k
    from repro.core.distributed import (DistributedScannExecutor,
                                        shard_index, sharded_graph_search_fn)
    from repro.core.executor import GraphExecutor
    from repro.core.types import SearchStats

    t = Timer()
    num_leaves = num_leaves or max(16, int(np.sqrt(rows)))
    store, q, g, idx, workloads = build(seed, rows, queries, num_leaves)
    mesh = Mesh(np.asarray(jax.devices()[:SHARDS]), ("shard",))
    params = SearchParams(k=K, strategy="sweeping")
    ex = GraphExecutor(g, store, strategy="sweeping")
    fn = sharded_graph_search_fn(g, store, SHARDS, params, mesh=mesh,
                                 axis="shard")
    tier_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(fn.args))
    per_chip = {}
    for leaf in jax.tree.leaves(fn.args):
        for s in leaf.addressable_shards:
            per_chip[s.device.id] = per_chip.get(s.device.id, 0) \
                + s.data.nbytes
    say(f"[mesh] sharded graph tiers {tier_bytes} bytes; per chip "
        + ", ".join(f"{d}: {b} ({b / tier_bytes:.3f})"
                    for d, b in sorted(per_chip.items())))
    check(all(abs(b / tier_bytes - 1 / SHARDS) < 0.01
              for b in per_chip.values()),
          "a chip does not hold about 1/4 of the sharded graph tiers")
    fields = [f.name for f in dataclasses.fields(SearchStats)]
    t.lap()
    for w, bm in workloads.items():
        d, ids, stats = _ready(fn(q, bm))
        ref = ex.search(q, bm, params)
        same = same_answer(f"sharded vs one chip on {w}", ids, d, ref.ids,
                           ref.dists)
        for f in fields:
            check(np.array_equal(np.asarray(getattr(stats, f)),
                                 np.asarray(getattr(ref.stats, f))),
                  f"sharded counter {f} differs from one chip on {w}")
        say(f"[mesh] {w}: {same}, {len(fields)} counters equal")
    say(f"[mesh] lockstep S={SHARDS} ids, dists and {len(fields)} counters "
        f"equal one chip on {len(workloads)} workloads; {t.lap():.1f} s "
        f"with compile")
    sharded = shard_index(idx, store, mesh, "shard")
    leaves = jax.tree.leaves((sharded.index.leaf_tiles,
                              sharded.index.leaf_rowids))
    for leaf in leaves:
        frac = max(s.data.nbytes for s in leaf.addressable_shards) \
            / leaf.nbytes
        check(abs(frac - 1 / SHARDS) < 0.01,
              f"scann leaves {leaf.shape}: a chip holds {frac:.3f}")
    dex = DistributedScannExecutor(sharded)
    recalls = []
    for w, bm in workloads.items():
        res = dex.search(q, bm, params)
        truth = filtered_knn(store, q, bm, K)
        recalls.append(np.asarray(recall_at_k(res.ids, truth[1], K)))
    rec = float(np.mean(np.concatenate(recalls)))
    say(f"[mesh] DistributedScannExecutor over {SHARDS} chips (leaves "
        f"1/{SHARDS} per chip): recall@10 {rec:.4f} (floor "
        f"{DISTRIBUTED_SCANN_FLOOR}); {t.lap():.1f} s with compile")
    check(rec >= DISTRIBUTED_SCANN_FLOOR,
          f"distributed scann recall@10 {rec:.4f} below its floor")
    device_memory("mesh")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded phase")
    args = ap.parse_args(argv)
    devs = require_tpu(4 if args.four_chips else 1)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from repro.launch.compile_cache import configure_compile_cache
    say(f"[env] jax {jax.__version__}, {len(devs)} x {devs[0].device_kind}, "
        f"host cores {len(os.sched_getaffinity(0))} of {os.cpu_count()}, "
        f"compile cache {configure_compile_cache()}")
    if args.four_chips:
        four_chips(args.seed)
    else:
        smoke(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
