"""Distributed filtered vector search over the production mesh.

The paper's single-node PostgreSQL study scales out here (DESIGN.md §4):

  * ScaNN leaves (and their heap rows) are sharded across mesh devices.
    Each device runs the fused filtered leaf-scan on its shard, reranks its
    own candidates against its *local* full-precision rows (exact distances
    never cross devices), and contributes a local top-k.  The only
    collective is an all-gather of (devices × k) (dist, id) pairs — a few
    KB — followed by a replicated final top-k.  The collective-roofline
    term of FVS serving is therefore negligible by construction.
  * Graph search is query-parallel: queries shard over devices, the graph
    is replicated.  This is the honest TPU mapping of the paper's Table 1
    "Parallelism" row — graph traversal itself does not shard (dependent
    gathers), and the paper shows why trying is a system tax.
  * Index construction (k-means) is data-parallel: local assignment +
    psum centroid reduction (classic distributed Lloyd's).

Everything lowers under `shard_map` on an abstract mesh, so the multi-pod
dry-run can compile it for 512 devices from this CPU container.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.scann import (ScannIndex, _quant_pages_per_leaf,
                              build_scann)
from repro.core.types import SearchParams, SearchStats, VectorStore, \
    distance, heap_pages_per_vector, probe_bitmap, topk_smallest
from repro.kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class ShardedFVS:
    """Host-side container: per-device leaf/heap shards stacked on axis 0."""

    index: ScannIndex          # leaf arrays padded to devices × per-device
    store: VectorStore         # heap rows (row ids remain global)
    mesh: Mesh
    axis: str                  # mesh axis (or flattened axes) leaves shard on


def place_shards(tree, mesh: Mesh, axis: str):
    """Put every array leaf of `tree` on the mesh, split along its
    leading axis over mesh axis `axis` (each device holds 1/size)."""
    return jax.device_put(tree, NamedSharding(mesh, P(axis)))


def shard_index(index: ScannIndex, store: VectorStore, mesh: Mesh,
                axis: str) -> ShardedFVS:
    """Pad leaf count to a multiple of the axis size; device d owns leaves
    [d*Lp, (d+1)*Lp), placed there.  The quantizer, PCA and heap are
    replicated on every device (the heap stays globally addressed: rows
    are gathered only on the owner — local leaves only reference
    local-shard rows by build)."""
    nd = mesh.shape[axis]
    L = index.num_leaves
    pad = (-L) % nd
    if pad:
        index = dataclasses.replace(
            index,
            leaf_tiles=jnp.pad(index.leaf_tiles, ((0, pad), (0, 0), (0, 0))),
            leaf_rowids=jnp.pad(index.leaf_rowids, ((0, pad), (0, 0)),
                                constant_values=-1),
            leaf_centroids=jnp.pad(index.leaf_centroids,
                                   ((0, pad), (0, 0)),
                                   constant_values=jnp.inf),
        )
    leaves = place_shards(dict(leaf_tiles=index.leaf_tiles,
                               leaf_rowids=index.leaf_rowids,
                               leaf_centroids=index.leaf_centroids),
                          mesh, axis)
    rep = NamedSharding(mesh, P())
    index = dataclasses.replace(
        index, **leaves, **jax.device_put(
            dict(scale=index.scale, mean=index.mean, pca=index.pca), rep))
    store = dataclasses.replace(
        store, vectors=jax.device_put(store.vectors, rep),
        norms_sq=jax.device_put(store.norms_sq, rep))
    return ShardedFVS(index=index, store=store, mesh=mesh, axis=axis)


def distributed_search_raw(sharded: ShardedFVS, params: SearchParams,
                           use_pallas: bool = False,
                           heap_layout: str = "replicated",
                           with_stats: bool = False):
    """shard_map'd search over EXPLICIT array args (lowerable against
    ShapeDtypeStructs — used by launch/fvs_dryrun.py):
    fn(tiles, rowids, cents, scale, mean, pca, vectors, norms_sq,
       queries, bitmaps) -> (dists, ids).

    use_pallas=True runs the FUSED leaf-scan kernel (int8 tiles stream
    HBM→VMEM once; dequant+probe+score stay in VMEM) — §Perf FVS it2.

    heap_layout: "replicated" (default — full-precision rows on every
    device; correct for arbitrary kmeans row placement, used at test
    scale) or "leaf_ordered" (rows permuted into leaf-major order at
    build so each device's leaves reference its local heap slice —
    the production layout modeled by launch/fvs_dryrun.py).

    with_stats=True additionally returns per-query Table-6 counters as a
    third output, (Q, 7) int32 in SearchStats field order: each device
    counts its local work (leaves opened, valid/passing rows, reorder
    candidates, analytic page counters) and the counters cross the mesh
    with the SAME all-gather the (dist, id) pairs already ride — 28 more
    bytes per query, still collective-negligible."""
    mesh, axis = sharded.mesh, sharded.axis
    idx, store = sharded.index, sharded.store
    k = params.k
    nl = params.num_leaves_to_search
    metric = idx.metric
    qppl = _quant_pages_per_leaf(idx)
    ppv = heap_pages_per_vector(store.dim)

    n_total = sharded.store.n
    nd_axis = mesh.shape[axis]

    def local_search(tiles, rowids, cents, scale, mean, pca, vectors,
                     norms_sq, queries, bitmaps):
        # tiles: (Lp, C, dp) local shard. queries: (Q, d) replicated.
        if heap_layout == "leaf_ordered":
            offset = jax.lax.axis_index(axis) * (n_total // nd_axis)
        else:
            offset = 0

        def one(q, bm):
            proj, mu_p = pca[:-1], pca[-1]
            qp = jnp.matmul(q, proj,
                            precision=jax.lax.Precision.HIGHEST) - mu_p
            cd = distance(metric, qp[None], cents,
                          jnp.sum(cents * cents, -1))
            cd = jnp.where(jnp.isfinite(cents[:, 0]), cd, jnp.inf)
            nsel = min(max(1, -(-nl // mesh.shape[axis])), cents.shape[0])
            _, leaves = topk_smallest(cd, nsel)
            scores = kops.leaf_scan(qp, tiles[leaves], rowids[leaves],
                                    scale, mean, bm, metric,
                                    use_pallas=use_pallas)
            r = min(k * params.reorder_factor, nsel * tiles.shape[1])
            fs, fp = topk_smallest(scores.reshape(-1), r)
            rows = rowids[leaves].reshape(-1)[fp]
            ok = jnp.isfinite(fs) & (rows >= 0)
            local_rows = rows - offset
            ok &= (local_rows >= 0) & (local_rows < vectors.shape[0])
            safe = jnp.clip(local_rows, 0, vectors.shape[0] - 1)
            exact = distance(metric, q[None], vectors[safe], norms_sq[safe])
            exact = jnp.where(ok, exact, jnp.inf)
            ld, lp = topk_smallest(exact, k)
            lids = jnp.where(jnp.isinf(ld), -1, rows[lp])
            if not with_stats:
                return ld, lids
            # local Table-6 counters (single-node ScaNN semantics per
            # shard: fc = valid rows in opened leaves, dc = passing rows
            # + centroids scored + reorder candidates, analytic pages)
            n_reorder = ok.sum().astype(jnp.int32)
            fc = (rowids[leaves] >= 0).sum().astype(jnp.int32)
            n_pass = jnp.isfinite(scores).sum().astype(jnp.int32)
            cent_fin = jnp.isfinite(cents[:, 0]).sum().astype(jnp.int32)
            st = jnp.stack([
                n_pass + cent_fin + n_reorder,            # distance_comps
                fc,                                       # filter_checks
                jnp.int32(nsel),                          # hops (leaves)
                jnp.int32(nsel * qppl),                   # index pages
                n_reorder * ppv,                          # heap pages
                jnp.int32(0),                             # tmap_lookups
                n_reorder])                               # reorder_rows
            return ld, lids, st

        if with_stats:
            ld, lids, lst = jax.vmap(one)(queries, bitmaps)
        else:
            ld, lids = jax.vmap(one)(queries, bitmaps)   # (Q, k) local
        gd = jax.lax.all_gather(ld, axis, axis=1)        # (Q, nd, k)
        gi = jax.lax.all_gather(lids, axis, axis=1)
        q_ = gd.shape[0]
        gd = gd.reshape(q_, -1)
        gi = gi.reshape(q_, -1)
        fd, fpos = jax.vmap(lambda d_: topk_smallest(d_, k))(gd)
        fids = jnp.take_along_axis(gi, fpos, axis=1)
        fids = jnp.where(jnp.isinf(fd), -1, fids)
        if not with_stats:
            return fd, fids
        gst = jax.lax.all_gather(lst, axis, axis=1)      # (Q, nd, 7)
        return fd, fids, gst.sum(axis=1)

    pspec = P(axis)
    rep = P()
    vspec = P(axis) if heap_layout == "leaf_ordered" else rep
    return jax.shard_map(
        local_search, mesh=mesh,
        in_specs=(pspec, pspec, pspec, rep, rep, rep, vspec, vspec,
                  rep, rep),
        out_specs=(rep, rep, rep) if with_stats else (rep, rep),
        check_vma=False)


def distributed_search_fn(sharded: ShardedFVS, params: SearchParams,
                          use_pallas: bool = False,
                          heap_layout: str = "replicated",
                          with_stats: bool = False):
    """Distributed filtered-search step bound to a concrete store:
    (queries (Q, d), bitmaps (Q, W)) -> (dists (Q, k), ids[, stats]).
    The placed index tiers are arguments of the jitted step, not
    constants folded into it."""
    fn = jax.jit(distributed_search_raw(sharded, params,
                                        use_pallas=use_pallas,
                                        heap_layout=heap_layout,
                                        with_stats=with_stats))
    idx, store = sharded.index, sharded.store
    return partial(fn, idx.leaf_tiles, idx.leaf_rowids, idx.leaf_centroids,
                   idx.scale, idx.mean, idx.pca, store.vectors,
                   store.norms_sq)


# ---------------------------------------------------------------------------
# Distributed k-means index build (data-parallel Lloyd's with psum)
# ---------------------------------------------------------------------------

def distributed_kmeans_fn(mesh: Mesh, axis: str, k: int, iters: int,
                          metric: str = "l2"):
    """Returns jittable fn: (x_shard (N, d) sharded, init_cent (k, d)) ->
    centroids.  Local assignment, psum'd centroid sums — the canonical
    distributed index build (paper Table 3 build-time scaling, scaled out).
    """

    def local(x, cent):
        def step(cent, _):
            d = (jnp.sum(x * x, 1)[:, None] + jnp.sum(cent * cent, 1)[None]
                 - 2.0 * x @ cent.T)
            a = jnp.argmin(d, 1)
            one_hot = jax.nn.one_hot(a, k, dtype=x.dtype)
            sums = jax.lax.psum(one_hot.T @ x, axis)
            cnts = jax.lax.psum(one_hot.sum(0), axis)
            newc = sums / jnp.maximum(cnts, 1.0)[:, None]
            return jnp.where((cnts > 0)[:, None], newc, cent), None

        cent, _ = jax.lax.scan(step, cent, None, length=iters)
        return cent

    fn = jax.shard_map(local, mesh=mesh, in_specs=(P(axis), P()),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn)


def build_sharded_scann(store: VectorStore, mesh: Mesh, axis: str,
                        num_leaves: int, **kw) -> ShardedFVS:
    """Build on host (small scale) then shard leaves over the mesh axis.

    Leaves are assigned to devices contiguously; the heap rows referenced by
    a device's leaves live with the device (row locality by construction).
    """
    idx = build_scann(store, num_leaves=num_leaves, **kw)
    return shard_index(idx, store, mesh, axis)


class DistributedScannExecutor:
    """Executor-protocol port of the sharded ScaNN path (DESIGN.md §6).

    Consumers (serving/rag.py, launch/fvs_dryrun.py) hold an Executor and
    never touch the mesh plumbing.  Per-query SearchStats ride the
    existing all-gather as a (Q, 7) int32 block (`with_stats`), so
    table6/fig10-style accounting covers the mesh path too; pass
    `with_stats=False` to drop the counters from the collective (the
    launch dry-run compiles the raw fn without them).
    """

    name = "scann_distributed"

    def __init__(self, sharded: ShardedFVS, use_pallas: bool = False,
                 heap_layout: str = "replicated", with_stats: bool = True):
        self.sharded = sharded
        self.store = sharded.store
        self.use_pallas = use_pallas
        self.heap_layout = heap_layout
        self.with_stats = with_stats
        self._fns: dict = {}      # params -> jitted bound search fn

    def plan(self, queries, bitmaps, params: SearchParams):
        from repro.core.executor import SearchPlan
        if params.strategy != "scann":
            params = dataclasses.replace(params, strategy="scann")
        return SearchPlan("scann", params, queries, bitmaps)

    def execute(self, plan):
        from repro.core.types import SearchResult
        fn = self._fns.get(plan.params)
        if fn is None:
            fn = self._fns[plan.params] = distributed_search_fn(
                self.sharded, plan.params, use_pallas=self.use_pallas,
                heap_layout=self.heap_layout, with_stats=self.with_stats)
        out = fn(plan.queries, plan.bitmaps)
        stats = None
        if self.with_stats:
            d, ids, st = out
            stats = SearchStats(*(st[:, i] for i in range(7)))
        else:
            d, ids = out
        return SearchResult(dists=d, ids=ids, stats=stats, strategy="scann",
                            plan=plan)

    def search(self, queries, bitmaps, params: SearchParams):
        return self.execute(self.plan(queries, bitmaps, params))

    def raw_search_fn(self, params: SearchParams, use_pallas=None,
                      heap_layout=None):
        """The shard_map'd explicit-args fn (lowerable against
        ShapeDtypeStructs) — what launch/fvs_dryrun.py compiles."""
        return distributed_search_raw(
            self.sharded, params,
            use_pallas=self.use_pallas if use_pallas is None else use_pallas,
            heap_layout=heap_layout or self.heap_layout)


# ===========================================================================
# Mesh-sharded graph + storage tiers (DESIGN.md §13)
# ===========================================================================
# The ScaNN tier above shards *leaves*; the tier below shards the graph's
# row-indexed state — base/upper adjacency, the f32 heap, the SQ8 shadow
# heap, and (host-side) one BufferPool per shard.  Traversal runs as
# per-shard frontier supersteps over the `ShardGraph`/`ShardStore` views
# (core/shardtypes.py), whose gather helpers inside core/graph_search.py
# resolve every global-row read by ownership + a pmin/pmax collective:
#
#   * beam_exchange_interval == 1 (lockstep): per-query lane state is
#     replicated on every shard and only the storage reads shard.  The
#     owner-masked reductions SELECT the owner's untouched payload, so the
#     final ids/dists/counters are bit-identical to the single-device
#     frontier engine for ANY shard count — by construction, not by luck.
#   * beam_exchange_interval == E > 1 (drift): each shard traverses its
#     own induced subgraph (remote adjacency masked -1, remote distances
#     +inf) for E supersteps, then an all-gather top-k beam exchange
#     re-seeds every shard's beam from the global top-ef.  Cheaper
#     collectives (ef ids+dists every E hops instead of every candidate
#     every hop), approximate results.
#
# The same shard body runs under jax.vmap(..., axis_name=...) — the
# single-device emulation this CPU container uses — and under shard_map on
# a real mesh (`sharded_graph_search_fn`); `ShardStore.offset` is derived
# from lax.axis_index at trace time so both bind identically.

from repro.core import costmodel
from repro.core import graph_search as gs
from repro.core.hnsw import HNSWGraph
from repro.core.shardtypes import SHARD_AXIS, ShardGraph, ShardStore
from repro.storage.bufferpool import BufferPoolState
from repro.storage.engine import StorageStats, merge_storage_stats


def shard_graph_tiers(graph: HNSWGraph, store: VectorStore,
                      num_shards: int, axis: str = SHARD_AXIS,
                      f32: bool = True):
    """Partition the adjacency + heap tiers by contiguous row range.

    Returns (ShardGraph, ShardStore) view pytrees whose data leaves carry
    a leading (num_shards,) stack axis — shard s owns global rows
    [s*rps, (s+1)*rps) with rps = ceil(n / num_shards), the last block
    zero/-1 padded.  Adjacency values stay GLOBAL row ids.  `f32=False`
    drops the full-precision tier from the views (SQ8-only giant-scale
    mode; the executor validates quant/rerank compatibility).
    """
    n = graph.n
    if store.n != n:
        raise ValueError(f"graph ({n} rows) and store ({store.n} rows) "
                         "disagree")
    S = int(num_shards)
    if S < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    rps = -(-n // S)

    def block(a, fill):
        a = np.asarray(a)
        pad = S * rps - a.shape[0]
        if pad:
            a = np.concatenate(
                [a, np.full((pad,) + a.shape[1:], fill, a.dtype)])
        return jnp.asarray(a.reshape(S, rps, *a.shape[1:]))

    nb = np.asarray(graph.neighbors)                    # (L, n, deg)
    L, _, deg = nb.shape
    pad = S * rps - n
    if pad:
        nb = np.concatenate([nb, np.full((L, pad, deg), -1, nb.dtype)],
                            axis=1)
    nb = np.ascontiguousarray(
        nb.reshape(L, S, rps, deg).transpose(1, 0, 2, 3))

    # Per-shard drift entry: the shard's own highest-level node (global
    # id), -1 on an all-padding shard.  For S=1 this IS the global entry.
    levels = np.asarray(graph.node_level)
    local_entry = np.full((S,), -1, np.int32)
    for s in range(S):
        lo, hi = s * rps, min((s + 1) * rps, n)
        if lo < hi:
            local_entry[s] = lo + int(np.argmax(levels[lo:hi]))

    gviews = ShardGraph(
        neighbors=jnp.asarray(nb),
        entry_point=jnp.full((S,), int(graph.entry_point), jnp.int32),
        local_entry=jnp.asarray(local_entry),
        m=graph.m, axis=axis, n_total=n, collective=True)

    has_f32 = f32 and store.vectors is not None
    has_q = store.q_vectors is not None
    sviews = ShardStore(
        vectors=block(store.vectors, 0) if has_f32 else None,
        norms_sq=block(store.norms_sq, 0) if has_f32 else None,
        metric=store.metric, axis=axis, n_total=n, collective=True,
        q_vectors=block(store.q_vectors, 0) if has_q else None,
        q_scale=(jnp.broadcast_to(jnp.asarray(store.q_scale),
                                  (S,) + np.shape(store.q_scale))
                 if has_q else None),
        q_mean=(jnp.broadcast_to(jnp.asarray(store.q_mean),
                                 (S,) + np.shape(store.q_mean))
                if has_q else None),
        q_norms_sq=block(store.q_norms_sq, 0) if has_q else None)
    return gviews, sviews


def _graph_shard_body(gv: ShardGraph, sv: ShardStore, queries, bitmaps,
                      params: SearchParams, use_pallas: bool,
                      collect_trace: bool):
    """One shard's program — bound under vmap-with-axis-name or shard_map."""
    E = params.beam_exchange_interval
    if E <= 1:
        # Lockstep: the full frontier engine over collective views.  Lane
        # state (beams, pools, visited bitsets, counters) is replicated,
        # so the carried stats are already the single-device counters —
        # no psum (it would multiply the replicated counts by S).
        return gs._frontier_search_batch(gv, sv, queries, bitmaps, params,
                                         use_pallas, collect_trace)
    # Drift: induced-subgraph traversal between beam exchanges.  Each
    # shard zooms in from its own local_entry, runs E supersteps on
    # masked (non-collective) views, then the all-gather top-ef exchange
    # re-seeds W.  The outer cond all-gathers `done` so every shard runs
    # the same trip count and the in-body collectives stay aligned.
    lg = dataclasses.replace(gv, collective=False,
                             entry_point=gv.local_entry)
    ls = dataclasses.replace(sv, collective=False)
    st = gs.frontier_init(lg, ls, queries, bitmaps, params)
    rounds = -(-params.max_hops // E)

    def cond(c):
        t, s = c
        return (t < rounds) & ~jnp.all(jax.lax.all_gather(s.done, gv.axis))

    def body(c):
        t, s = c
        s = gs.step_supersteps(lg, ls, s, params, E, use_pallas=use_pallas)
        s = gs.beam_exchange(ls, s, params, gv.axis)
        return t + 1, s

    _, st = jax.lax.while_loop(cond, body,
                               (jnp.asarray(0, jnp.int32), st))
    # Finalize on COLLECTIVE views: the beam now holds remote rows, and
    # the exact sq8 rerank / emit must read their true payloads.  The
    # finalize delta (rerank counters) is computed replicated, so add it
    # once to the psum'd (per-shard, genuinely different) traversal work.
    d, ids, fstats, _ = gs.frontier_finalize(gv, sv, st, params)
    delta = jax.tree.map(lambda a, b: a - b, fstats, st.stats)
    total = jax.tree.map(lambda a: jax.lax.psum(a, gv.axis), st.stats)
    stats = jax.tree.map(lambda a, b: a + b, total, delta)
    return d, ids, stats


def _squeeze0(tree):
    return jax.tree.map(lambda a: a[0], tree)


@partial(jax.jit, static_argnames=("params", "use_pallas", "collect_trace"))
def _sharded_search(gviews, sviews, queries, bitmaps, params, use_pallas,
                    collect_trace):
    out = jax.vmap(
        lambda gv, sv: _graph_shard_body(gv, sv, queries, bitmaps, params,
                                         use_pallas, collect_trace),
        in_axes=(0, 0), axis_name=gviews.axis)(gviews, sviews)
    # Every output leaf is replicated across the stack axis (lockstep) or
    # already reduced to identical values (drift: all-gather/psum), so
    # shard 0's copy IS the answer.
    return _squeeze0(out)


@partial(jax.jit, static_argnames=("params", "collect_trace"))
def _sharded_init(gviews, sviews, queries, bitmaps, deadlines, params,
                  collect_trace):
    out = jax.vmap(
        lambda gv, sv: gs.frontier_init(gv, sv, queries, bitmaps, params,
                                        collect_trace=collect_trace,
                                        deadlines=deadlines),
        in_axes=(0, 0), axis_name=gviews.axis)(gviews, sviews)
    return _squeeze0(out)


@partial(jax.jit, static_argnames=("params", "width", "collect_trace"))
def _sharded_idle(gviews, sviews, params, width, collect_trace):
    out = jax.vmap(
        lambda gv, sv: gs.frontier_idle(gv, sv, params, width,
                                        collect_trace=collect_trace),
        in_axes=(0, 0), axis_name=gviews.axis)(gviews, sviews)
    return _squeeze0(out)


@partial(jax.jit, static_argnames=("params", "n_hops", "use_pallas",
                                   "dynamic_deadline"))
def _sharded_step(gviews, sviews, state, params, n_hops, use_pallas,
                  dynamic_deadline):
    out = jax.vmap(
        lambda gv, sv: gs.step_supersteps(gv, sv, state, params, n_hops,
                                          use_pallas=use_pallas,
                                          dynamic_deadline=dynamic_deadline),
        in_axes=(0, 0), axis_name=gviews.axis)(gviews, sviews)
    return _squeeze0(out)


@partial(jax.jit, static_argnames=("params",))
def _sharded_finalize(gviews, sviews, state, params):
    out = jax.vmap(
        lambda gv, sv: gs.frontier_finalize(gv, sv, state, params),
        in_axes=(0, 0), axis_name=gviews.axis)(gviews, sviews)
    return _squeeze0(out)


class ShardedStorageAccountant:
    """Per-shard BufferPool replay facade (DESIGN.md §13).

    One `StorageEngine` (with its own pool) per shard, each over the
    GLOBAL page layout — shard s's pool only ever sees pages of rows
    [lo, hi), so per-shard capacity is naturally `capacity_frac /
    num_shards` of the global budget (the caller builds the engines that
    way).  `account_graph` slices the replicated lockstep trace by row
    ownership, replays each slice through its shard's pool, and merges
    the per-shard StorageStats into the aggregate the cost model and
    benchmarks consume; `last_per_shard` keeps the unmerged parts for
    per-shard hit-rate telemetry."""

    def __init__(self, engines, n: int):
        if not engines:
            raise ValueError("need at least one per-shard engine")
        self.engines = list(engines)
        self.num_shards = len(self.engines)
        self.n = int(n)
        self.rows_per_shard = -(-self.n // self.num_shards)
        self.last_per_shard: list[StorageStats] | None = None

    # GraphExecutor-compatible layout probes (constructor validation).
    @property
    def graph(self):
        return self.engines[0].graph

    @property
    def qheap(self):
        return self.engines[0].qheap

    def reset_cold(self) -> None:
        for e in self.engines:
            e.reset_cold()

    def state(self) -> BufferPoolState:
        """Aggregate residency snapshot: capacities/used/dirty sum;
        per-segment residency averages across shards (every engine lays
        out the same global segments, and a row's pages live in exactly
        one shard's pool — the mean is the global resident fraction up to
        the per-shard page rounding)."""
        states = [e.state() for e in self.engines]
        residency = {seg: float(np.mean([s.residency.get(seg, 0.0)
                                         for s in states]))
                     for seg in states[0].residency}
        dirty_by: dict[str, int] = {}
        for s in states:
            for seg, v in s.dirty_by_segment.items():
                dirty_by[seg] = dirty_by.get(seg, 0) + v
        return BufferPoolState(
            capacity=sum(s.capacity for s in states),
            used=sum(s.used for s in states),
            residency=residency,
            dirty=sum(s.dirty for s in states),
            dirty_by_segment=dirty_by)

    def account_graph(self, heap_steps, index_steps, rerank_rows=None,
                      quant: bool = False) -> StorageStats:
        hsteps = np.asarray(heap_steps)
        isteps = np.asarray(index_steps)
        parts = []
        for s, eng in enumerate(self.engines):
            lo = s * self.rows_per_shard
            hi = min(lo + self.rows_per_shard, self.n)
            hs = np.array(hsteps, copy=True)
            is_ = np.array(isteps, copy=True)
            hs[:, :lo] = gs.TRACE_UNTOUCHED
            hs[:, hi:] = gs.TRACE_UNTOUCHED
            is_[:, :lo] = gs.TRACE_UNTOUCHED
            is_[:, hi:] = gs.TRACE_UNTOUCHED
            rr = None
            if rerank_rows is not None:
                rr = np.array(rerank_rows, copy=True)
                rr[(rr < lo) | (rr >= hi)] = -1
            parts.append(eng.account_graph(hs, is_, rerank_rows=rr,
                                           quant=quant))
        self.last_per_shard = parts
        return merge_storage_stats(parts)


def make_sharded_storage(engines, n: int) -> ShardedStorageAccountant:
    """Wrap per-shard engines (one BufferPool each, typically built with
    capacity_frac / num_shards) into the accounting facade."""
    return ShardedStorageAccountant(engines, n)


class ShardedGraphExecutor:
    """The five graph strategies over mesh-sharded tiers (DESIGN.md §13).

    Mirrors `GraphExecutor`'s full surface — plan/execute/search plus the
    stepped-frontier delegates — so benchmarks and the continuous-batching
    server consume it unchanged.  In lockstep mode the per-lane
    FrontierState is replicated on every shard, so the executor keeps ONE
    single-device-shaped state and binds it to all shards per step; ids,
    dists, and all seven counters are bit-identical to `GraphExecutor`
    for any shard count."""

    def __init__(self, graph: HNSWGraph, store: VectorStore,
                 num_shards: int, strategy: str = "sweeping",
                 use_pallas: bool = False,
                 storage: ShardedStorageAccountant | None = None,
                 graph_quant: str = "none", axis: str = SHARD_AXIS,
                 f32: bool = True):
        if strategy not in costmodel.GRAPH_STRATEGIES:
            raise ValueError(f"unknown graph strategy {strategy!r}")
        if graph_quant not in ("none", "sq8"):
            raise ValueError(f"unknown graph_quant {graph_quant!r}")
        if graph_quant == "sq8" and store.q_vectors is None:
            raise ValueError("graph_quant='sq8' needs a quantize_store'd "
                             "VectorStore (SQ8 shadow missing)")
        if not f32:
            if graph_quant != "sq8":
                raise ValueError("f32=False (no full-precision tier) "
                                 "requires graph_quant='sq8'")
        if storage is not None:
            if storage.num_shards != num_shards:
                raise ValueError(
                    f"storage facade has {storage.num_shards} shards, "
                    f"executor has {num_shards}")
            if storage.graph is None:
                raise ValueError("storage engines lack a graph adjacency "
                                 "layout; build them with graph=")
            if graph_quant == "sq8" and storage.qheap is None:
                raise ValueError("storage engines lack the qheap (SQ8 "
                                 "shadow) segment")
        self.graph = graph
        self.store = store
        self.num_shards = int(num_shards)
        self.strategy = strategy
        self.use_pallas = use_pallas
        self.storage = storage
        self.graph_quant = graph_quant
        self.axis = axis
        self._f32 = f32
        self._gv, self._sv = shard_graph_tiers(graph, store, num_shards,
                                               axis=axis, f32=f32)
        base = strategy if graph_quant == "none" \
            else f"{strategy}_{graph_quant}"
        self.name = f"sharded{self.num_shards}_{base}"

    def resolve_params(self, params: SearchParams) -> SearchParams:
        """Plan-time coercion — same contract as GraphExecutor (the
        resolved object is the jit cache key), plus the sharded-mode
        validations."""
        if params.strategy != self.strategy or \
                params.graph_quant != self.graph_quant:
            params = dataclasses.replace(params, strategy=self.strategy,
                                         graph_quant=self.graph_quant)
        if params.graph_exec_mode != "frontier":
            raise ValueError("the sharded executor runs the frontier "
                             "engine only (graph_exec_mode='frontier')")
        E = params.beam_exchange_interval
        if E < 1:
            raise ValueError(f"beam_exchange_interval must be >= 1, "
                             f"got {E}")
        if E > 1:
            if self.strategy == "iterative_scan":
                raise ValueError(
                    "drift mode (beam_exchange_interval > 1) drives the "
                    "base beam engine; iterative_scan's W is an emission "
                    "buffer, not a beam — run it lockstep "
                    "(beam_exchange_interval=1)")
            if self.storage is not None:
                raise ValueError(
                    "storage accounting needs the lockstep replicated "
                    "trace; set beam_exchange_interval=1")
        if not self._f32 and params.sq8_rerank:
            raise ValueError("no f32 tier to rerank from (f32=False); "
                             "set sq8_rerank=False")
        return params

    def _lockstep(self, params: SearchParams) -> SearchParams:
        params = self.resolve_params(params)
        if params.beam_exchange_interval > 1:
            raise ValueError("stepped serving runs lockstep only; drift "
                             "mode (beam_exchange_interval > 1) is "
                             "batch-path only")
        return params

    def plan(self, queries, bitmaps, params: SearchParams):
        from repro.core.executor import SearchPlan
        return SearchPlan(self.strategy, self.resolve_params(params),
                          queries, bitmaps)

    def execute(self, plan):
        from repro.core.types import SearchResult
        p = plan.params
        if self.storage is None:
            d, ids, stats = _sharded_search(self._gv, self._sv,
                                            plan.queries, plan.bitmaps, p,
                                            self.use_pallas, False)
            return SearchResult(dists=d, ids=ids, stats=stats,
                                strategy=self.strategy, plan=plan,
                                anytime=costmodel.evaluate_anytime(
                                    stats, p, self.store.dim, ids,
                                    hop_cap=p.max_hops))
        d, ids, stats, trace = _sharded_search(self._gv, self._sv,
                                               plan.queries, plan.bitmaps,
                                               p, self.use_pallas, True)
        rr = trace.get("rerank_rows")
        sstats = self.storage.account_graph(
            np.asarray(trace["heap_steps"]),
            np.asarray(trace["index_steps"]),
            rerank_rows=None if rr is None else np.asarray(rr),
            quant=self.graph_quant == "sq8")
        return SearchResult(dists=d, ids=ids, stats=stats,
                            strategy=self.strategy, plan=plan,
                            storage=sstats,
                            anytime=costmodel.evaluate_anytime(
                                stats, p, self.store.dim, ids,
                                hop_cap=p.max_hops))

    def search(self, queries, bitmaps, params: SearchParams):
        return self.execute(self.plan(queries, bitmaps, params))

    # ---- stepped frontier delegates (serving/continuous.py) ----------

    def idle_frontier(self, params: SearchParams, width: int):
        return _sharded_idle(self._gv, self._sv, self._lockstep(params),
                             width, self.storage is not None)

    def init_frontier(self, queries, bitmaps, params: SearchParams,
                      deadlines=None):
        return _sharded_init(self._gv, self._sv, queries, bitmaps,
                             deadlines, self._lockstep(params),
                             self.storage is not None)

    def write_frontier_slot(self, state, lane, slot):
        return gs.frontier_write_slot(state, lane, slot)

    def step_frontier(self, state, params: SearchParams, n_hops: int,
                      dynamic_deadline: bool = False):
        return _sharded_step(self._gv, self._sv, state,
                             self._lockstep(params), n_hops,
                             self.use_pallas, dynamic_deadline)

    def finalize_frontier(self, state, params: SearchParams):
        return _sharded_finalize(self._gv, self._sv, state,
                                 self._lockstep(params))


def sharded_graph_search_fn(graph: HNSWGraph, store: VectorStore,
                            num_shards: int, params: SearchParams,
                            mesh: Mesh | None = None,
                            axis: str = SHARD_AXIS,
                            use_pallas: bool = False):
    """The real-mesh path: the same shard body under `shard_map`.

    Builds (or takes) a 1-D mesh over the first `num_shards` devices,
    places shard s of every tier on device s, and returns
    `partial(step, gviews, sviews)`: a (queries, bitmaps) -> (dists, ids,
    SearchStats) fn whose placed tiers ride as jit arguments (`.args`).
    Validation twin of the vmap emulation — in lockstep mode both produce
    bit-identical results (tests/test_sharding.py runs this under
    --xla_force_host_platform_device_count)."""
    if mesh is None:
        devs = jax.devices()
        if len(devs) < num_shards:
            raise ValueError(f"need {num_shards} devices for "
                             f"{num_shards} shards, have {len(devs)}")
        mesh = Mesh(np.asarray(devs[:num_shards]), (axis,))
    tiers = place_shards(shard_graph_tiers(graph, store, num_shards,
                                           axis=axis), mesh, axis)

    def local(gstack, sstack, queries, bitmaps):
        g = _squeeze0(gstack)
        s = _squeeze0(sstack)
        return _graph_shard_body(g, s, queries, bitmaps, params,
                                 use_pallas, False)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(), P()),
                       out_specs=(P(), P(), P()), check_vma=False)
    return partial(jax.jit(fn), *tiers)
