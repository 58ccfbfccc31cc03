"""Filtered ScaNN: clustering-based index (paper §2.3.7, §3.3).

Tree: optional branch level over leaves (the paper's `max_num_levels`), built
with k-means.  Leaves are dense, MXU-aligned int8 (SQ8) tiles — the TPU
analogue of the paper's "leaf packs as many vectors as fit in a page, linked
list of pages" layout.  Optional PCA rotation precedes quantization (paper
Table 5: PCA 1536→193 for OpenAI-5M).

Search (paper Fig. 5/7): ① score branch centroids → top branches,
② score their leaf centroids → top `num_leaves_to_search` leaves,
③ fused filtered leaf scan (Pallas kernel): bitmap probe → dequantized
scoring of passing rows only, ④ reordering: fetch full-precision vectors of
the top k×reorder_factor candidates from the heap, rescore exactly, top-k.

Counters follow Table 6's ScaNN columns: filter checks = every valid row in
every opened leaf; distance comps = rows passing filters; hops = leaves
scanned; reorder_rows = reordering candidates; page accesses = quantized
leaf pages + heap pages for reordering.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import (SearchParams, SearchStats, VectorStore,
                              distance, heap_pages_per_vector,
                              probe_bitmap, sq8_quantize, topk_smallest)
from repro.kernels import ops as kops
from repro.storage.pages import PAGE_BYTES, scann_pages_per_leaf


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ScannIndex:
    # quantized leaf storage (possibly PCA-projected space)
    leaf_tiles: jax.Array      # (L, C, dp) int8
    leaf_rowids: jax.Array     # (L, C) int32, -1 padded
    leaf_centroids: jax.Array  # (L, dp) f32
    scale: jax.Array           # (dp,) f32   dequant: x = tile*scale + mean
    mean: jax.Array            # (dp,) f32
    # optional branch level (ids -1-padded); single-level if B == 0 rows
    branch_centroids: jax.Array  # (B, dp) f32
    branch_leaves: jax.Array     # (B, Lb) int32
    # optional PCA projection from original d to dp
    pca: jax.Array               # (d, dp) f32 (identity-like if disabled)
    # build-time ||x||² of the dequantized rows (L2 fast path; None on
    # indexes built before this field existed — recomputed lazily)
    row_norms_sq: jax.Array | None = None   # (L, C) f32
    metric: str = dataclasses.field(metadata=dict(static=True), default="l2")
    levels: int = dataclasses.field(metadata=dict(static=True), default=2)

    def __getattr__(self, name):
        # indexes pickled before row_norms_sq existed unpickle without the
        # attribute; treat them as "not precomputed"
        if name == "row_norms_sq":
            return None
        raise AttributeError(name)

    @property
    def num_leaves(self) -> int:
        return self.leaf_tiles.shape[0]


@partial(jax.jit, static_argnames=("n",))
def _nearest_centroid(xb: jax.Array, cent: jax.Array, n: int) -> jax.Array:
    """Index of the L2-nearest centroid of every row: xb (B, block, d)
    row blocks (zero-padded past row n), cent (k, d) -> (n,) int32."""
    cn = jnp.sum(cent * cent, axis=1)

    def block(x):
        ip = jnp.matmul(x, cent.T, precision=jax.lax.Precision.HIGHEST)
        d = jnp.sum(x * x, axis=1, keepdims=True) + cn[None, :] - 2.0 * ip
        return jnp.argmin(d, axis=1).astype(jnp.int32)

    return jax.lax.map(block, xb).reshape(-1)[:n]


def _kmeans(x: np.ndarray, k: int, iters: int = 12, seed: int = 0,
            block: int = 8192) -> tuple[np.ndarray, np.ndarray]:
    """Plain Lloyd's. Returns (centroids (k, d), assignment (n,)).

    The assignment step, n·k distances per iteration, runs on the default
    device in f32; the centroid update stays on the host."""
    n, d = x.shape
    rng = np.random.RandomState(seed)
    cent = x[rng.choice(n, size=k, replace=False)].copy()
    block = min(block, n)
    xb = jnp.asarray(np.pad(x, ((0, (-n) % block), (0, 0)))
                     .reshape(-1, block, d), jnp.float32)
    for _ in range(iters):
        assign = np.asarray(_nearest_centroid(
            xb, jnp.asarray(cent, jnp.float32), n), np.int64)
        # per-cluster row sums, accumulated row by row in row order (the
        # sums np.add.at gives, without its per-element dispatch)
        cnt = np.bincount(assign, minlength=k)
        order = np.argsort(assign, kind="stable")
        starts = np.concatenate([[0], np.cumsum(cnt)[:-1]])
        nz = np.flatnonzero(cnt)
        sums = np.zeros_like(cent)
        sums[nz] = np.add.reduceat(x[order], starts[nz], axis=0,
                                   dtype=sums.dtype)
        cnt = cnt.astype(np.float64)
        empty = cnt == 0
        cent = np.where(empty[:, None], cent,
                        sums / np.maximum(cnt, 1)[:, None])
        if empty.any():  # reseed empty clusters on far points
            far = rng.choice(n, size=int(empty.sum()), replace=False)
            cent[empty] = x[far]
    return cent.astype(np.float32), assign


def build_scann(store: VectorStore, num_leaves: int, levels: int = 2,
                pca_dims: int | None = None, seed: int = 0,
                kmeans_iters: int = 12) -> ScannIndex:
    x = np.asarray(store.vectors, np.float32)
    n, d = x.shape

    if pca_dims is not None and pca_dims < d:
        mu = x.mean(0)
        xc = x - mu
        cov = (xc.T @ xc) / max(n - 1, 1)
        w, v = np.linalg.eigh(cov)
        proj = v[:, ::-1][:, :pca_dims].astype(np.float32)
        # fold the centering into the projection space: xp = (x - mu) @ proj
        xp = xc @ proj
        pca = proj
        pca_mu = mu
    else:
        xp = x
        pca = np.eye(d, dtype=np.float32)
        pca_mu = np.zeros(d, np.float32)
    dp = xp.shape[1]

    cent, assign = _kmeans(xp, num_leaves, iters=kmeans_iters, seed=seed)
    counts = np.bincount(assign, minlength=num_leaves)
    cap = int(counts.max())
    cap += (-cap) % 8  # sublane alignment
    rowids = np.full((num_leaves, cap), -1, np.int64)
    # rows of each leaf in ascending row order
    order = np.argsort(assign, kind="stable")
    leaf = assign[order]
    starts = np.cumsum(counts) - counts
    rowids[leaf, np.arange(n) - starts[leaf]] = order

    # SQ8: per-dimension affine quantization over the dataset (the shared
    # quantizer — the graph engine's shadow store uses the same one)
    q, scale, mean = sq8_quantize(xp)
    tiles = np.zeros((num_leaves, cap, dp), np.int8)
    valid = rowids >= 0
    tiles[valid] = q[rowids[valid]]

    if levels >= 2 and num_leaves >= 16:
        nb = max(4, int(np.sqrt(num_leaves)))
        bcent, bassign = _kmeans(cent, nb, iters=kmeans_iters, seed=seed + 1)
        lb = int(np.bincount(bassign, minlength=nb).max())
        bleaves = np.full((nb, lb), -1, np.int64)
        boffs = np.zeros(nb, np.int64)
        for leaf in np.argsort(bassign, kind="stable"):
            b = bassign[leaf]
            bleaves[b, boffs[b]] = leaf
            boffs[b] += 1
    else:
        levels = 1
        bcent = np.zeros((1, dp), np.float32)
        bleaves = np.arange(num_leaves, dtype=np.int64)[None, :]

    # store the PCA mean by folding it into `mean` of the quantizer space:
    # query projection must also subtract pca_mu — stash it in pca row space
    # by augmenting: qp = (q - pca_mu) @ pca. We keep pca_mu separately:
    tiles_j = jnp.asarray(tiles)
    scale_j, mean_j = jnp.asarray(scale), jnp.asarray(mean)
    idx = ScannIndex(
        leaf_tiles=tiles_j,
        leaf_rowids=jnp.asarray(rowids, jnp.int32),
        leaf_centroids=jnp.asarray(cent),
        scale=scale_j, mean=mean_j,
        branch_centroids=jnp.asarray(bcent),
        branch_leaves=jnp.asarray(bleaves, jnp.int32),
        pca=jnp.asarray(np.concatenate([pca, pca_mu[None, :] @ pca], 0)),
        row_norms_sq=_row_norms_sq(tiles_j, scale_j, mean_j),
        metric=store.metric, levels=levels)
    return idx


@jax.jit
def _row_norms_sq(tiles: jax.Array, scale: jax.Array,
                  mean: jax.Array) -> jax.Array:
    """||x||² of every dequantized leaf row, (L, C) f32 — same dequant +
    reduction the kernels apply, so precomputed and inline norms agree."""
    x = tiles.astype(jnp.float32) * scale + mean
    return jnp.sum(x * x, axis=-1)


def project_query(index: ScannIndex, q: jax.Array) -> jax.Array:
    """Apply the (folded-centering) PCA projection to a query."""
    proj, mu_p = index.pca[:-1], index.pca[-1]
    return jnp.matmul(q, proj,
                      precision=jax.lax.Precision.HIGHEST) - mu_p


def _quant_pages_per_leaf(index: ScannIndex) -> int:
    # geometry owned by the storage layer (storage/pages.py, DESIGN.md §8)
    return scann_pages_per_leaf(index.leaf_tiles.shape[1],
                                index.leaf_tiles.shape[2])


_heap_pages_per_vector = heap_pages_per_vector  # shared formula (types.py)


def leaves_within_budget(index: ScannIndex, store: VectorStore,
                         params: SearchParams) -> tuple[int, bool]:
    """Plan-time anytime clamp (DESIGN.md §10): the largest
    `num_leaves_to_search` whose worst-case per-query cost fits the
    budgets in `params` — ScaNN's leaf count is a static shape, so its
    budget enforcement happens at planning, not inside the kernels.

    Returns (nl, clamped).  Never returns less than one leaf: the last
    leaf always scans and the caller flags the query budget_exhausted
    instead (ScannExecutor threads `clamped` into AnytimeInfo).
    """
    from repro.core.costmodel import budget_cycle_weights
    L, C, _ = index.leaf_tiles.shape
    nl0 = min(params.num_leaves_to_search, L)
    if params.page_budget <= 0 and params.hop_budget <= 0 \
            and params.deadline_cycles <= 0:
        return nl0, False
    qppl = _quant_pages_per_leaf(index)
    ppv = _heap_pages_per_vector(store.dim)
    cent = L + (index.branch_centroids.shape[0] if index.levels >= 2 else 0)
    w = budget_cycle_weights(store.dim)
    for nl in range(nl0, 0, -1):
        r = min(params.k * params.reorder_factor, nl * C)
        ok = True
        if params.hop_budget > 0:
            ok = nl <= params.hop_budget
        if ok and params.page_budget > 0:
            ok = nl * qppl + r * ppv <= params.page_budget
        if ok and params.deadline_cycles > 0:
            rows = nl * C
            cyc = (rows + cent + r) * w["distance_comps"] \
                + rows * w["filter_checks"] \
                + nl * qppl * w["page_accesses_index"] \
                + r * ppv * w["page_accesses_heap"] \
                + r * w["reorder_rows"]
            ok = cyc <= params.deadline_cycles
        if ok:
            return nl, nl < nl0
    return 1, nl0 > 1


def _search_single(index: ScannIndex, store: VectorStore, q, bitmap,
                   params: SearchParams, use_pallas: bool):
    qp = project_query(index, q)
    L, C, dp = index.leaf_tiles.shape
    nl = min(params.num_leaves_to_search, L)
    stats = SearchStats.zeros()

    if index.levels >= 2:
        B, Lb = index.branch_leaves.shape
        bd = distance(index.metric, qp[None], index.branch_centroids,
                      jnp.sum(index.branch_centroids ** 2, -1))
        # open enough branches to cover nl leaves (paper Fig. 5-①)
        nb = min(B, max(1, -(-nl * 2 * B // L)))
        _, bsel = topk_smallest(bd, nb)
        cand_leaves = index.branch_leaves[bsel].reshape(-1)      # (nb*Lb,)
        cl = jnp.maximum(cand_leaves, 0)
        ld = distance(index.metric, qp[None], index.leaf_centroids[cl],
                      jnp.sum(index.leaf_centroids[cl] ** 2, -1))
        ld = jnp.where(cand_leaves >= 0, ld, jnp.inf)
        _, pos = topk_smallest(ld, nl)
        leaves = cl[pos]                                          # (nl,)
        cent_scored = index.branch_centroids.shape[0] + cand_leaves.shape[0]
    else:
        ld = distance(index.metric, qp[None], index.leaf_centroids,
                      jnp.sum(index.leaf_centroids ** 2, -1))
        _, leaves = topk_smallest(ld, nl)
        cent_scored = L

    tiles = index.leaf_tiles[leaves]          # (nl, C, dp)
    rowids = index.leaf_rowids[leaves]        # (nl, C)
    scores = kops.leaf_scan(qp, tiles, rowids, index.scale, index.mean,
                            bitmap, metric=index.metric,
                            use_pallas=use_pallas)                # (nl, C)

    valid = rowids >= 0
    n_valid = valid.sum()
    passing = jnp.isfinite(scores)
    n_pass = passing.sum()

    # candidate selection + full-precision reordering (paper §6.2.2)
    r = min(params.k * params.reorder_factor, nl * C)
    flat_s, flat_pos = topk_smallest(scores.reshape(-1), r)
    cand_rows = rowids.reshape(-1)[flat_pos]
    cand_ok = jnp.isfinite(flat_s) & (cand_rows >= 0)
    exact = distance(store.metric, q[None], store.vectors[
        jnp.maximum(cand_rows, 0)], store.norms_sq[jnp.maximum(cand_rows, 0)])
    exact = jnp.where(cand_ok, exact, jnp.inf)
    dk, pos = topk_smallest(exact, params.k)
    ids = jnp.where(jnp.isinf(dk), -1, cand_rows[pos])

    n_reorder = cand_ok.sum()
    stats = SearchStats(
        distance_comps=stats.distance_comps + n_pass + cent_scored + n_reorder,
        filter_checks=stats.filter_checks + n_valid,
        hops=stats.hops + nl,
        page_accesses_index=stats.page_accesses_index
        + nl * _quant_pages_per_leaf(index),
        page_accesses_heap=stats.page_accesses_heap
        + n_reorder * _heap_pages_per_vector(store.dim),
        tmap_lookups=stats.tmap_lookups,
        reorder_rows=stats.reorder_rows + n_reorder)
    return dk, ids, stats


@partial(jax.jit, static_argnames=("params", "use_pallas"))
def scann_search_batch_vmapped(index: ScannIndex, store: VectorStore,
                               queries, bitmaps, params: SearchParams,
                               use_pallas: bool = False):
    """Legacy per-query path: vmap of the single-query search.  Every leaf
    tile is re-fetched and re-scored once per query — kept as the
    equivalence oracle and microbenchmark baseline for the batched
    pipeline below."""
    return jax.vmap(lambda q, b: _search_single(
        index, store, q, b, params, use_pallas))(queries, bitmaps)


def _unique_pad(ids: jax.Array, domain: int, cap: int):
    """Static-shape set union: distinct values of `ids` (all in
    [0, domain)), padded to `cap` entries.  Returns (members (cap,) int32,
    valid (cap,) bool, inv (domain,) int32) with inv[members[i]] == i for
    valid slots.  Order: ascending id, members first (lax.top_k tie-break
    is lowest-index-first)."""
    present = jnp.zeros((domain,), jnp.int32).at[ids].set(1)
    pv, members = jax.lax.top_k(present, cap)
    valid = pv > 0
    inv = jnp.zeros((domain,), jnp.int32).at[members].set(
        jnp.arange(cap, dtype=jnp.int32))
    return members.astype(jnp.int32), valid, inv


def _select_leaves(index: ScannIndex, qp: jax.Array, nl: int,
                   use_pallas: bool):
    """Stage ①/② of Fig. 5, batched: one distance_matrix call per centroid
    level instead of per-query loops.  Returns (leaves (Q, nl), cent_scored
    per query)."""
    L = index.leaf_tiles.shape[0]
    if index.levels >= 2:
        B, Lb = index.branch_leaves.shape
        bd = kops.distance_matrix(qp, index.branch_centroids,
                                  metric=index.metric,
                                  use_pallas=use_pallas)          # (Q, B)
        nb = min(B, max(1, -(-nl * 2 * B // L)))
        _, bsel = topk_smallest(bd, nb)                           # (Q, nb)
        cand = index.branch_leaves[bsel].reshape(qp.shape[0], -1)  # (Q, nb*Lb)
        cl = jnp.maximum(cand, 0)
        ldf = kops.distance_matrix(qp, index.leaf_centroids,
                                   metric=index.metric,
                                   use_pallas=use_pallas)         # (Q, L)
        ld = jnp.where(cand >= 0, jnp.take_along_axis(ldf, cl, 1), jnp.inf)
        _, pos = topk_smallest(ld, nl)
        leaves = jnp.take_along_axis(cl, pos, 1)                  # (Q, nl)
        return leaves, B + cand.shape[1]
    ld = kops.distance_matrix(qp, index.leaf_centroids,
                              metric=index.metric, use_pallas=use_pallas)
    _, leaves = topk_smallest(ld, nl)
    return leaves, L


@partial(jax.jit, static_argnames=("params", "use_pallas", "collect_trace"))
def scann_search_batch(index: ScannIndex, store: VectorStore, queries,
                       bitmaps, params: SearchParams,
                       use_pallas: bool = False,
                       collect_trace: bool = False):
    """Filtered ScaNN search, query-batched (DESIGN.md §4).

    The whole batch moves through each stage together: ① one
    distance_matrix call per centroid level, ② the union of opened leaves
    is scanned ONCE by the batched fused kernel (MXU (Q, d) × (d, C)
    contraction per tile, per-query bitmap probes), ③ per-query candidate
    selection over the gathered scores, ④ the union of reordering
    candidates is gathered full-precision once and each query rescores its
    own r candidates in one batched contraction.  Counters
    keep Table 6 semantics; index-page accounting follows
    params.scann_page_accounting (DESIGN.md §5).

    `params.scann_query_block` > 0 tiles the query batch: each tile of B
    queries runs the full pipeline over its own leaf union, so the
    (Q, U, C) union-scan block — which grows ~quadratically with batch
    size when query leaf sets are disjoint — stays VMEM/HBM-bounded
    (DESIGN.md §4 "Scaling envelope").  ids/dists are tile-size-invariant
    (each query only ever reads its own leaves' scores); "batch"
    index-page accounting amortizes per tile instead of per batch.

    `collect_trace=True` additionally returns the storage-access trace
    (DESIGN.md §8) as a 4th element: `{"leaves": (Q, nl) leaves opened in
    rank order, "cand_rows": (Q, r) reorder heap rows in candidate order,
    "cand_ok": (Q, r) validity}` — exactly the object touches the page
    counters charge, for the buffer pool to replay.  ids/dists/stats are
    identical with the flag on or off."""
    if index.metric not in ("l2", "ip") or store.metric not in ("l2", "ip"):
        # distance_matrix (and the leaf-scan kernels) only implement L2/IP;
        # fail loudly instead of silently ranking cos stores by L2
        raise NotImplementedError(
            f"batched ScaNN pipeline supports 'l2'/'ip' metrics, got "
            f"index={index.metric!r} store={store.metric!r}; use "
            f"scann_search_batch_vmapped for other metrics")
    Q = queries.shape[0]
    B = params.scann_query_block
    if B < 0:
        raise ValueError(f"scann_query_block must be >= 0, got {B}")
    if 0 < B < Q:
        outs = [_scann_search_block(index, store, queries[s:s + B],
                                    bitmaps[s:s + B], params, use_pallas,
                                    collect_trace)
                for s in range(0, Q, B)]
        dk = jnp.concatenate([o[0] for o in outs])
        ids = jnp.concatenate([o[1] for o in outs])
        stats = jax.tree.map(lambda *xs: jnp.concatenate(xs),
                             *[o[2] for o in outs])
        if collect_trace:
            trace = {k: jnp.concatenate([o[3][k] for o in outs])
                     for k in outs[0][3]}
            return dk, ids, stats, trace
        return dk, ids, stats
    return _scann_search_block(index, store, queries, bitmaps, params,
                               use_pallas, collect_trace)


def _scann_search_block(index: ScannIndex, store: VectorStore, queries,
                        bitmaps, params: SearchParams, use_pallas: bool,
                        collect_trace: bool = False):
    """One query tile through the batched pipeline (stages ①–④ above)."""
    Q = queries.shape[0]
    L, C, dp = index.leaf_tiles.shape
    nl = min(params.num_leaves_to_search, L)
    qp = project_query(index, queries)                            # (Q, dp)

    leaves, cent_scored = _select_leaves(index, qp, nl, use_pallas)

    # ② union of opened leaves — each tile fetched/scored once per batch
    cap = min(L, Q * nl)
    uleaves, uvalid, inv = _unique_pad(leaves.reshape(-1), L, cap)
    tiles = index.leaf_tiles[uleaves]                             # (U, C, dp)
    rowids_u = jnp.where(uvalid[:, None], index.leaf_rowids[uleaves], -1)
    if index.metric == "ip":
        norms_u = jnp.zeros((cap, C), jnp.float32)                # unused
    elif index.row_norms_sq is not None:
        norms_u = index.row_norms_sq[uleaves]
    else:
        norms_u = _row_norms_sq(tiles, index.scale, index.mean)
    scores_u = kops.leaf_scan_batched(qp, tiles, rowids_u, index.scale,
                                      index.mean, bitmaps, norms_u,
                                      metric=index.metric,
                                      use_pallas=use_pallas)      # (Q, U, C)

    # gather each query's opened leaves back out of the union scan
    pos_in_u = inv[leaves]                                        # (Q, nl)
    scores = jnp.take_along_axis(scores_u, pos_in_u[:, :, None], 1)
    rowids = rowids_u[pos_in_u]                                   # (Q, nl, C)

    valid = rowids >= 0
    n_valid = valid.sum(axis=(1, 2))                              # (Q,)
    n_pass = jnp.isfinite(scores).sum(axis=(1, 2))

    # ③ per-query candidate selection (paper §6.2.2)
    r = min(params.k * params.reorder_factor, nl * C)
    flat_s, flat_pos = topk_smallest(scores.reshape(Q, -1), r)
    cand_rows = jnp.take_along_axis(rowids.reshape(Q, -1), flat_pos, 1)
    cand_ok = jnp.isfinite(flat_s) & (cand_rows >= 0)

    # ④ full-precision reordering: the union of candidate heap rows is
    # gathered from the store ONCE (the shared-fetch amortization), then
    # each query rescores only its own r candidates out of the fetched
    # block — one batched (Q, r, d) contraction at the legacy FLOP count,
    # not Q × |union| distances.  Dedup via sort + searchsorted —
    # O(Q·r log Q·r), independent of store.n.
    safe_rows = jnp.maximum(cand_rows, 0)
    rcap = min(store.n, Q * r)
    flat = safe_rows.reshape(-1)
    srt = jnp.sort(flat)
    is_new = jnp.concatenate([jnp.ones((1,), bool), srt[1:] != srt[:-1]])
    uslot = jnp.cumsum(is_new) - 1              # unique slot of each sorted id
    urows = jnp.zeros((rcap,), jnp.int32).at[uslot].set(srt)
    rows_u = store.vectors[urows]                                 # (rcap, d)
    norms_u2 = store.norms_sq[urows]
    pos = uslot[jnp.searchsorted(srt, flat)].reshape(Q, r)
    exact = distance(store.metric, queries[:, None, :],
                     rows_u[pos], norms_u2[pos])                  # (Q, r)
    exact = jnp.where(cand_ok, exact, jnp.inf)
    dk, pos = topk_smallest(exact, params.k)
    ids = jnp.where(jnp.isinf(dk),
                    -1, jnp.take_along_axis(cand_rows, pos, 1))
    n_reorder = cand_ok.sum(axis=1)

    # counters (Table 6 semantics, per query)
    qppl = _quant_pages_per_leaf(index)
    if params.scann_page_accounting not in ("batch", "per_query"):
        raise ValueError(
            f"scann_page_accounting must be 'batch' or 'per_query', got "
            f"{params.scann_page_accounting!r}")
    if params.scann_page_accounting == "per_query":
        idx_pages = jnp.full((Q,), nl * qppl, jnp.int32)
    else:
        # batch accounting: each opened leaf page is charged once per
        # batch, to the first query that opened it (DESIGN.md §5)
        opened = jnp.zeros((Q, cap), bool).at[
            jnp.arange(Q)[:, None], pos_in_u].set(True)
        first = jnp.argmax(opened, axis=0)                        # (cap,)
        idx_pages = jnp.sum(
            uvalid[None, :] & (first[None, :] == jnp.arange(Q)[:, None]),
            axis=1).astype(jnp.int32) * qppl
    z = jnp.zeros((Q,), jnp.int32)
    stats = SearchStats(
        distance_comps=(n_pass + cent_scored + n_reorder).astype(jnp.int32),
        filter_checks=n_valid.astype(jnp.int32),
        hops=z + nl,
        page_accesses_index=idx_pages,
        page_accesses_heap=(n_reorder
                            * _heap_pages_per_vector(store.dim)).astype(
                                jnp.int32),
        tmap_lookups=z,
        reorder_rows=n_reorder.astype(jnp.int32))
    if collect_trace:
        trace = {"leaves": leaves.astype(jnp.int32),
                 "cand_rows": cand_rows.astype(jnp.int32),
                 "cand_ok": cand_ok}
        return dk, ids, stats, trace
    return dk, ids, stats
