"""Filtered ScaNN: clustering-based index (paper §2.3.7, §3.3).

Tree: optional branch level over leaves (the paper's `max_num_levels`), built
with k-means.  Leaves are dense, MXU-aligned int8 (SQ8) tiles — the TPU
analogue of the paper's "leaf packs as many vectors as fit in a page, linked
list of pages" layout.  Optional PCA rotation precedes quantization (paper
Table 5: PCA 1536→193 for OpenAI-5M).

Search (paper Fig. 5/7): ① score branch centroids → the nearest branches,
as many as hold `num_leaves_to_search` leaves, ② score their leaf
centroids → top `num_leaves_to_search` leaves,
③ fused filtered leaf scan (Pallas kernel): bitmap probe → dequantized
scoring of passing rows only, ④ reordering: fetch full-precision vectors of
the top k×reorder_factor candidates from the heap, rescore exactly, top-k.

Counters follow Table 6's ScaNN columns: filter checks = every valid row in
every opened leaf; distance comps = rows passing filters; hops = leaves
scanned; reorder_rows = reordering candidates; page accesses = quantized
leaf pages + heap pages for reordering.

The batched search names its stages for the profiler (`jax.named_scope`):
`scann.select` (①②), `scann.leaf_scan` (③) and `scann.reorder` (④).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.types import (SearchParams, SearchStats, VectorStore,
                              distance, heap_pages_per_vector,
                              sq8_codes, sq8_params, topk_smallest)
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


HIGHEST = jax.lax.Precision.HIGHEST
KMEANS_BLOCK = 8192   # rows per step of a Lloyd iteration
PACK_BLOCK = 16       # leaves per step of the SQ8 pack


@partial(jax.jit, static_argnames=("block",))
def _lloyd_step(x: jax.Array, cent: jax.Array, block: int):
    """One Lloyd step over the rows of x (n, d), `block` rows at a time:
    the L2-nearest centroid of every row (n,) int32, and per centroid the
    sum (k, d) f32 and the count (k,) int32 of its rows.  The last block
    ends at row n; the rows it shares with the block before it are
    assigned again (to the same centroid) and summed once."""
    n, d = x.shape
    k = cent.shape[0]
    cn = jnp.sum(cent * cent, axis=1)
    ids = jnp.arange(k, dtype=jnp.int32)

    def step(i, carry):
        assign, sums, counts = carry
        lo = i * block
        start = jnp.minimum(lo, n - block)
        xb = jax.lax.dynamic_slice_in_dim(x, start, block)
        ip = jnp.matmul(xb, cent.T, precision=HIGHEST)
        dist = jnp.sum(xb * xb, axis=1, keepdims=True) + cn[None, :] - 2.0 * ip
        a = jnp.argmin(dist, axis=1).astype(jnp.int32)
        new = start + jnp.arange(block) >= lo
        member = (a[:, None] == ids[None, :]) & new[:, None]      # (block, k)
        sums = sums + jnp.matmul(member.astype(jnp.float32).T, xb,
                                 precision=HIGHEST)
        counts = counts + jnp.sum(member, axis=0, dtype=jnp.int32)
        return (jax.lax.dynamic_update_slice_in_dim(assign, a, start, 0),
                sums, counts)

    return jax.lax.fori_loop(
        0, -(-n // block), step,
        (jnp.zeros((n,), jnp.int32), jnp.zeros((k, d), jnp.float32),
         jnp.zeros((k,), jnp.int32)))


def kmeans(x: jax.Array, k: int, iters: int = 12, seed: int = 0,
           block: int = KMEANS_BLOCK):
    """Plain Lloyd's over the rows of x (n, d) f32, on x's device: x is
    read in place, `block` rows per step, and never copied.

    Returns (centroids (k, d) f32 numpy, assignment (n,) int32 on the
    device, counts (k,) numpy).  As in the host recipe it replaces, the
    assignment is the last step's (made with the centroids before the
    last update), the seeds and the reseeds of empty clusters are host
    draws from `seed`, and a centroid is its rows' mean, divided in f64 on
    the host; the per-centroid sums are accumulated on the device in f32
    instead of on the host in row order."""
    n = x.shape[0]
    rng = np.random.RandomState(seed)

    def rows(ids):
        return np.asarray(x[jnp.asarray(ids)], np.float64)

    cent = rows(rng.choice(n, size=k, replace=False))
    block = min(block, n)
    for _ in range(iters):
        assign, sums, counts = _lloyd_step(x, jnp.asarray(cent, jnp.float32),
                                           block)
        counts = np.asarray(counts)
        empty = counts == 0
        cent = np.where(empty[:, None], cent, np.asarray(sums, np.float64)
                        / np.maximum(counts, 1)[:, None])
        if empty.any():  # reseed empty clusters on far points
            cent[empty] = rows(rng.choice(n, size=int(empty.sum()),
                                          replace=False))
    return cent.astype(np.float32), assign, counts


@jax.jit
def _by_cluster(assign: jax.Array) -> jax.Array:
    """Row ids sorted by cluster, ascending within each cluster."""
    return jnp.argsort(assign, stable=True).astype(jnp.int32)


def _members(order: np.ndarray, counts: np.ndarray,
             width: int) -> np.ndarray:
    """(k, width) int32 on the host: the members of each of k clusters
    in ascending order, -1 padded, from `_by_cluster`'s order and the
    clusters' sizes.  Laid out here, not on the device: the device's
    gather compiles anew for every width, which is every seed's largest
    leaf, and its compile takes longer than this."""
    starts = np.cumsum(counts) - counts
    cluster = np.repeat(np.arange(len(counts)), counts)
    out = np.full((len(counts), width), -1, np.int32)
    out[cluster, np.arange(len(order)) - starts[cluster]] = order
    return out


@jax.jit
def _pack_leaves(x: jax.Array, rowids: jax.Array, scale: jax.Array,
                 mean: jax.Array) -> jax.Array:
    """SQ8 tiles (L, C, d) int8 of the leaves' rows, 0 where padded,
    PACK_BLOCK leaves per step, so that only one block of rows is ever
    held in f32.  The last block ends at leaf L and packs again the
    leaves it shares with the block before it."""
    L, C = rowids.shape
    block = min(PACK_BLOCK, L)

    def step(i, tiles):
        start = jnp.minimum(i * block, L - block)
        r = jax.lax.dynamic_slice_in_dim(rowids, start, block)
        q = jnp.where((r >= 0)[..., None],
                      sq8_codes(x[jnp.maximum(r, 0)], scale, mean),
                      jnp.int8(0))
        return jax.lax.dynamic_update_slice_in_dim(tiles, q, start, 0)

    return jax.lax.fori_loop(0, -(-L // block), step,
                             jnp.zeros((L, C, x.shape[1]), jnp.int8))


@jax.jit
def _pca_moments(x: jax.Array):
    """The rows' mean and the scatter matrix about it."""
    mu = jnp.mean(x, axis=0)
    xc = x - mu
    return mu, jnp.matmul(xc.T, xc, precision=HIGHEST)


@jax.jit
def _project(x: jax.Array, mu: jax.Array, proj: jax.Array) -> jax.Array:
    return jnp.matmul(x - mu, proj, precision=HIGHEST)


def _pca(x: jax.Array, dims: int):
    """(x projected onto its top `dims` principal axes (n, dims) on the
    device, the axes (d, dims) and the mean (d,) as numpy)."""
    mu, scatter = _pca_moments(x)
    cov = np.asarray(scatter) / max(x.shape[0] - 1, 1)
    _, v = np.linalg.eigh(cov)
    proj = v[:, ::-1][:, :dims].astype(np.float32)
    return _project(x, mu, jnp.asarray(proj)), proj, np.asarray(mu)


def build_scann(store: VectorStore, num_leaves: int, levels: int = 2,
                pca_dims: int | None = None, seed: int = 0,
                kmeans_iters: int = 12) -> ScannIndex:
    """Build the index on the store's device from `store.vectors`, read in
    place: the table is neither fetched to the host nor copied on the
    device (with `pca_dims`, its projection (n, pca_dims) is held beside
    it).  The host keeps what is small: the centroid updates, the leaf
    and branch layouts (row ids, from the device's sort), and the PCA
    basis.

    Spans (`repro.obs`): `scann.build` (args `rows`, `leaves`, `levels`)
    holds `scann.pca` (with `pca_dims` only), `scann.kmeans` (args
    `rows`, `leaves`, `iters`: once for the leaves and, with two levels,
    once for the branches over the leaf centroids), `scann.pack` (the
    leaf and branch layouts, the SQ8 range, the int8 tiles and their row
    norms, ended once they are on the device) and `scann.upload` (the
    host's centroids, branch layout and basis onto the device)."""
    n, d = store.vectors.shape
    with obs.span("scann.build", rows=n, leaves=num_leaves, levels=levels):
        x = store.vectors
        pca, pca_mu = np.eye(d, dtype=np.float32), np.zeros(d, np.float32)
        if pca_dims is not None and pca_dims < d:
            with obs.span("scann.pca", dims=pca_dims):
                x, pca, pca_mu = jax.block_until_ready(_pca(x, pca_dims))
        with obs.span("scann.kmeans", rows=n, leaves=num_leaves,
                      iters=kmeans_iters):
            cent, assign, counts = kmeans(x, num_leaves, kmeans_iters, seed)
        if levels >= 2 and num_leaves >= 16:
            nb = max(4, int(np.sqrt(num_leaves)))
            with obs.span("scann.kmeans", rows=num_leaves, leaves=nb,
                          iters=kmeans_iters):
                bcent, bassign, bcounts = kmeans(
                    jnp.asarray(cent), nb, kmeans_iters, seed + 1)
        else:
            levels = 1
            bcent = np.zeros((1, x.shape[1]), np.float32)
        with obs.span("scann.pack"):
            cap = int(counts.max())
            cap += (-cap) % 8  # sublane alignment
            rowids = jnp.asarray(_members(np.asarray(_by_cluster(assign)),
                                          counts, cap))
            # SQ8: per-dimension affine quantization over the dataset (the
            # shared quantizer: the graph engine's shadow store uses it too)
            scale, mean = sq8_params(jnp.min(x, axis=0), jnp.max(x, axis=0))
            tiles = _pack_leaves(x, rowids, scale, mean)
            # one fused pass over the int8 tiles (no f32 copy of them)
            norms = _row_norms_sq(tiles, scale, mean)
            if levels >= 2:
                bleaves = _members(np.asarray(_by_cluster(bassign)),
                                   bcounts, int(bcounts.max()))
            else:
                bleaves = np.arange(num_leaves, dtype=np.int32)[None, :]
            jax.block_until_ready((tiles, norms))
        with obs.span("scann.upload"):
            # the PCA mean rides in the projection's last row: a query is
            # projected as q @ pca - pca_mu @ pca (project_query)
            return jax.block_until_ready(ScannIndex(
                leaf_tiles=tiles, leaf_rowids=rowids,
                leaf_centroids=jnp.asarray(cent),
                scale=scale, mean=mean,
                branch_centroids=jnp.asarray(bcent),
                branch_leaves=jnp.asarray(bleaves),
                pca=jnp.asarray(np.concatenate([pca, pca_mu[None, :] @ pca],
                                               0)),
                row_norms_sq=norms, metric=store.metric, levels=levels))


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


def _open_branches(bd: jax.Array, branch_leaves: jax.Array, nl: int,
                   L: int) -> jax.Array:
    """The branches a query opens (paper Fig. 5-①), (..., B) bool from its
    branch distances bd (..., B) over L leaves: its nb nearest, nb being
    the number that holds 2·nl leaves where branches hold the mean, then,
    nearest first, as many more as it takes for the opened branches to
    hold nl leaves.  So the nl nearest leaves of the opened branches are
    nl distinct leaves, however unevenly the branches split the leaves.
    The counters charge B branch centroids and Lb leaf slots per opened
    branch."""
    B = branch_leaves.shape[0]
    nb = min(B, max(1, -(-nl * 2 * B // L)))
    _, order = topk_smallest(bd, B)                  # nearest first
    held = jnp.sum(branch_leaves >= 0, axis=1)[order]
    rank_open = (jnp.arange(B) < nb) | (jnp.cumsum(held, axis=-1) - held < nl)
    return jnp.take_along_axis(rank_open, jnp.argsort(order, axis=-1),
                               axis=-1)


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
        opened = _open_branches(bd, index.branch_leaves, nl, L)  # (B,)
        cand = index.branch_leaves.reshape(-1)                    # (B*Lb,)
        cl = jnp.maximum(cand, 0)
        ld = distance(index.metric, qp[None], index.leaf_centroids[cl],
                      jnp.sum(index.leaf_centroids[cl] ** 2, -1))
        ld = jnp.where((cand >= 0) & jnp.repeat(opened, Lb), ld, jnp.inf)
        _, pos = topk_smallest(ld, nl)
        leaves = cl[pos]                                          # (nl,)
        cent_scored = B + jnp.sum(opened) * Lb
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
        opened = _open_branches(bd, index.branch_leaves, nl, L)  # (Q, B)
        bl = index.branch_leaves
        branch_of = jnp.zeros((L,), jnp.int32).at[
            jnp.where(bl >= 0, bl, L)].set(
                jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None],
                                 bl.shape), mode="drop")          # (L,)
        ldf = kops.distance_matrix(qp, index.leaf_centroids,
                                   metric=index.metric,
                                   use_pallas=use_pallas)         # (Q, L)
        ld = jnp.where(opened[:, branch_of], ldf, jnp.inf)
        _, leaves = topk_smallest(ld, nl)                         # (Q, nl)
        return leaves, B + jnp.sum(opened, axis=1) * Lb
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
    (DESIGN.md §4 "Scaling envelope").  The tiles run one after another
    (`lax.map`), so one tile's temporaries bound the memory; a last tile
    short of B queries is filled with copies of the last query, whose
    answers are dropped.  ids/dists are tile-size-invariant
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
        tiles = -(-Q // B)
        if tiles * B > Q:       # fill the last tile with the last query
            fill = tiles * B - Q
            queries = jnp.concatenate(
                [queries, jnp.repeat(queries[-1:], fill, axis=0)])
            bitmaps = jnp.concatenate(
                [bitmaps, jnp.repeat(bitmaps[-1:], fill, axis=0)])
        out = jax.lax.map(
            lambda qb: _scann_search_block(index, store, qb[0], qb[1],
                                           params, use_pallas,
                                           collect_trace),
            (queries.reshape(tiles, B, -1), bitmaps.reshape(tiles, B, -1)))
        return jax.tree.map(
            lambda a: a.reshape((tiles * B,) + a.shape[2:])[:Q], out)
    return _scann_search_block(index, store, queries, bitmaps, params,
                               use_pallas, collect_trace)


def _take_leaves(x: jax.Array, leaves: jax.Array) -> jax.Array:
    """x[leaves] for a per-leaf array x (L, ...), one leaf's slab per
    step.  The same values as the gather, which the TPU compiler lowers
    by slicing the whole of x into temporaries (3.1 GB for the tiles of
    3,162 leaves of 9,216 rows, by its memory analysis)."""
    def step(i, out):
        return jax.lax.dynamic_update_slice_in_dim(
            out, jax.lax.dynamic_slice_in_dim(x, leaves[i], 1, 0), i, 0)
    return jax.lax.fori_loop(
        0, leaves.shape[0], step,
        jnp.zeros((leaves.shape[0],) + x.shape[1:], x.dtype))


def _scann_search_block(index: ScannIndex, store: VectorStore, queries,
                        bitmaps, params: SearchParams, use_pallas: bool,
                        collect_trace: bool = False):
    """One query tile through the batched pipeline (stages ①–④ above)."""
    Q = queries.shape[0]
    L, C, dp = index.leaf_tiles.shape
    nl = min(params.num_leaves_to_search, L)
    with jax.named_scope("scann.select"):
        qp = project_query(index, queries)                        # (Q, dp)
        leaves, cent_scored = _select_leaves(index, qp, nl, use_pallas)

    with jax.named_scope("scann.leaf_scan"):
        # ② union of opened leaves, each tile fetched and scored once per
        # batch
        cap = min(L, Q * nl)
        uleaves, uvalid, inv = _unique_pad(leaves.reshape(-1), L, cap)
        tiles = _take_leaves(index.leaf_tiles, uleaves)          # (U, C, dp)
        rowids_u = jnp.where(uvalid[:, None],
                             _take_leaves(index.leaf_rowids, uleaves), -1)
        if index.metric == "ip":
            norms_u = jnp.zeros((cap, C), jnp.float32)                # unused
        elif index.row_norms_sq is not None:
            norms_u = _take_leaves(index.row_norms_sq, uleaves)
        else:
            norms_u = _row_norms_sq(tiles, index.scale, index.mean)
        scores_u = kops.leaf_scan_batched(qp, tiles, rowids_u, index.scale,
                                          index.mean, bitmaps, norms_u,
                                          metric=index.metric,
                                          use_pallas=use_pallas)    # (Q, U, C)

        # gather each query's opened leaves back out of the union scan
        pos_in_u = inv[leaves]                                        # (Q, nl)
        scores = jnp.take_along_axis(scores_u, pos_in_u[:, :, None], 1)
        rowids = rowids_u[pos_in_u]                                # (Q, nl, C)

        valid = rowids >= 0
        n_valid = valid.sum(axis=(1, 2))                              # (Q,)
        n_pass = jnp.isfinite(scores).sum(axis=(1, 2))

    with jax.named_scope("scann.reorder"):
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
        uslot = jnp.cumsum(is_new) - 1          # unique slot of each sorted id
        urows = jnp.zeros((rcap,), jnp.int32).at[uslot].set(srt)
        rows_u = store.vectors[urows]                               # (rcap, d)
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
