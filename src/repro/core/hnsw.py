"""HNSW-family navigable graph: construction + container.

Construction is the *deterministic, vectorizable* variant described in
DESIGN.md §13 ("Scale path"): geometric level assignment exactly as HNSW,
per-level kNN candidates (exact on levels of up to `_EXACT_THRESHOLD`
members, cluster-routed on larger ones) with random long-range extras,
the standard HNSW select-neighbors *diversity heuristic* for pruning,
reverse-edge augmentation, and a base-layer connectivity repair.  This
produces the same navigable-small-world topology class the paper's
pgvector index has (M connections per node per layer, 2M at the base
layer).  `build_graph` is the one builder, at every size: it computes
the candidates, prunes them and fills the routed levels' reverse edges
on the device, and repairs connectivity on the host.  The same recipe
in numpy, the oracle it is tested against, and a classic insert-by-
insert builder live with the tests (`tests/hnsw_host_recipe.py`).

The graph is stored the way pgvector stores it (paper §3.1): a padded
neighbor table per level — the TPU analogue of index pages.  Fetching row i
of `neighbors[l]` is one "index page access".
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.core.types import VectorStore


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HNSWGraph:
    """Padded neighbor tables. neighbors: (L, N, 2M) int32, -1 padded.

    Level 0 may use all 2M slots (HNSW spec); levels >=1 use at most M.
    """

    neighbors: jax.Array
    node_level: jax.Array  # (N,)
    entry_point: jax.Array  # ()
    m: int = dataclasses.field(metadata=dict(static=True), default=16)

    @property
    def num_levels(self) -> int:
        return self.neighbors.shape[0]

    @property
    def n(self) -> int:
        return self.neighbors.shape[1]


# ---------------------------------------------------------------------------
# Construction: the host's part.  Levels of up to _EXACT_THRESHOLD members
# take the exact kNN among them; larger ones (at 1M rows: levels 0 and 1)
# route each row to its _ROUTE_EXPAND nearest of ~2√n sampled centroids and
# take the kNN within the routed buckets (expected candidate work ≈
# expand·n²/C), since an O(n²) kNN per level is days of work at 1M-5M rows.
# ---------------------------------------------------------------------------

_EXACT_THRESHOLD = 20_000   # members of the largest level with exact kNN
_ROUTE_EXPAND = 3           # buckets each row of a routed level searches


def _pairwise_dists(x: np.ndarray, y: np.ndarray, metric: str) -> np.ndarray:
    if metric == "ip":
        return -x @ y.T
    if metric == "cos":
        xn = x / (np.linalg.norm(x, axis=1, keepdims=True) + 1e-12)
        yn = y / (np.linalg.norm(y, axis=1, keepdims=True) + 1e-12)
        return 1.0 - xn @ yn.T
    d = (x * x).sum(1)[:, None] + (y * y).sum(1)[None, :] - 2.0 * (x @ y.T)
    return np.maximum(d, 0.0)


def _augment_reverse(level_nbrs: np.ndarray, members: np.ndarray,
                     pruned: np.ndarray, m_l: int) -> None:
    """The exact levels' reverse fill: add reverse edges into free (-1)
    slots, capped at m_l per node, skipping an edge already there."""
    src = np.repeat(members, pruned.shape[1])
    dst = pruned.reshape(-1)
    ok = dst >= 0
    src, dst = src[ok], dst[ok]
    counts = (level_nbrs[:, :m_l] >= 0).sum(1)
    order = np.argsort(dst, kind="stable")
    for s, d in zip(src[order], dst[order]):
        c = counts[d]
        if c < m_l and not np.any(level_nbrs[d, :c] == s):
            level_nbrs[d, c] = s
            counts[d] += 1


def _route_centroids(n: int, rng: np.random.RandomState
                     ) -> tuple[np.ndarray, int]:
    """The rows drawn as centroids (~2√n of them), and how many of them
    each row routes to."""
    C = min(int(np.clip(2 * np.sqrt(n), 64, 4096)), n)
    return rng.choice(n, C, replace=False), min(_ROUTE_EXPAND, C)


def _bucket_order(routes: np.ndarray, C: int):
    """Group rows by bucket (their primary route) and (row, slot) pairs by
    the bucket they query.  Returns `order` (rows by bucket, stable),
    `bounds` (bucket c is `order[bounds[c]:bounds[c + 1]]`), `q_order`
    (flat pair index i * expand + slot, by queried bucket, stable) and
    `q_bounds`."""
    primary = routes[:, 0]
    order = np.argsort(primary, kind="stable")
    bounds = np.searchsorted(primary[order], np.arange(C + 1))
    # rows querying bucket c = rows routing to c through ANY slot
    flat = routes.reshape(-1)
    q_order = np.argsort(flat, kind="stable")
    q_bounds = np.searchsorted(flat[q_order], np.arange(C + 1))
    return order, bounds, q_order, q_bounds


def _repair_connectivity(level_nbrs: np.ndarray, vectors: np.ndarray,
                         metric: str, rng: np.random.RandomState,
                         max_iters: int = 16) -> None:
    """Ensure the base layer is a single weakly-connected component.

    Real HNSW graphs are connected by construction; batch construction can
    leave rare islands.  Repair: one sparse connected-components pass,
    then link EVERY minor component to its nearest node in the major one
    (bidirectional, overwriting the last slot if full); at most
    `max_iters` passes, until one component is left.  Components of more than 20,000 (major) or
    4,096 (minor) rows are sampled from `rng`.  In-place on level_nbrs.
    """
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components
    n = level_nbrs.shape[0]
    for _ in range(max_iters):
        src = np.repeat(np.arange(n), level_nbrs.shape[1])
        dstf = level_nbrs.reshape(-1)
        ok = dstf >= 0
        g = sp.coo_matrix((np.ones(int(ok.sum()), np.int8),
                           (src[ok], dstf[ok])), shape=(n, n))
        ncomp, comp = connected_components(g, directed=False)
        if ncomp == 1:
            return
        ids, counts = np.unique(comp, return_counts=True)
        major = ids[np.argmax(counts)]
        b_ids = np.flatnonzero(comp == major)
        sub = b_ids if len(b_ids) <= 20000 else \
            rng.choice(b_ids, 20000, replace=False)
        for minor in ids[ids != major]:
            a_ids = np.flatnonzero(comp == minor)
            asub = a_ids if len(a_ids) <= 4096 else \
                rng.choice(a_ids, 4096, replace=False)
            d = _pairwise_dists(vectors[asub], vectors[sub], metric)
            ai, bi = np.unravel_index(np.argmin(d), d.shape)
            a, b = int(asub[ai]), int(sub[bi])
            for u, v in ((a, b), (b, a)):
                row = level_nbrs[u]
                free = np.where(row < 0)[0]
                row[free[0] if len(free) else len(row) - 1] = v


# ---------------------------------------------------------------------------
# Candidate generation on the device: the `hnsw.knn` stage of
# `build_graph`.  The exact kNN (levels of up to _EXACT_THRESHOLD members)
# or the routed one (larger levels), plus the random long-range extras and
# the sort of both, from two jitted programs:
# f32 distances at HIGHEST precision, exact `lax.top_k`.  The centroids,
# the extras and the grouping of (row, route) pairs by bucket are drawn on
# the host, so the build consumes the same rng stream.  An exact level is
# one bucket holding every member, queried once per row.  Every shape is
# padded (rows to a multiple of _ROW_CLASS, centroids to a power of two)
# and the loops run for a traced count of tiles, so the programs depend on
# d, metric, kc and the store's row class, never on a level's member
# count or the seed.
# ---------------------------------------------------------------------------

_ROW_CLASS = 4096   # store rows are padded to a multiple of this
_TQ = 512           # (row, route) pairs per query tile
_TC = 512           # bucket members per column tile
_RB = 512           # rows per block when routing and merging
_N_EXTRA = 8        # random long-range candidates per row


def _dists_dev(x: jax.Array, y: jax.Array, metric: str) -> jax.Array:
    """`_pairwise_dists` on the device, f32 at HIGHEST precision."""
    dot = functools.partial(jnp.dot, precision=lax.Precision.HIGHEST)
    if metric == "ip":
        return -dot(x, y.T)
    if metric == "cos":
        xn = x / (jnp.linalg.norm(x, axis=1, keepdims=True) + 1e-12)
        yn = y / (jnp.linalg.norm(y, axis=1, keepdims=True) + 1e-12)
        return 1.0 - dot(xn, yn.T)
    d = (x * x).sum(1)[:, None] + (y * y).sum(1)[None, :] - 2.0 * dot(x, y.T)
    return jnp.maximum(d, 0.0)


def _rows_dist_dev(x: jax.Array, y: jax.Array, metric: str) -> jax.Array:
    """Distance from x[i] to each y[i, j]: x (b, d) against y (b, k, d)
    -> (b, k)."""
    x = x[:, None, :]
    if metric == "ip":
        return -(x * y).sum(2)
    if metric == "cos":
        xn = x / (jnp.linalg.norm(x, axis=2, keepdims=True) + 1e-12)
        yn = y / (jnp.linalg.norm(y, axis=2, keepdims=True) + 1e-12)
        return 1.0 - (xn * yn).sum(2)
    diff = y - x
    return (diff * diff).sum(2)


def _smallest(d: jax.Array, ids: jax.Array, k: int):
    """The k smallest distances of each row, ascending, and their ids."""
    neg, pos = lax.top_k(-d, k)
    return -neg, jnp.take_along_axis(ids, pos, axis=1)


@functools.partial(jax.jit, static_argnames=("metric", "expand"))
def _routes_dev(vecs, members, cents, n_blocks, *, metric, expand):
    """Each member's `expand` nearest centroids, nearest first: (P, expand)
    indices into `cents`.  vecs (P, d) store rows; members (P,) the
    level's rows; cents (CP,) the centroids' rows; both -1 padded."""
    xc = vecs[jnp.maximum(cents, 0)]

    def block(b, out):
        rows = lax.dynamic_slice(members, (b * _RB,), (_RB,))
        d = _dists_dev(vecs[jnp.maximum(rows, 0)], xc, metric)
        _, near = lax.top_k(-jnp.where(cents < 0, jnp.inf, d), expand)
        return lax.dynamic_update_slice(out, near, (b * _RB, 0))

    return lax.fori_loop(0, n_blocks, block,
                         jnp.zeros((members.shape[0], expand), jnp.int32))


@functools.partial(jax.jit, static_argnames=("metric", "kc"))
def _knn_dev(vecs, members, cols, pairs, tiles, n_qtiles, pair_pos, extra,
             n_blocks, *, metric, kc):
    """The candidates of a level's rows: (P, kc + _N_EXTRA) distances and
    local ids, ascending (stable), inf / -1 padded.

    vecs (P, d) store rows; members (P,) the level's rows (local id i is
    row members[i]).  cols (2, P + _TC): the members by bucket, as (local
    id, bucket).  pairs (2, E * P): the (row, route) pairs by the bucket
    they query, as (local id, bucket).  tiles (2, E * P / _TQ): for each
    query tile of pairs, its first column and its count of column tiles.
    pair_pos (P, E): where each row's pairs sit in `pairs`.  extra (P,
    _N_EXTRA): random candidates.  Every array is -1 padded; the loops run
    `n_qtiles` query tiles and `n_blocks` row blocks.
    """
    def rows_of(ids):
        return vecs[jnp.maximum(members[jnp.maximum(ids, 0)], 0)]

    def empty(n, k):
        return (jnp.full((n, k), jnp.inf, jnp.float32),
                jnp.full((n, k), -1, jnp.int32))

    def q_tile(t, res):
        qid = lax.dynamic_slice(pairs[0], (t * _TQ,), (_TQ,))
        qkey = lax.dynamic_slice(pairs[1], (t * _TQ,), (_TQ,))
        xq = rows_of(qid)

        def c_tile(j, run):
            s = tiles[0, t] + j * _TC
            cid = lax.dynamic_slice(cols[0], (s,), (_TC,))
            ckey = lax.dynamic_slice(cols[1], (s,), (_TC,))
            drop = ((ckey[None, :] != qkey[:, None]) | (cid < 0)[None, :]
                    | (cid[None, :] == qid[:, None]))
            d = jnp.where(drop, jnp.inf, _dists_dev(xq, rows_of(cid), metric))
            ids = jnp.where(drop, -1, cid[None, :])
            return _smallest(jnp.concatenate([run[0], d], 1),
                             jnp.concatenate([run[1], ids], 1), kc)

        run = lax.fori_loop(0, tiles[1, t], c_tile, empty(_TQ, kc))
        return tuple(lax.dynamic_update_slice(r, x, (t * _TQ, 0))
                     for r, x in zip(res, run))

    res = lax.fori_loop(0, n_qtiles, q_tile, empty(pairs.shape[1], kc))
    expand = pair_pos.shape[1]

    def block(b, out):
        r0 = b * _RB
        pos = lax.dynamic_slice(pair_pos, (r0, 0), (_RB, expand))
        ok = (pos >= 0)[:, :, None]
        pd = jnp.where(ok, res[0][jnp.maximum(pos, 0)], jnp.inf)
        pi = jnp.where(ok, res[1][jnp.maximum(pos, 0)], -1)
        kd, ki = _smallest(pd.reshape(_RB, -1), pi.reshape(_RB, -1), kc)
        ex = lax.dynamic_slice(extra, (r0, 0), (_RB, _N_EXTRA))
        ed = jnp.where(ex < 0, jnp.inf, _rows_dist_dev(
            rows_of(r0 + jnp.arange(_RB)), rows_of(ex), metric))
        cd = jnp.concatenate([kd, ed], 1)
        ci = jnp.concatenate([ki, ex], 1)
        o = jnp.argsort(cd, axis=1, stable=True)
        return tuple(lax.dynamic_update_slice(a, jnp.take_along_axis(x, o, 1),
                                              (r0, 0))
                     for a, x in zip(out, (cd, ci)))

    return lax.fori_loop(0, n_blocks, block,
                         empty(members.shape[0], kc + _N_EXTRA))


def _upload_rows(vectors: np.ndarray) -> jax.Array:
    """The store's rows on the device, zero-padded to a multiple of
    _ROW_CLASS: the one array every level's candidates are computed from."""
    n, d = vectors.shape
    pad = np.zeros((-(-n // _ROW_CLASS) * _ROW_CLASS, d), np.float32)
    pad[:n] = vectors
    return jax.device_put(pad)


def _padded_members(members: np.ndarray, P: int) -> np.ndarray:
    """A level's rows as the device programs take them: (P,) int32, -1
    padded."""
    mem = np.full(P, -1, np.int32)
    mem[:len(members)] = members
    return mem


def _knn_device(vecs: jax.Array, members: np.ndarray, metric: str, kc: int,
                rng: np.random.RandomState, routed: bool
                ) -> tuple[jax.Array, jax.Array, dict]:
    """One level's candidates: the exact kNN among its members or, if
    `routed`, the kNN within each row's routed buckets (drawing the
    centroids from `rng`); then the random extras, sorted.

    Returns, on the device, local ids (P, kc + 8) and their f32 distances,
    ascending: row i < n holds min(kc, n - 1) + min(8, n - 1) candidates,
    then -1 / inf padding, as do the rows past n.  Also the counters
    `tiles` (query tile x column tile steps run) and `pad_share` (share of
    the distances those steps computed that were masked: padding, other
    buckets, self).
    """
    P = vecs.shape[0]
    n = len(members)
    mem = _padded_members(members, P)
    if routed:
        cent_rows, expand = _route_centroids(n, rng)
        C = len(cent_rows)
        cents = np.full(max(64, 1 << (C - 1).bit_length()), -1, np.int32)
        cents[:C] = members[cent_rows]
        routes = np.asarray(_routes_dev(vecs, mem, cents, -(-n // _RB),
                                        metric=metric, expand=expand))[:n]
    else:                        # one bucket holding every member
        C, expand = 1, 1
        routes = np.zeros((n, 1), np.int32)
    order, bounds, q_order, q_bounds = _bucket_order(routes, C)
    n_pairs = n * expand
    cols = np.full((2, P + _TC), -1, np.int32)
    cols[0, :n], cols[1, :n] = order, routes[order, 0]
    pairs = np.full((2, expand * P), -1, np.int32)
    pairs[0, :n_pairs] = q_order // expand
    pairs[1, :n_pairs] = routes.reshape(-1)[q_order]
    pos = np.empty(n_pairs, np.int32)
    pos[q_order] = np.arange(n_pairs)
    pair_pos = np.full((P, expand), -1, np.int32)
    pair_pos[:n] = pos.reshape(n, expand)
    # a query tile's pairs query a run of buckets: scan their members
    n_qt = -(-n_pairs // _TQ)
    ends = np.minimum(np.arange(1, n_qt + 1) * _TQ, n_pairs)
    first = bounds[pairs[1, np.arange(n_qt) * _TQ]]
    last = bounds[pairs[1, ends - 1] + 1]
    tiles = np.zeros((2, expand * P // _TQ), np.int32)
    tiles[0, :n_qt] = first
    tiles[1, :n_qt] = -(-(last - first) // _TC)
    n_rand = min(_N_EXTRA, n - 1)
    rnd = rng.randint(0, n, size=(n, n_rand)).astype(np.int64)
    rnd = np.where(rnd == np.arange(n)[:, None], (rnd + 1) % n, rnd)
    extra = np.full((P, _N_EXTRA), -1, np.int32)
    extra[:n, :n_rand] = rnd
    d, ids = _knn_dev(vecs, mem, cols, pairs, tiles, n_qt, pair_pos, extra,
                      -(-n // _RB), metric=metric, kc=kc)
    steps = int(tiles[1].sum())
    useful = int((np.diff(q_bounds) * np.diff(bounds)).sum()) - n
    return ids, d, {"tiles": steps,
                    "pad_share": 1.0 - useful / (steps * _TQ * _TC)}


# ---------------------------------------------------------------------------
# Neighbour selection on the device: the `hnsw.prune` stage and, on routed
# levels, the reverse-edge fill of `hnsw.link`, from the candidates
# `_knn_dev` left there.  `_prune_dev` is the HNSW select-neighbors
# heuristic (the keep rule at HIGHEST precision, then
# keepPrunedConnections); `_reverse_dev` ranks each node's reverse edges by
# source, in local ids, integer work only.  Like
# `_knn_dev`, both depend on d, metric, widths and the row class only:
# rows and rounds run for a traced count.
# ---------------------------------------------------------------------------

def _prune_rows(w: int, d: int) -> int:
    """Rows per prune block: a power of two from 256 to _ROW_CLASS (so it
    divides the padded row count) whose gathered candidate rows, (rows, w,
    d) f32, stay within 128 MiB."""
    fit = (128 << 20) // (4 * w * d)
    return int(np.clip(1 << (max(fit, 1).bit_length() - 1), 256, _ROW_CLASS))


@functools.partial(jax.jit, static_argnames=("metric", "m"))
def _prune_dev(vecs, members, cand, cand_d, n_blocks, *, metric, m):
    """The HNSW select-neighbors heuristic over a level's candidates:
    (P, m) local ids, -1 padded, and the counts of slots the keep rule and
    keepPrunedConnections filled.  vecs (P, d) store rows; members (P,)
    the level's rows; cand, cand_d (P, w) `_knn_dev`'s candidates; the
    loop runs `n_blocks` blocks of `_prune_rows(w, d)` rows.

    Keep candidate c (in increasing-distance order) iff it is closer to
    the node than to every already-kept neighbour, up to m of them; then
    keepPrunedConnections (standard HNSW): slots left free take the
    closest pruned candidates, skipping ids already selected.  Kept
    candidates come first, then the pruned ones in distance order."""
    P, w = cand.shape
    rows = _prune_rows(w, vecs.shape[1])
    col = jnp.arange(w)
    earlier = col[None, :] < col[:, None]                 # [j, i]: i < j

    def block(b, carry):
        out, n_kept, n_fill = carry
        cids = lax.dynamic_slice(cand, (b * rows, 0), (rows, w))
        cd = lax.dynamic_slice(cand_d, (b * rows, 0), (rows, w))
        cvec = vecs[jnp.maximum(members[jnp.maximum(cids, 0)], 0)]
        cc = jax.vmap(lambda c: _dists_dev(c, c, metric))(cvec)

        def keep(j, st):
            kept, cnt = st
            to_kept = jnp.where(kept, lax.dynamic_index_in_dim(
                cc, j, 1, keepdims=False), jnp.inf).min(1)
            ok = (lax.dynamic_index_in_dim(cd, j, 1, keepdims=False)
                  < to_kept) & (cnt < m)
            return kept | ((col == j)[None, :] & ok[:, None]), cnt + ok

        kept, cnt = lax.fori_loop(0, w, keep, (jnp.zeros((rows, w), bool),
                                               jnp.zeros(rows, jnp.int32)))
        same = cids[:, :, None] == cids[:, None, :]
        in_kept = (same & kept[:, None, :]).any(2)
        repeat = (same & ~kept[:, None, :] & earlier).any(2)
        fill = ~kept & ~in_kept & ~repeat
        key = jnp.where(kept, col, jnp.where(fill, w + col, 2 * w))
        pick = jnp.argsort(key, axis=1, stable=True)[:, :m]
        sel = jnp.where(jnp.take_along_axis(key, pick, 1) < 2 * w,
                        jnp.take_along_axis(cids, pick, 1), -1)
        return (lax.dynamic_update_slice(out, sel, (b * rows, 0)),
                n_kept + cnt.sum(), n_fill + (sel >= 0).sum() - cnt.sum())

    zero = jnp.zeros((), jnp.int32)
    return lax.fori_loop(0, n_blocks, block,
                         (jnp.full((P, m), -1, jnp.int32), zero, zero))


def _prune_device(vecs: jax.Array, members: np.ndarray, cand: jax.Array,
                  cand_d: jax.Array, m: int, metric: str
                  ) -> tuple[jax.Array, dict]:
    """One level's pruned neighbours, (P, m) local ids on the device, -1
    padded, once ready; and the counters `kept_share` and `fill_share`:
    the shares of the level's n * m output slots filled by the keep rule
    and by keepPrunedConnections."""
    P, w = cand.shape
    n = len(members)
    pruned, kept, filled = jax.block_until_ready(_prune_dev(
        vecs, _padded_members(members, P), cand, cand_d,
        -(-n // _prune_rows(w, vecs.shape[1])), metric=metric, m=m))
    slots = n * m
    return pruned, {"kept_share": int(kept) / slots,
                    "fill_share": int(filled) / slots}


@jax.jit
def _reverse_dev(members, pruned):
    """The reverse fill of a routed level: its adjacency (P, m) in global
    ids, the forward edges first, then reverse edges in the free slots,
    ranked by source within each destination; and the counts of reverse
    edges placed and of all reverse edges.  members (P,) the level's rows,
    -1 padded; pruned (P, m) local ids, -1 padded.  Unlike
    `_augment_reverse` it does not dedup against the forward edges: a
    repeated adjacency id only wastes the slot (the engine's visited
    bitset dedups at traversal time).

    Edge e = i * m + s runs from row i to pruned[i, s], so ranking by e
    within a destination is a stable sort by destination.  Round
    r hands each destination with more than r free slots its r-th edge:
    the least e not yet taken (a scatter-min).  The rounds run to the
    largest number of reverse edges any node takes, none where the prune
    filled every slot.  A 1-D sort of the edges would do the same in one
    step, but compiles in about a minute for the TPU."""
    P, m = pruned.shape
    ok = pruned >= 0
    fwd = jnp.where(ok, members[jnp.maximum(pruned, 0)], -1)
    filled = ok.sum(1)
    dst = jnp.where(ok, pruned, P).reshape(-1)
    size = jnp.zeros(P, jnp.int32).at[dst].add(1, mode="drop")
    need = jnp.minimum(size, m - filled)       # reverse edges each node takes
    edge = jnp.arange(P * m, dtype=jnp.int32)
    live = jnp.where(need.at[dst].get(mode="fill", fill_value=0) > 0, dst, P)
    col = jnp.arange(m)[None, :]

    def take(r, st):
        adj, live = st
        first = jnp.full(P, P * m, jnp.int32).at[live].min(edge, mode="drop")
        put = (col == (filled + r)[:, None]) & (r < need)[:, None]
        adj = jnp.where(put, members[jnp.minimum(first // m, P - 1)][:, None],
                        adj)
        taken = first.at[live].get(mode="fill", fill_value=-1) == edge
        return adj, jnp.where(taken, P, live)

    adj, _ = lax.fori_loop(0, need.max(), take, (fwd, live))
    return adj, need.sum(), ok.sum()


def _reverse_device(members: np.ndarray, pruned: jax.Array
                    ) -> tuple[np.ndarray, int, int]:
    """A routed level's adjacency after the reverse fill, (n, m) global
    ids on the host, with the counts of reverse edges placed and of all
    reverse edges."""
    adj, placed, edges = _reverse_dev(
        _padded_members(members, pruned.shape[0]), pruned)
    return np.asarray(adj)[:len(members)], int(placed), int(edges)


def build_graph(store: VectorStore, m: int = 16, ef_construction: int = 32,
                seed: int = 0, max_level: int | None = None) -> HNSWGraph:
    """The HNSW graph of `store`: M neighbours per node per level, 2M at
    level 0, from `ef_construction` (at least that level's M + 8)
    candidates each.

    Levels with <= `_EXACT_THRESHOLD` members take the exact kNN among
    their members; larger levels (at 1M rows: levels 0 and 1) take the
    kNN within each row's `_ROUTE_EXPAND` nearest buckets.  The same
    graph, up to near-ties, as the host recipe of
    `tests/hnsw_host_recipe.py` where every level is exact.

    Where each stage runs: level assignment, centroid and random-extra
    draws on the host; the candidates (routes, kNN, extras and their
    sort, `_knn_device`), their pruning (`_prune_device`) and, on routed
    levels, the reverse-edge fill (`_reverse_device`) on the device, one
    level after another from the rows uploaded once; the exact levels'
    reverse fill, which dedups against forward edges, and the
    connectivity repair on the host.

    Spans (`repro.obs`): `hnsw.build` holds `hnsw.fetch` (vectors to the
    host), per level `hnsw.knn`, `hnsw.prune` and `hnsw.link` (args
    `level`, `members`), and `hnsw.upload` (the graph onto the device,
    ended once it is there).  `hnsw.knn` adds `on_device`, `tiles` and
    `pad_share` (`_knn_device`'s counters) and ends once the candidates
    are ready on the device; `hnsw.prune` adds `on_device`, `kept_share`
    and `fill_share` (`_prune_device`'s) and ends once the pruned ids are
    ready there.  `hnsw.link` covers the reverse fill, the pull of the
    adjacency, its write into the host table and the repair, and adds
    `reverse_placed` and `reverse_dropped`: the reverse edges that found
    a free slot and those that did not (a full node, or at exact levels
    an edge already there).
    """
    with obs.span("hnsw.build"):
        with obs.span("hnsw.fetch"):
            vectors = np.asarray(store.vectors)
        nbrs, levels, entry = _link_levels(
            vectors, store.metric, m, ef_construction, seed, max_level)
        with obs.span("hnsw.upload"):
            return jax.block_until_ready(HNSWGraph(
                neighbors=jnp.asarray(nbrs),
                node_level=jnp.asarray(levels, jnp.int32),
                entry_point=jnp.asarray(entry, jnp.int32), m=m))


# The benchmark's HNSW index (`fvsbench/indexes/hnsw.py`) imports the
# builder under this name; it is `build_graph` itself.
build_graph_blocked = build_graph


def _link_levels(vectors: np.ndarray, metric: str, m: int,
                 ef_construction: int, seed: int, max_level: int | None):
    """The level loop of `build_graph`: (neighbors, levels, entry) on the
    host."""
    n = vectors.shape[0]
    rng = np.random.RandomState(seed)
    ml = 1.0 / np.log(max(m, 2))
    levels = np.minimum(
        np.floor(-np.log(rng.uniform(1e-12, 1.0, n)) * ml).astype(np.int64),
        12)
    if max_level is not None:
        levels = np.minimum(levels, max_level)
    top = int(levels.max())
    entry = int(np.argmax(levels))
    mmax0 = 2 * m
    nbrs = np.full((top + 1, n, mmax0), -1, np.int32)
    vecs = None              # the rows on the device, uploaded once

    for lvl in range(top + 1):
        members = np.where(levels >= lvl)[0]
        if len(members) <= 1:
            continue
        n_m = len(members)
        at = {"level": lvl, "members": n_m}
        m_l = mmax0 if lvl == 0 else m
        with obs.span("hnsw.knn", **at) as knn:
            if vecs is None:
                vecs = _upload_rows(vectors)
            cand, cand_d, counters = _knn_device(
                vecs, members, metric, max(ef_construction, m_l + 8), rng,
                n_m > _EXACT_THRESHOLD)
            jax.block_until_ready((cand, cand_d))
            knn.set_metadata(on_device=True, **counters)
        with obs.span("hnsw.prune", **at) as prune:
            pruned, shares = _prune_device(vecs, members, cand, cand_d, m_l,
                                           metric)
            prune.set_metadata(on_device=True, **shares)
        with obs.span("hnsw.link", **at) as link:
            if n_m <= _EXACT_THRESHOLD:
                local = np.asarray(pruned)[:n_m]
                fwd = np.where(local >= 0,
                               members[np.maximum(local, 0)], -1)
                nbrs[lvl, members, :m_l] = fwd
                _augment_reverse(nbrs[lvl], members, fwd, m_l)
                edges = int((fwd >= 0).sum())
                placed = int((nbrs[lvl, members, :m_l] >= 0).sum()) - edges
            else:
                adj, placed, edges = _reverse_device(members, pruned)
                nbrs[lvl, members, :m_l] = adj
            if lvl == 0:
                _repair_connectivity(nbrs[0], vectors, metric, rng)
            link.set_metadata(reverse_placed=placed,
                              reverse_dropped=edges - placed)
    return nbrs, levels, entry


# ---------------------------------------------------------------------------
# JAG-style attribute-partitioned graphs (DESIGN.md §14).  For a hot
# predicate *family* — a concrete filter bitmap shared by many queries —
# the agnostic/filtered trade-off can be skipped entirely: build a
# dedicated subgraph over exactly the family's passing rows and traverse
# it UNFILTERED (every row passes by construction, so the per-node filter
# checks the paper measures vanish).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GraphPartition:
    """One predicate family's dedicated subgraph.

    rows: (n_f,) int64 ascending global row ids of the family's passing
    set — the local→global id map (subgraph results are `rows[local]`).
    store/graph index the *gathered* rows, so local ids are dense and the
    heap rows are physically the same vectors as the base store's (the
    storage layer charges the same heap pages; only the adjacency tier is
    family-private).
    """

    tag: str
    bitmap: np.ndarray          # (W,) uint32 packed family bitmap
    rows: np.ndarray            # (n_f,) int64 global row ids, ascending
    store: VectorStore          # gathered family rows (+ SQ8 shadow)
    graph: HNSWGraph            # subgraph over the local rows


@dataclasses.dataclass(frozen=True)
class PartitionedGraph:
    """The registered family subgraphs + the staleness guard.

    built_n: base-store row count at build time.  A store that has grown
    past it (live ingest, DESIGN.md §12) invalidates every partition —
    new rows may pass a family's predicate but are absent from its
    subgraph, so the executor must fall back to the base index until a
    rebuild re-registers the families.
    """

    partitions: tuple[GraphPartition, ...]
    built_n: int

    @property
    def tags(self) -> tuple[str, ...]:
        return tuple(p.tag for p in self.partitions)

    def match(self, bitmaps) -> np.ndarray:
        """(Q,) int32 partition index whose bitmap equals each query's
        bitmap word-for-word, or -1 (exact match only — a family
        subgraph can never serve a predicate it was not built for)."""
        bm = np.asarray(bitmaps)
        if not self.partitions:
            return np.full(bm.shape[0], -1, np.int32)
        # dedupe first: family workloads repeat the same predicate bitmap
        # across the batch, and each distinct bitmap needs exactly one
        # comparison against the family catalog
        uniq, inv = np.unique(bm, axis=0, return_inverse=True)
        fam = np.stack([p.bitmap for p in self.partitions])
        eq = (uniq[:, None, :] == fam[None, :, :]).all(-1)
        hit = eq.any(1)
        um = np.where(hit, eq.argmax(1), -1).astype(np.int32)
        return um[inv.reshape(-1)]


def build_graph_partitioned(store: VectorStore,
                            families: dict[str, np.ndarray], m: int = 16,
                            ef_construction: int = 32, seed: int = 0
                            ) -> PartitionedGraph:
    """Build one subgraph per predicate family (JAG tier, DESIGN.md §14).

    families maps tag -> packed (W,) uint32 bitmap over the store's rows.
    Each family's passing rows are gathered into a dense sub-store
    (carrying the SQ8 shadow rows verbatim when present, so quantized
    traversal works unchanged) and indexed by `build_graph`, the same
    recipe as the base graph.
    """
    from repro.core.types import unpack_bitmap
    n = store.n
    parts = []
    for i, tag in enumerate(sorted(families)):
        bm = np.asarray(families[tag], np.uint32)
        rows = np.nonzero(unpack_bitmap(bm, n))[0].astype(np.int64)
        if rows.size < 2:
            raise ValueError(f"family {tag!r} has {rows.size} passing "
                             "rows; a subgraph needs at least 2")
        sub = gather_substore(store, rows)
        g = build_graph(sub, m=m, ef_construction=ef_construction,
                        seed=seed + i)
        parts.append(GraphPartition(tag=tag, bitmap=bm, rows=rows,
                                    store=sub, graph=g))
    return PartitionedGraph(partitions=tuple(parts), built_n=n)


def gather_substore(store: VectorStore, rows: np.ndarray) -> VectorStore:
    """Dense sub-store over `rows` (ascending global ids), carrying the
    SQ8 shadow rows verbatim when present so quantized traversal works
    unchanged on the subgraph."""
    sub = VectorStore.build(np.asarray(store.vectors)[rows],
                            metric=store.metric)
    if store.has_sq8:
        sub = dataclasses.replace(
            sub, q_vectors=jnp.asarray(np.asarray(store.q_vectors)[rows]),
            q_scale=store.q_scale, q_mean=store.q_mean,
            q_norms_sq=jnp.asarray(np.asarray(store.q_norms_sq)[rows]))
    return sub
