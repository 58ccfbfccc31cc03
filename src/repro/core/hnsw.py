"""HNSW-family navigable graph: construction + container.

Construction here is the *deterministic, vectorizable* variant described in
DESIGN.md §8(2): geometric level assignment exactly as HNSW, per-level exact
kNN candidate generation, and the standard HNSW select-neighbors *diversity
heuristic* for pruning, plus reverse-edge augmentation.  This produces the
same navigable-small-world topology class the paper's pgvector index has
(M connections per node per layer, 2M at the base layer), while being
buildable in seconds on CPU.  An incremental reference builder
(`build_incremental`) with classic insert semantics is kept for small-N
validation tests.

The graph is stored the way pgvector stores it (paper §3.1): a padded
neighbor table per level — the TPU analogue of index pages.  Fetching row i
of `neighbors[l]` is one "index page access".
"""
from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

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
# Vectorized construction
# ---------------------------------------------------------------------------

def _pairwise_dists(x: np.ndarray, y: np.ndarray, metric: str) -> np.ndarray:
    if metric == "ip":
        return -x @ y.T
    if metric == "cos":
        xn = x / (np.linalg.norm(x, axis=1, keepdims=True) + 1e-12)
        yn = y / (np.linalg.norm(y, axis=1, keepdims=True) + 1e-12)
        return 1.0 - xn @ yn.T
    d = (x * x).sum(1)[:, None] + (y * y).sum(1)[None, :] - 2.0 * (x @ y.T)
    return np.maximum(d, 0.0)


def _knn_among(vectors: np.ndarray, metric: str, k: int,
               block: int = 2048) -> tuple[np.ndarray, np.ndarray]:
    """Exact kNN of each row among all rows (self excluded)."""
    n = vectors.shape[0]
    k = min(k, n - 1)
    ids = np.empty((n, k), np.int64)
    dst = np.empty((n, k), np.float32)
    for s in range(0, n, block):
        e = min(s + block, n)
        d = _pairwise_dists(vectors[s:e], vectors, metric)
        d[np.arange(e - s), np.arange(s, e)] = np.inf  # drop self
        part = np.argpartition(d, k - 1, axis=1)[:, :k]
        pd = np.take_along_axis(d, part, axis=1)
        order = np.argsort(pd, axis=1)
        ids[s:e] = np.take_along_axis(part, order, axis=1)
        dst[s:e] = np.take_along_axis(pd, order, axis=1)
    return ids, dst


def _rows_dist(vectors: np.ndarray, ids: np.ndarray, metric: str) -> np.ndarray:
    """Distance from row i to vectors[ids[i, j]] — (n, k)."""
    x = vectors[:, None, :]
    y = vectors[ids]
    if metric == "ip":
        return -np.einsum("nod,nkd->nk", x, y)[:, :]
    if metric == "cos":
        xn = x / (np.linalg.norm(x, axis=2, keepdims=True) + 1e-12)
        yn = y / (np.linalg.norm(y, axis=2, keepdims=True) + 1e-12)
        return 1.0 - np.einsum("nod,nkd->nk", xn, yn)
    diff = y - x
    return np.einsum("nkd,nkd->nk", diff, diff)


def _repair_connectivity(level_nbrs: np.ndarray, vectors: np.ndarray,
                         metric: str, max_iters: int = 64) -> None:
    """Ensure the base layer is a single weakly-connected component.

    Real HNSW graphs are connected by construction; batch construction can
    leave rare islands.  Repair: link each minor component to its nearest
    node in the major component (bidirectional, overwriting the last slot
    if full).  In-place on level_nbrs.
    """
    n = level_nbrs.shape[0]
    for _ in range(max_iters):
        comp = _components(level_nbrs)
        ids, counts = np.unique(comp, return_counts=True)
        if len(ids) == 1:
            return
        major = ids[np.argmax(counts)]
        minor = ids[ids != major][np.argmin(counts[ids != major])]
        a_ids = np.where(comp == minor)[0]
        b_ids = np.where(comp == major)[0]
        # nearest cross pair (blocked if large)
        sub = b_ids if len(b_ids) <= 20000 else \
            b_ids[np.random.RandomState(0).choice(len(b_ids), 20000, False)]
        d = _pairwise_dists(vectors[a_ids], vectors[sub], metric)
        ai, bi = np.unravel_index(np.argmin(d), d.shape)
        a, b = int(a_ids[ai]), int(sub[bi])
        for u, v in ((a, b), (b, a)):
            row = level_nbrs[u]
            free = np.where(row < 0)[0]
            row[free[0] if len(free) else len(row) - 1] = v


def _components(level_nbrs: np.ndarray) -> np.ndarray:
    """Weakly-connected components via union-find over the edge list."""
    n = level_nbrs.shape[0]
    parent = np.arange(n)

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    src = np.repeat(np.arange(n), level_nbrs.shape[1])
    dst = level_nbrs.reshape(-1)
    ok = dst >= 0
    for u, v in zip(src[ok], dst[ok]):
        ru, rv = find(u), find(v)
        if ru != rv:
            parent[ru] = rv
    return np.array([find(i) for i in range(n)])


_PRUNE_THREADS = 8


def _diversity_prune(vectors: np.ndarray, cand_ids: np.ndarray,
                     cand_d: np.ndarray, m: int, metric: str,
                     block: int = 4096) -> np.ndarray:
    """HNSW select-neighbors heuristic, vectorized over nodes.

    Keep candidate c (in increasing-distance order) iff it is closer to the
    node than to every already-kept neighbor.  Returns (n, m) ids, -1 padded.
    Node blocks are independent and run on a thread pool (numpy releases
    the interpreter lock in these kernels); the result does not depend on
    the thread count.  Each running block holds about 250 MB at 4096
    nodes, 48 candidates and d=128, so the pool is capped at
    `_PRUNE_THREADS` whatever the host's core count.
    """
    n = cand_ids.shape[0]
    out = np.full((n, m), -1, np.int64)
    starts = range(0, n, block)
    workers = max(1, min(len(starts), len(os.sched_getaffinity(0)),
                         _PRUNE_THREADS))
    with ThreadPoolExecutor(max_workers=workers) as pool:
        for s, sel in zip(starts, pool.map(
                lambda s: _prune_block(vectors, cand_ids[s:s + block],
                                       cand_d[s:s + block], m, metric),
                starts)):
            out[s:s + block, :sel.shape[1]] = sel
    return out


def _prune_block(vectors: np.ndarray, cids: np.ndarray, cd: np.ndarray,
                 m: int, metric: str) -> np.ndarray:
    """`_diversity_prune` of one node block: (b, kc) candidates (ids and
    distances to the node, ascending) -> (b, min(m, kc)) ids, -1 padded."""
    b, kc = cids.shape
    cvec = vectors[cids]                           # (b, kc, d)
    # pairwise distances between candidates of the same node: (b, kc, kc)
    if metric == "ip":
        cc = -np.einsum("bid,bjd->bij", cvec, cvec)
    elif metric == "cos":
        cn = cvec / (np.linalg.norm(cvec, axis=2, keepdims=True) + 1e-12)
        cc = 1.0 - np.einsum("bid,bjd->bij", cn, cn)
    else:
        sq = (cvec * cvec).sum(2)
        cc = sq[:, :, None] + sq[:, None, :] - 2.0 * np.einsum(
            "bid,bjd->bij", cvec, cvec)
    kept = np.zeros((b, kc), bool)
    kept_cnt = np.zeros(b, np.int64)
    for j in range(kc):
        # distance from candidate j to every kept candidate
        d_to_kept = np.where(kept, cc[:, j, :], np.inf)
        ok = (cd[:, j] < d_to_kept.min(axis=1)) & (kept_cnt < m)
        kept[:, j] = ok
        kept_cnt += ok
    # keepPrunedConnections (standard HNSW): slots left free take the
    # closest pruned candidates, skipping ids already selected.  Kept
    # candidates come first, then the pruned ones in distance order.
    same = cids[:, :, None] == cids[:, None, :]               # (b, kc, kc)
    in_kept = (same & kept[:, None, :]).any(2)
    earlier = np.tril(np.ones((kc, kc), bool), -1)            # [j, i]: i < j
    repeat = (same & ~kept[:, None, :] & earlier).any(2)
    fill = ~kept & ~in_kept & ~repeat
    col = np.arange(kc)
    key = np.where(kept, col, np.where(fill, kc + col, 2 * kc))
    pick = np.argsort(key, axis=1, kind="stable")[:, :m]
    return np.where(np.take_along_axis(key, pick, 1) < 2 * kc,
                    np.take_along_axis(cids, pick, 1), -1)


def build_graph(store: VectorStore, m: int = 16, ef_construction: int = 64,
                seed: int = 0, max_level: int | None = None) -> HNSWGraph:
    vectors = np.asarray(store.vectors)
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
    nbrs = np.full((top + 1, n, mmax0), -1, np.int64)

    for lvl in range(top + 1):
        members = np.where(levels >= lvl)[0]
        if len(members) <= 1:
            continue
        mv = vectors[members]
        m_l = mmax0 if lvl == 0 else m
        kc = min(max(ef_construction, m_l + 8), len(members) - 1)
        cand_local, cand_d = _knn_among(mv, store.metric, kc)
        # Long-range candidates (NSW semantics): real HNSW's insertion search
        # exposes far nodes to the pruning heuristic, which keeps a few long
        # edges for navigability.  We reproduce that by appending random
        # candidates before pruning.
        n_m = len(members)
        n_rand = min(8, n_m - 1)
        if n_rand > 0:
            rnd = rng.randint(0, n_m, size=(n_m, n_rand)).astype(np.int64)
            rnd = np.where(rnd == np.arange(n_m)[:, None],
                           (rnd + 1) % n_m, rnd)
            rd = _rows_dist(mv, rnd, store.metric)
            cand_local = np.concatenate([cand_local, rnd], 1)
            cand_d = np.concatenate([cand_d, rd], 1)
            order = np.argsort(cand_d, axis=1, kind="stable")
            cand_local = np.take_along_axis(cand_local, order, 1)
            cand_d = np.take_along_axis(cand_d, order, 1)
        pruned_local = _diversity_prune(mv, cand_local, cand_d, m_l, store.metric)
        # map local ids back to global
        valid = pruned_local >= 0
        pruned = np.where(valid, members[np.clip(pruned_local, 0, None)], -1)
        nbrs[lvl, members, :m_l] = pruned[:, :m_l]
        # reverse-edge augmentation: fill free slots with reverse links
        _augment_reverse(nbrs[lvl], members, pruned, m_l)
        if lvl == 0:
            _repair_connectivity(nbrs[0], vectors, store.metric)

    return HNSWGraph(neighbors=jnp.asarray(nbrs, jnp.int32),
                     node_level=jnp.asarray(levels, jnp.int32),
                     entry_point=jnp.asarray(entry, jnp.int32), m=m)


def _augment_reverse(level_nbrs: np.ndarray, members: np.ndarray,
                     pruned: np.ndarray, m_l: int) -> None:
    """Add reverse edges into free (-1) slots, capped at m_l per node."""
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


# ---------------------------------------------------------------------------
# Incremental reference builder (classic HNSW inserts) — small N only.
# ---------------------------------------------------------------------------

def build_incremental(store: VectorStore, m: int = 16,
                      ef_construction: int = 64, seed: int = 0) -> HNSWGraph:
    vectors = np.asarray(store.vectors)
    n = vectors.shape[0]
    rng = np.random.RandomState(seed)
    ml = 1.0 / np.log(max(m, 2))
    levels = np.minimum(
        np.floor(-np.log(rng.uniform(1e-12, 1.0, n)) * ml).astype(np.int64), 12)
    top = int(levels.max())
    mmax0 = 2 * m
    nbrs = np.full((top + 1, n, mmax0), -1, np.int64)
    metric = store.metric

    def dist(a, b_ids):
        return _pairwise_dists(vectors[a][None], vectors[b_ids], metric)[0]

    def greedy(q, entry, lvl):
        cur, cur_d = entry, dist(q, np.array([entry]))[0]
        while True:
            nb = nbrs[lvl, cur]
            nb = nb[nb >= 0]
            if len(nb) == 0:
                return cur
            ds = dist(q, nb)
            j = int(np.argmin(ds))
            if ds[j] < cur_d:
                cur, cur_d = int(nb[j]), float(ds[j])
            else:
                return cur

    def search_layer(q, entry, lvl, ef):
        visited = {entry}
        ds0 = float(dist(q, np.array([entry]))[0])
        cand = [(ds0, entry)]
        result = [(ds0, entry)]
        while cand:
            cand.sort()
            d_c, c = cand.pop(0)
            result.sort()
            if d_c > result[min(len(result), ef) - 1][0] and len(result) >= ef:
                break
            nb = nbrs[lvl, c]
            nb = [int(x) for x in nb[nb >= 0] if int(x) not in visited]
            if not nb:
                continue
            visited.update(nb)
            ds = dist(q, np.array(nb))
            worst = result[min(len(result), ef) - 1][0]
            for dd, node in zip(ds, nb):
                if len(result) < ef or dd < worst:
                    cand.append((float(dd), node))
                    result.append((float(dd), node))
                    result.sort()
                    result = result[:ef]
                    worst = result[-1][0]
        return result

    def select(q_id, cand_pairs, m_l):
        cand_pairs = sorted(cand_pairs)
        kept: list[int] = []
        for d_c, c in cand_pairs:
            if len(kept) >= m_l:
                break
            if all(_pairwise_dists(vectors[c][None], vectors[np.array([k])],
                                   metric)[0, 0] > d_c for k in kept):
                kept.append(c)
        return kept

    entry = 0
    entry_level = int(levels[0])
    for i in range(1, n):
        lvl_i = int(levels[i])
        ep = entry
        for lvl in range(entry_level, lvl_i, -1):
            ep = greedy(i, ep, min(lvl, entry_level))
        for lvl in range(min(lvl_i, entry_level), -1, -1):
            res = search_layer(i, ep, lvl, ef_construction)
            m_l = mmax0 if lvl == 0 else m
            sel = select(i, res, m_l)
            nbrs[lvl, i, : len(sel)] = sel
            for s in sel:
                cur = nbrs[lvl, s]
                free = np.where(cur < 0)[0]
                if len(free):
                    cur[free[0]] = i
                else:
                    # re-prune neighbor's list with i included
                    cand = [(float(_pairwise_dists(vectors[s][None],
                                                   vectors[np.array([c])],
                                                   metric)[0, 0]), int(c))
                            for c in cur] + [
                        (float(_pairwise_dists(vectors[s][None],
                                               vectors[np.array([i])],
                                               metric)[0, 0]), i)]
                    sel2 = select(s, cand, m_l)
                    cur[:] = -1
                    cur[: len(sel2)] = sel2
            ep = res[0][1]
        if lvl_i > entry_level:
            entry, entry_level = i, lvl_i

    return HNSWGraph(neighbors=jnp.asarray(nbrs, jnp.int32),
                     node_level=jnp.asarray(levels, jnp.int32),
                     entry_point=jnp.asarray(entry, jnp.int32), m=m)


# ---------------------------------------------------------------------------
# Blocked (cluster-routed) construction — the >=1M-row path (DESIGN.md §13).
#
# `build_graph`'s per-level exact kNN is O(n²) per level; at the sharding
# bench's operating point (1M-5M × 768) that is days of single-core work.
# The blocked builder keeps the construction *recipe* — geometric levels,
# long-range candidates, diversity pruning, reverse augmentation,
# base-layer connectivity repair — and replaces only the candidate
# generation on large levels with cluster routing: rows route to their
# `route_expand` nearest of ~2√n sampled centroids and take exact kNN
# within the routed buckets (expected candidate work ≈ expand·n²/C).
# Small levels (< exact_threshold members) still use the exact kNN, so
# upper navigation layers are identical in kind to build_graph's.
# ---------------------------------------------------------------------------

def _knn_routed(mv: np.ndarray, metric: str, kc: int,
                rng: np.random.RandomState, route_expand: int = 3,
                num_centroids: int | None = None
                ) -> tuple[np.ndarray, np.ndarray]:
    """Approximate kNN among rows via sampled-centroid bucket routing."""
    n = mv.shape[0]
    kc = min(kc, n - 1)
    C = num_centroids or int(np.clip(2 * np.sqrt(n), 64, 4096))
    C = min(C, n)
    expand = min(route_expand, C)
    cents = mv[rng.choice(n, C, replace=False)]
    routes = np.empty((n, expand), np.int64)
    for s in range(0, n, 8192):
        e = min(s + 8192, n)
        d = _pairwise_dists(mv[s:e], cents, metric)
        routes[s:e] = np.argpartition(d, expand - 1, axis=1)[:, :expand]
    primary = routes[:, 0]
    order = np.argsort(primary, kind="stable")
    bounds = np.searchsorted(primary[order], np.arange(C + 1))
    # rows querying bucket c = rows routing to c through ANY slot
    q_order = np.argsort(routes.reshape(-1), kind="stable")
    q_rows = q_order // expand
    q_bounds = np.searchsorted(routes.reshape(-1)[q_order],
                               np.arange(C + 1))
    ids = np.full((n, kc), -1, np.int64)
    dst = np.full((n, kc), np.inf, np.float32)
    for c in range(C):
        grp = order[bounds[c]:bounds[c + 1]]
        qr = q_rows[q_bounds[c]:q_bounds[c + 1]]
        if len(grp) == 0 or len(qr) == 0:
            continue
        d = _pairwise_dists(mv[qr], mv[grp], metric)
        d[qr[:, None] == grp[None, :]] = np.inf      # drop self
        t = min(kc, len(grp))
        part = np.argpartition(d, t - 1, axis=1)[:, :t]
        pd = np.take_along_axis(d, part, axis=1).astype(np.float32)
        # merge bucket top-t into the running per-row top-kc
        cat_d = np.concatenate([dst[qr], pd], axis=1)
        cat_i = np.concatenate([ids[qr], grp[part]], axis=1)
        sel = np.argpartition(cat_d, kc - 1, axis=1)[:, :kc]
        sd = np.take_along_axis(cat_d, sel, axis=1)
        si = np.take_along_axis(cat_i, sel, axis=1)
        o = np.argsort(sd, axis=1, kind="stable")
        dst[qr] = np.take_along_axis(sd, o, axis=1)
        ids[qr] = np.take_along_axis(si, o, axis=1)
    # a row can reach the same neighbor through several buckets: mask the
    # sorted-adjacent duplicates so the pruner never keeps a repeat
    dup = np.zeros_like(ids, bool)
    srt = np.sort(ids, axis=1)
    inv = np.argsort(ids, axis=1, kind="stable")
    dup_sorted = np.concatenate(
        [np.zeros((n, 1), bool), srt[:, 1:] == srt[:, :-1]], axis=1)
    np.put_along_axis(dup, inv, dup_sorted, axis=1)
    dst[dup] = np.inf
    ids[dup] = -1
    o = np.argsort(dst, axis=1, kind="stable")
    return (np.take_along_axis(ids, o, axis=1),
            np.take_along_axis(dst, o, axis=1))


def _augment_reverse_blocked(level_nbrs: np.ndarray, members: np.ndarray,
                             pruned: np.ndarray, m_l: int) -> None:
    """Vectorized reverse-edge fill: rank edges within each destination
    group and scatter into the free slots in one shot (the per-edge
    python loop of `_augment_reverse` is the 1M-row bottleneck).  Unlike
    the exact twin it does not dedup against existing forward edges — a
    repeated adjacency id only wastes the slot (the engine's visited
    bitset dedups at traversal time)."""
    src = np.repeat(members, pruned.shape[1])
    dst = pruned.reshape(-1)
    ok = dst >= 0
    src, dst = src[ok], dst[ok]
    if len(dst) == 0:
        return
    order = np.argsort(dst, kind="stable")
    src, dst = src[order], dst[order]
    first = np.concatenate([[True], dst[1:] != dst[:-1]])
    grp_start = np.flatnonzero(first)
    rank = np.arange(len(dst)) - grp_start[np.cumsum(first) - 1]
    slot = (level_nbrs[dst, :m_l] >= 0).sum(1) + rank
    keep = slot < m_l
    level_nbrs[dst[keep], slot[keep]] = src[keep]


def _repair_connectivity_blocked(level_nbrs: np.ndarray,
                                 vectors: np.ndarray, metric: str,
                                 rng: np.random.RandomState,
                                 max_iters: int = 16) -> None:
    """scipy-csgraph twin of `_repair_connectivity`: one sparse
    connected-components pass links EVERY minor component to the major
    one per iteration (the union-find python loop is quadratic-ish in
    practice at 1M rows)."""
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


def build_graph_blocked(store: VectorStore, m: int = 16,
                        ef_construction: int = 32, seed: int = 0,
                        max_level: int | None = None,
                        exact_threshold: int = 20_000,
                        route_expand: int = 3) -> HNSWGraph:
    """`build_graph` recipe with cluster-routed candidates on big levels.

    Levels with <= `exact_threshold` members build exactly like
    `build_graph`; larger levels (at 1M rows: levels 0 and 1) swap the
    O(n²) exact kNN for `_knn_routed` and the python-loop reverse/repair
    passes for their vectorized twins.  Same topology class, not
    bit-identical to `build_graph`.
    """
    vectors = np.asarray(store.vectors)
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
    nbrs = np.full((top + 1, n, mmax0), -1, np.int64)

    for lvl in range(top + 1):
        members = np.where(levels >= lvl)[0]
        if len(members) <= 1:
            continue
        mv = vectors[members]
        m_l = mmax0 if lvl == 0 else m
        kc = min(max(ef_construction, m_l + 8), len(members) - 1)
        if len(members) <= exact_threshold:
            cand_local, cand_d = _knn_among(mv, store.metric, kc)
        else:
            cand_local, cand_d = _knn_routed(mv, store.metric, kc, rng,
                                             route_expand=route_expand)
        n_m = len(members)
        n_rand = min(8, n_m - 1)
        if n_rand > 0:
            rnd = rng.randint(0, n_m, size=(n_m, n_rand)).astype(np.int64)
            rnd = np.where(rnd == np.arange(n_m)[:, None],
                           (rnd + 1) % n_m, rnd)
            rd = _rows_dist(mv, rnd, store.metric)
            cand_local = np.concatenate([cand_local, rnd], 1)
            cand_d = np.concatenate([cand_d, rd], 1)
            order = np.argsort(cand_d, axis=1, kind="stable")
            cand_local = np.take_along_axis(cand_local, order, 1)
            cand_d = np.take_along_axis(cand_d, order, 1)
        pruned_local = _diversity_prune(mv, cand_local, cand_d, m_l,
                                        store.metric)
        valid = pruned_local >= 0
        pruned = np.where(valid, members[np.clip(pruned_local, 0, None)], -1)
        nbrs[lvl, members, :m_l] = pruned[:, :m_l]
        if len(members) <= exact_threshold:
            _augment_reverse(nbrs[lvl], members, pruned, m_l)
        else:
            _augment_reverse_blocked(nbrs[lvl], members, pruned, m_l)
        if lvl == 0:
            if n <= exact_threshold:
                _repair_connectivity(nbrs[0], vectors, store.metric)
            else:
                _repair_connectivity_blocked(nbrs[0], vectors,
                                             store.metric, rng)

    return HNSWGraph(neighbors=jnp.asarray(nbrs, jnp.int32),
                     node_level=jnp.asarray(levels, jnp.int32),
                     entry_point=jnp.asarray(entry, jnp.int32), m=m)


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
                            ef_construction: int = 32, seed: int = 0,
                            blocked_threshold: int = 20_000
                            ) -> PartitionedGraph:
    """Build one subgraph per predicate family (JAG tier, DESIGN.md §14).

    families maps tag -> packed (W,) uint32 bitmap over the store's rows.
    Each family's passing rows are gathered into a dense sub-store
    (carrying the SQ8 shadow rows verbatim when present, so quantized
    traversal works unchanged) and indexed with the same recipe as the
    base graph — `build_graph` below `blocked_threshold` rows, the
    cluster-routed `build_graph_blocked` above it (the PR-9 builder that
    scales past the toy grids).
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
        build = (build_graph if rows.size <= blocked_threshold
                 else build_graph_blocked)
        g = build(sub, m=m, ef_construction=ef_construction, seed=seed + i)
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
