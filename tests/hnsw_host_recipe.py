"""The HNSW build on the host: the oracle that `repro.core.hnsw.build_graph`
is checked against, stage by stage and whole.

`host_build_graph` is the construction recipe with every stage in numpy:
geometric levels, the exact kNN among each level's members (`_knn_among`),
random long-range extras, the diversity prune (`_diversity_prune`), the
reverse fill that dedups against forward edges and the library's
connectivity repair.  `_knn_routed` and `_augment_reverse_blocked` are the
host forms of the routed levels' candidates and reverse fill.
`build_incremental` is the classic insert-by-insert HNSW, for small-N
recall checks.  Not a test module: pytest does not collect it.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import jax.numpy as jnp
import numpy as np

from repro.core import hnsw
from repro.core.hnsw import HNSWGraph, _pairwise_dists
from repro.core.types import VectorStore


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


def _knn_routed(mv: np.ndarray, metric: str, kc: int,
                rng: np.random.RandomState) -> tuple[np.ndarray, np.ndarray]:
    """Approximate kNN among rows via sampled-centroid bucket routing."""
    n = mv.shape[0]
    kc = min(kc, n - 1)
    cent_rows, expand = hnsw._route_centroids(n, rng)
    C = len(cent_rows)
    cents = mv[cent_rows]
    routes = np.empty((n, expand), np.int64)
    for s in range(0, n, 8192):
        e = min(s + 8192, n)
        d = _pairwise_dists(mv[s:e], cents, metric)
        routes[s:e] = np.argpartition(d, expand - 1, axis=1)[:, :expand]
    order, bounds, q_order, q_bounds = hnsw._bucket_order(routes, C)
    q_rows = q_order // expand
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
    # a row's routes are distinct buckets, and buckets partition the rows,
    # so no candidate repeats
    return ids, dst


_PRUNE_THREADS = 8


def _diversity_prune(vectors: np.ndarray, cand_ids: np.ndarray,
                     cand_d: np.ndarray, m: int, metric: str,
                     block: int = 4096) -> np.ndarray:
    """HNSW select-neighbors heuristic, vectorized over nodes: the host
    form of `hnsw._prune_dev`.

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


def _augment_reverse_blocked(level_nbrs: np.ndarray, members: np.ndarray,
                             pruned: np.ndarray, m_l: int) -> None:
    """Vectorized reverse-edge fill, the host form of `hnsw._reverse_dev`:
    rank edges within each destination group and scatter into the free
    slots in one shot.  Unlike `hnsw._augment_reverse` it does not dedup
    against existing forward edges — a repeated adjacency id only wastes
    the slot (the engine's visited bitset dedups at traversal time)."""
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


def host_build_graph(store: VectorStore, m: int = 16,
                     ef_construction: int = 32, seed: int = 0,
                     max_level: int | None = None) -> HNSWGraph:
    """`hnsw.build_graph`'s recipe at every level on the host, with the
    exact kNN: the same rng stream, so the same levels, entry point and
    random extras."""
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
        hnsw._augment_reverse(nbrs[lvl], members, pruned, m_l)
        if lvl == 0:
            hnsw._repair_connectivity(nbrs[0], vectors, store.metric, rng)

    return HNSWGraph(neighbors=jnp.asarray(nbrs, jnp.int32),
                     node_level=jnp.asarray(levels, jnp.int32),
                     entry_point=jnp.asarray(entry, jnp.int32), m=m)


def build_incremental(store: VectorStore, m: int = 16,
                      ef_construction: int = 64, seed: int = 0) -> HNSWGraph:
    """Classic HNSW inserts, one row at a time — small N only."""
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
