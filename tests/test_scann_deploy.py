"""The sift10m ScaNN deployment at a small size: the executor's answers
against exact filtered search, the on-device build against the host
recipe it replaced, and the build's spans.

The deployment (fvsbench/configs/sift10m-scann.json): 128-d L2 rows,
about sqrt(n) leaves on one level, SQ8 leaf tiles, 32 leaves scanned,
reorder factor 4, query blocks of 16.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import (SearchParams, WorkloadSpec, build_scann,
                        filtered_knn, generate_bitmaps, make_executor,
                        probe_bitmap)
from repro.core.scann import _row_norms_sq, kmeans
from repro.core.types import sq8_quantize
from repro.data import DatasetSpec, make_dataset

ROWS, LEAVES, K = 20000, 141, 10
PARAMS = SearchParams(k=K, num_leaves_to_search=32, reorder_factor=4,
                      scann_query_block=16)


@pytest.fixture(scope="module")
def deployment():
    store, queries = make_dataset(
        DatasetSpec("t-sift10m-scann", ROWS, 128, "l2", clusters=128),
        num_queries=64, seed=7)
    index = build_scann(store, num_leaves=LEAVES, levels=1, seed=0)
    return store, jnp.asarray(queries), index


# Recall floors, 0.1 under the readings with these seeds (1.0, 1.0 and
# 0.806): 32 of 141 leaves hold about 23% of the rows, and the rarer the
# passing rows, the more of a query's filtered top-10 lies in leaves it
# does not open (inline filtering).
@pytest.mark.parametrize("selectivity,correlation,floor", [
    (0.5, "high_pos", 0.9), (0.1, "none", 0.9), (0.01, "none", 0.7)])
def test_executor_against_exact_search(deployment, selectivity,
                                       correlation, floor):
    store, queries, index = deployment
    bm = generate_bitmaps(store, queries,
                          WorkloadSpec(selectivity, correlation), seed=11)
    res = make_executor("scann", store, index=index).search(queries, bm,
                                                            PARAMS)
    ids, dists = np.asarray(res.ids), np.asarray(res.dists)
    valid = ids >= 0
    ok = np.asarray(jax.vmap(probe_bitmap)(bm, jnp.asarray(
        np.maximum(ids, 0))))
    assert ok[valid].all()
    for row in ids:
        assert len(set(row[row >= 0].tolist())) == int((row >= 0).sum())
    passing = np.asarray(jax.vmap(lambda b: probe_bitmap(
        b, jnp.arange(store.n)))(bm)).sum(axis=1)
    assert (valid.sum(axis=1) >= np.minimum(K, passing)).all()
    # each returned distance is the id's exact f32 distance, to the
    # rounding of an f32 expansion of |q - x|^2
    x = np.asarray(store.vectors, np.float64)[np.maximum(ids, 0)]
    q = np.asarray(queries, np.float64)[:, None, :]
    scale = np.sum(q * q, -1) + np.sum(x * x, -1)
    exact = np.sum((q - x) ** 2, -1)
    assert (np.abs(dists - exact)[valid] <= 1e-5 * scale[valid]).all()
    _, truth = filtered_knn(store, queries, bm, K)
    truth = np.asarray(truth)
    hits = [len(set(a[a >= 0]) & set(t[t >= 0])) / max((t >= 0).sum(), 1)
            for a, t in zip(ids, truth)]
    assert np.mean(hits) >= floor, np.mean(hits)


@partial(jax.jit, static_argnames=("n",))
def _host_nearest(xb, cent, n):
    cn = jnp.sum(cent * cent, axis=1)

    def block(x):
        ip = jnp.matmul(x, cent.T, precision=jax.lax.Precision.HIGHEST)
        d = jnp.sum(x * x, axis=1, keepdims=True) + cn[None, :] - 2.0 * ip
        return jnp.argmin(d, axis=1).astype(jnp.int32)

    return jax.lax.map(block, xb).reshape(-1)[:n]


def host_kmeans(x, k, iters=12, seed=0, block=8192):
    """The recipe the device k-means replaced: assignment on the device
    over a padded copy of the rows, centroid sums on the host in row
    order."""
    n, d = x.shape
    rng = np.random.RandomState(seed)
    cent = x[rng.choice(n, size=k, replace=False)].copy()
    block = min(block, n)
    xb = jnp.asarray(np.pad(x, ((0, (-n) % block), (0, 0)))
                     .reshape(-1, block, d), jnp.float32)
    for _ in range(iters):
        assign = np.asarray(_host_nearest(
            xb, jnp.asarray(cent, jnp.float32), n), np.int64)
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
        if empty.any():
            far = rng.choice(n, size=int(empty.sum()), replace=False)
            cent[empty] = x[far]
    return cent.astype(np.float32), assign


def host_members(assign, k):
    cnt = np.bincount(assign, minlength=k)
    out = np.full((k, cnt.max()), -1, np.int64)
    order = np.argsort(assign, kind="stable")
    starts = np.cumsum(cnt) - cnt
    out[assign[order], np.arange(len(assign)) - starts[assign[order]]] = order
    return out


@pytest.fixture(scope="module")
def small_store():
    store, _ = make_dataset(DatasetSpec("t-kmeans", 3000, 24, "l2",
                                        clusters=12), num_queries=1, seed=5)
    return store


# f32 sums of at most 3,000 rows of norm about 1, accumulated in another
# order than the host's, differ from its f64 sums by well under 1e-5
ATOL = 1e-5


# 8192 rows: one block; 1024: three, the last overlapping the second
@pytest.mark.parametrize("block", [8192, 1024])
def test_kmeans_reproduces_the_host_recipe(small_store, block):
    x = np.asarray(small_store.vectors)
    want_c, want_a = host_kmeans(x, 40, seed=3)
    cent, assign, counts = kmeans(small_store.vectors, 40, seed=3,
                                  block=block)
    assert np.array_equal(np.asarray(assign), want_a)
    assert np.array_equal(counts, np.bincount(want_a, minlength=40))
    np.testing.assert_allclose(cent, want_c, rtol=0, atol=ATOL)


@pytest.mark.parametrize("levels", [1, 2])
def test_build_reproduces_the_host_layout(small_store, levels):
    idx = build_scann(small_store, num_leaves=32, levels=levels, seed=2)
    x = np.asarray(small_store.vectors)
    cent, assign = host_kmeans(x, 32, seed=2)
    rowids = host_members(assign, 32)
    cap = rowids.shape[1] + (-rowids.shape[1]) % 8
    rowids = np.pad(rowids, ((0, 0), (0, cap - rowids.shape[1])),
                    constant_values=-1)
    assert np.array_equal(np.asarray(idx.leaf_rowids), rowids)
    np.testing.assert_allclose(np.asarray(idx.leaf_centroids), cent,
                               rtol=0, atol=ATOL)
    q, scale, mean = sq8_quantize(x)
    tiles = np.where((rowids >= 0)[..., None], q[np.maximum(rowids, 0)], 0)
    assert np.array_equal(np.asarray(idx.leaf_tiles), tiles)
    assert np.array_equal(np.asarray(idx.scale), scale)
    assert np.array_equal(np.asarray(idx.mean), mean)
    assert np.array_equal(np.asarray(idx.row_norms_sq), np.asarray(
        _row_norms_sq(jnp.asarray(tiles), jnp.asarray(scale),
                      jnp.asarray(mean))))
    if levels == 2:
        _, bassign = host_kmeans(np.asarray(idx.leaf_centroids), 5, seed=3)
        assert np.array_equal(np.asarray(idx.branch_leaves),
                              host_members(bassign, 5))


def test_build_spans_cover_the_build(small_store):
    with obs.record() as rec:
        build_scann(small_store, num_leaves=32, levels=2, seed=1,
                    kmeans_iters=4)
    (build,) = [i for i, s in enumerate(rec.spans) if s.name == "scann.build"]
    kids = [s for s in rec.spans if s.parent == build]
    assert [s.name for s in kids] == ["scann.kmeans", "scann.kmeans",
                                      "scann.pack", "scann.upload"]
    assert [s.args for s in kids[:2]] == [
        {"rows": 3000, "leaves": 32, "iters": 4},
        {"rows": 32, "leaves": 5, "iters": 4}]
    covered = sum(s.end_ns - s.start_ns for s in kids) * 1e-9
    assert covered >= 0.95 * rec.total_seconds("scann.build")


def test_build_leaves_the_table_on_the_device(small_store, monkeypatch):
    """The build reads the table where it lies: no copy of it comes to
    the host."""
    table = small_store.vectors.shape
    cls = type(small_store.vectors)
    to_host = cls.__array__

    def guarded(self, *args, **kwargs):
        assert self.shape != table, "the table was fetched to the host"
        return to_host(self, *args, **kwargs)

    monkeypatch.setattr(cls, "__array__", guarded)
    idx = build_scann(small_store, num_leaves=16, levels=1, seed=0)
    assert int((np.asarray(idx.leaf_rowids) >= 0).sum()) == table[0]


def test_result_keeps_no_batch(deployment):
    """A kept result holds the plan's decisions, not the batch's queries
    and filter bitmaps (1.25 MB a query at the deployment's 10M rows)."""
    store, queries, index = deployment
    bm = generate_bitmaps(store, queries[:4], WorkloadSpec(0.1, "none"),
                          seed=2)
    res = make_executor("scann", store, index=index).search(
        queries[:4], bm, PARAMS)
    assert res.plan.queries is None and res.plan.bitmaps is None
    assert res.plan.params.scann_query_block == PARAMS.scann_query_block
