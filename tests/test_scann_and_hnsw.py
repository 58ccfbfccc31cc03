"""ScaNN index + HNSW construction invariants."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components

from hnsw_host_recipe import build_incremental
from repro.core import (SearchParams, VectorStore, WorkloadSpec, build_graph,
                        build_scann, filtered_knn, knn,
                        generate_bitmaps, recall_at_k, scann_search_batch,
                        search_batch, stats_table_row)
from repro.core.scann import project_query
from repro.data import DatasetSpec, make_dataset


def _recall(ids, tid, k=10):
    return float(np.mean(np.asarray(
        jax.vmap(lambda f, t: recall_at_k(f, t, k))(ids, tid))))


# ---------------- HNSW construction ----------------

def test_graph_invariants(small_dataset, small_graph):
    store, _ = small_dataset
    nb = np.asarray(small_graph.neighbors)
    n = store.n
    assert (nb < n).all()
    # no self edges at level 0
    self_edges = nb[0][np.arange(n)] == np.arange(n)[:, None]
    assert not self_edges.any()
    # base layer is a single component (repair pass)
    ok = nb[0] >= 0
    src = np.repeat(np.arange(n), nb.shape[2])
    edges = coo_matrix((np.ones(int(ok.sum())), (src[ok.ravel()],
                                                 nb[0][ok])), shape=(n, n))
    assert connected_components(edges, directed=False)[0] == 1
    # entry point has max level
    lv = np.asarray(small_graph.node_level)
    assert lv[int(small_graph.entry_point)] == lv.max()


def test_incremental_builder_recall():
    spec = DatasetSpec("t-inc", 600, 24, "l2", clusters=8)
    store, queries = make_dataset(spec, num_queries=5, seed=1)
    g = build_incremental(store, m=8, ef_construction=40, seed=0)
    _, tid = knn(store, jnp.asarray(queries), 5)
    words = (store.n + 31) // 32
    full = jnp.ones((5, words), jnp.uint32) * jnp.uint32(0xFFFFFFFF)
    p = SearchParams(k=5, ef_search=64, beam_width=256,
                     strategy="unfiltered")
    _, ids, _ = search_batch(g, store, jnp.asarray(queries), full, p)
    assert _recall(ids, tid, 5) >= 0.9


# ---------------- ScaNN ----------------

@pytest.fixture(scope="module")
def scann_setup(small_dataset):
    store, queries = small_dataset
    idx = build_scann(store, num_leaves=64, levels=2, seed=0)
    return store, queries, idx


def test_scann_leaf_partition(scann_setup):
    store, _, idx = scann_setup
    rid = np.asarray(idx.leaf_rowids)
    valid = rid[rid >= 0]
    assert len(valid) == store.n            # every row in exactly one leaf
    assert len(np.unique(valid)) == store.n


def test_scann_filtered_recall(scann_setup):
    store, queries, idx = scann_setup
    for sel in (0.1, 0.5):
        bm = generate_bitmaps(store, queries, WorkloadSpec(sel, "none"),
                              seed=1)
        _, tid = filtered_knn(store, queries, bm, 10)
        p = SearchParams(k=10, num_leaves_to_search=32, reorder_factor=4)
        _, ids, stats = scann_search_batch(idx, store, queries, bm, p)
        assert _recall(ids, tid) >= 0.9, sel
        row = stats_table_row(stats)
        assert row["hops"] == 32            # leaves scanned
        assert row["reorder_rows"] > 0


def test_scann_results_pass_filter(scann_setup):
    from repro.core import probe_bitmap
    store, queries, idx = scann_setup
    bm = generate_bitmaps(store, queries, WorkloadSpec(0.05, "none"), seed=2)
    p = SearchParams(k=10, num_leaves_to_search=32)
    _, ids, _ = scann_search_batch(idx, store, queries, bm, p)
    ok = jax.vmap(probe_bitmap)(bm, jnp.maximum(ids, 0))
    valid = np.asarray(ids) >= 0
    assert np.asarray(ok)[valid].all()


def test_scann_quantization_error_bounded(scann_setup):
    """SQ8 reconstruction error ≤ scale/2 per dim (affine quantizer)."""
    store, _, idx = scann_setup
    rid = np.asarray(idx.leaf_rowids)
    tiles = np.asarray(idx.leaf_tiles, np.float32)
    scale = np.asarray(idx.scale)
    mean = np.asarray(idx.mean)
    recon = tiles * scale + mean
    mask = rid >= 0
    orig = np.asarray(store.vectors)[rid[mask]]
    err = np.abs(recon[mask] - orig)
    assert (err <= scale[None, :] * 0.51 + 1e-5).all()


def test_scann_pca_path():
    spec = DatasetSpec("t-pca", 2000, 96, "ip", clusters=8)
    store, queries = make_dataset(spec, num_queries=4, seed=2)
    idx = build_scann(store, num_leaves=32, levels=1, pca_dims=24, seed=0)
    assert idx.leaf_tiles.shape[-1] == 24
    q = jnp.asarray(queries)
    qp = project_query(idx, q[0])
    assert qp.shape == (24,)
    words = (store.n + 31) // 32
    bm = jnp.ones((4, words), jnp.uint32) * jnp.uint32(0xFFFFFFFF)
    _, tid = knn(store, q, 10)
    p = SearchParams(k=10, num_leaves_to_search=16, reorder_factor=10)
    _, ids, _ = scann_search_batch(idx, store, q, bm, p)
    assert _recall(ids, tid) >= 0.8    # PCA 96->24 is lossy; reorder saves it


def test_scann_pallas_path_matches_ref(scann_setup):
    store, queries, idx = scann_setup
    bm = generate_bitmaps(store, queries[:2], WorkloadSpec(0.3, "none"),
                          seed=3)
    p = SearchParams(k=10, num_leaves_to_search=16)
    d1, i1, _ = scann_search_batch(idx, store, queries[:2], bm, p,
                                   use_pallas=False)
    d2, i2, _ = scann_search_batch(idx, store, queries[:2], bm, p,
                                   use_pallas=True)
    assert (np.asarray(i1) == np.asarray(i2)).all()
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-4)
