"""Leaf-scan kernel coverage (no hypothesis): Pallas-vs-ref equivalence for
the single and batched variants, padding edges, all-filtered bitmaps,
top-k sentinels, and batched-pipeline-vs-vmapped ScaNN equivalence."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (SearchParams, WorkloadSpec, generate_bitmaps,
                        scann_search_batch, scann_search_batch_vmapped)
from repro.core.types import merge_topk, pack_bool_bitmap
from repro.kernels import ops, ref


def _leaf_case(nl, c, d, q=4, n_rows=1024, density=0.5, seed=0):
    rng = np.random.RandomState(seed)
    tiles = jnp.asarray(rng.randint(-127, 128, (nl, c, d)).astype(np.int8))
    rowids = rng.permutation(n_rows)[: nl * c].reshape(nl, c).astype(np.int32)
    rowids[rng.rand(nl, c) < 0.1] = -1
    scale = jnp.asarray(np.abs(rng.randn(d)).astype(np.float32) * 0.02)
    mean = jnp.asarray(rng.randn(d).astype(np.float32) * 0.05)
    bms = jnp.stack([pack_bool_bitmap(rng.rand(n_rows) < density)
                     for _ in range(q)])
    queries = jnp.asarray(rng.randn(q, d).astype(np.float32))
    return queries, tiles, jnp.asarray(rowids), scale, mean, bms


def _assert_scores_match(a, b, atol=2e-3, rtol=1e-3):
    fa, fb = np.isfinite(np.asarray(a)), np.isfinite(np.asarray(b))
    assert (fa == fb).all()
    np.testing.assert_allclose(np.asarray(a)[fa], np.asarray(b)[fb],
                               atol=atol, rtol=rtol)


# shape grid: scalar-ish, unaligned C and d, exactly-aligned tiles
SHAPES = [(1, 1, 1), (3, 17, 40), (2, 33, 130), (2, 128, 128)]


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("nl,c,d", SHAPES)
def test_leaf_scan_single_pallas_vs_ref(nl, c, d, metric):
    queries, tiles, rowids, scale, mean, bms = _leaf_case(nl, c, d, q=1)
    a = ops.leaf_scan(queries[0], tiles, rowids, scale, mean, bms[0],
                      metric, use_pallas=True)
    b = ref.leaf_scan_ref(queries[0], tiles, rowids, scale, mean, bms[0],
                          metric)
    _assert_scores_match(a, b)


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("nl,c,d", SHAPES)
def test_leaf_scan_batched_pallas_vs_ref(nl, c, d, metric):
    queries, tiles, rowids, scale, mean, bms = _leaf_case(nl, c, d, q=5)
    x = tiles.astype(jnp.float32) * scale + mean
    norms = jnp.sum(x * x, axis=-1)
    a = ops.leaf_scan_batched(queries, tiles, rowids, scale, mean, bms,
                              norms, metric, use_pallas=True)
    b = ref.leaf_scan_batched_ref(queries, tiles, rowids, scale, mean, bms,
                                  norms, metric)
    assert a.shape == (5, nl, c)
    _assert_scores_match(a, b)


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_leaf_scan_batched_matches_vmapped_single(metric):
    """The batched kernel must agree with vmap of the single-query kernel
    row for row — same scores, same +inf mask."""
    queries, tiles, rowids, scale, mean, bms = _leaf_case(3, 20, 48, q=6,
                                                          seed=7)
    x = tiles.astype(jnp.float32) * scale + mean
    norms = jnp.sum(x * x, axis=-1)
    for use_pallas in (False, True):
        a = ops.leaf_scan_batched(queries, tiles, rowids, scale, mean, bms,
                                  norms, metric, use_pallas=use_pallas)
        b = jax.vmap(lambda q, bm: ops.leaf_scan(
            q, tiles, rowids, scale, mean, bm, metric,
            use_pallas=use_pallas))(queries, bms)
        _assert_scores_match(a, b)


def test_leaf_scan_batched_all_filtered():
    """Fully-failing filters -> all +inf for every query in the batch."""
    rng = np.random.RandomState(0)
    tiles = jnp.asarray(rng.randint(-127, 128, (2, 8, 16)).astype(np.int8))
    rowids = jnp.asarray(np.arange(16).reshape(2, 8).astype(np.int32))
    bms = jnp.stack([pack_bool_bitmap(np.zeros(64, bool))] * 3)
    norms = jnp.zeros((2, 8), jnp.float32)
    for use_pallas in (False, True):
        out = ops.leaf_scan_batched(
            jnp.ones((3, 16)), tiles, rowids, jnp.ones((16,)),
            jnp.zeros((16,)), bms, norms, "l2", use_pallas=use_pallas)
        assert not np.isfinite(np.asarray(out)).any()


def test_leaf_scan_batched_mixed_filters():
    """Each query sees its own bitmap: query 0 passes everything, query 1
    nothing — in the same batched call."""
    rng = np.random.RandomState(1)
    tiles = jnp.asarray(rng.randint(-127, 128, (2, 8, 16)).astype(np.int8))
    rowids = jnp.asarray(np.arange(16).reshape(2, 8).astype(np.int32))
    bms = jnp.stack([pack_bool_bitmap(np.ones(64, bool)),
                     pack_bool_bitmap(np.zeros(64, bool))])
    norms = jnp.zeros((2, 8), jnp.float32)
    out = ops.leaf_scan_batched(jnp.ones((2, 16)), tiles, rowids,
                                jnp.ones((16,)), jnp.zeros((16,)), bms,
                                norms, "ip", use_pallas=True)
    out = np.asarray(out)
    assert np.isfinite(out[0]).all()
    assert not np.isfinite(out[1]).any()


def test_topk_k_greater_than_n():
    """k above the number of finite candidates yields the real entries
    plus (+inf, -1) padding (the top-k every executor's result uses)."""
    a = jnp.asarray([[3.0, 1.0, 2.0]], jnp.float32)
    b = jnp.full((1, 5), jnp.inf, jnp.float32)
    vals, idx = merge_topk(a, jnp.asarray([[30, 10, 20]], jnp.int32), b,
                           jnp.full((1, 5), -1, jnp.int32), 8)
    vals, idx = np.asarray(vals)[0], np.asarray(idx)[0]
    np.testing.assert_allclose(vals[:3], [1.0, 2.0, 3.0])
    assert (idx[:3] == [10, 20, 30]).all()
    assert np.isinf(vals[3:]).all()
    assert (idx[3:] == -1).all()


def test_topk_inf_sentinels():
    """+inf (the universal filtered marker) reports index -1 even where
    the input carried a real id; finite values keep theirs."""
    a = jnp.asarray([[np.inf, 1.0]], jnp.float32)
    b = jnp.asarray([[np.inf, -2.0]], jnp.float32)
    vals, idx = merge_topk(a, jnp.asarray([[0, 1]], jnp.int32), b,
                           jnp.asarray([[2, 3]], jnp.int32), 4)
    vals, idx = np.asarray(vals)[0], np.asarray(idx)[0]
    assert vals[0] == -2.0 and idx[0] == 3
    assert vals[1] == 1.0 and idx[1] == 1
    assert np.isposinf(vals[2:]).all()
    assert (idx[2:] == -1).all()


# ---------------- batched pipeline vs legacy vmapped path ----------------

@pytest.fixture(scope="module")
def scann_fixture(small_dataset):
    from repro.core import build_scann
    store, queries = small_dataset
    idx = build_scann(store, num_leaves=64, levels=2, seed=0)
    bm = generate_bitmaps(store, queries, WorkloadSpec(0.3, "none"), seed=3)
    return store, queries, idx, bm


@pytest.mark.parametrize("use_pallas", [False, True])
def test_scann_batched_matches_vmapped(scann_fixture, use_pallas):
    """Acceptance: ids, distances, and SearchStats identical to the
    pre-refactor vmapped path under per-query page accounting.  Final
    distances are bit-for-bit because the exact-rescore stage uses the
    same distance() formulation as the legacy path."""
    store, queries, idx, bm = scann_fixture
    p = SearchParams(k=10, num_leaves_to_search=16,
                     scann_page_accounting="per_query")
    d1, i1, s1 = scann_search_batch_vmapped(idx, store, queries, bm, p,
                                            use_pallas=use_pallas)
    d2, i2, s2 = scann_search_batch(idx, store, queries, bm, p,
                                    use_pallas=use_pallas)
    assert (np.asarray(i1) == np.asarray(i2)).all()
    assert (np.asarray(d1) == np.asarray(d2)).all()
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_scann_batch_page_accounting(scann_fixture):
    """Batch accounting totals unique opened leaves; per-query accounting
    totals nl per query.  Only the index-page counter may differ."""
    from repro.core.scann import _quant_pages_per_leaf
    store, queries, idx, bm = scann_fixture
    nl = 16
    kw = dict(k=10, num_leaves_to_search=nl)
    pb = SearchParams(**kw, scann_page_accounting="batch")
    pq = SearchParams(**kw, scann_page_accounting="per_query")
    db, ib, sb = scann_search_batch(idx, store, queries, bm, pb)
    dq, iq, sq = scann_search_batch(idx, store, queries, bm, pq)
    assert (np.asarray(ib) == np.asarray(iq)).all()
    qppl = _quant_pages_per_leaf(idx)
    per_query = np.asarray(sq.page_accesses_index)
    assert (per_query == nl * qppl).all()
    batch_total = int(np.asarray(sb.page_accesses_index).sum())
    assert batch_total <= per_query.sum()
    assert batch_total % qppl == 0
    assert nl * qppl <= batch_total          # at least one query's worth
    for f in ("distance_comps", "filter_checks", "hops",
              "page_accesses_heap", "reorder_rows"):
        assert (np.asarray(getattr(sb, f))
                == np.asarray(getattr(sq, f))).all()


def test_scann_row_norms_backcompat(scann_fixture):
    """An index without precomputed row_norms_sq (pre-field pickles) must
    produce identical results via the lazy fallback."""
    import dataclasses
    store, queries, idx, bm = scann_fixture
    assert idx.row_norms_sq is not None
    old = dataclasses.replace(idx, row_norms_sq=None)
    p = SearchParams(k=10, num_leaves_to_search=16)
    d1, i1, _ = scann_search_batch(idx, store, queries, bm, p)
    d2, i2, _ = scann_search_batch(old, store, queries, bm, p)
    assert (np.asarray(i1) == np.asarray(i2)).all()
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-5)
