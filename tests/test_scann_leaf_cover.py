"""A two-level ScaNN query opens `num_leaves_to_search` distinct leaves,
however unevenly the branches split the leaves.

The tree search opens a query's nearest branches and scans the nearest
leaves among theirs.  When the branches it opened held fewer leaves than
it scans, the padded branch slots used to stand for leaf 0: leaf 0 was
scanned more than once and its rows came back repeated in one answer.
Here every branch but one is moved far from the queries and the near one
holds a single leaf, so each query's first branch holds fewer leaves than
it scans.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SearchParams, WorkloadSpec, build_scann,
                        generate_bitmaps, probe_bitmap)
from repro.core.scann import scann_search_batch, scann_search_batch_vmapped
from repro.data import DatasetSpec, make_dataset

NL = 4          # leaves scanned; with 8 branches over 64 leaves the tree
                # opens one branch (ceil(4 * 2 * 8 / 64)) before it looks
                # at how many leaves that branch holds


@pytest.fixture(scope="module")
def uneven():
    store, _ = make_dataset(DatasetSpec("t-cover", 4000, 32, "l2",
                                        clusters=8), num_queries=1, seed=3)
    idx = build_scann(store, num_leaves=64, levels=2, seed=0)
    B, Lb = idx.branch_leaves.shape
    cent = np.asarray(idx.leaf_centroids)
    # queries at leaf 0's rows, so that leaf 0 would win every repeat
    rows0 = np.asarray(idx.leaf_rowids)[0]
    queries = jnp.asarray(np.asarray(store.vectors)[rows0[rows0 >= 0][:6]])
    far = 1e3 * np.random.RandomState(1).randn(B, cent.shape[1])
    lone = 1 + int(np.argmax(np.linalg.norm(cent[1:] - cent[0], axis=1)))
    others = np.array([leaf for leaf in range(64) if leaf != lone])
    leaves = np.full((B, Lb), -1, np.int64)
    leaves[0, 0] = lone
    for b in range(1, B):
        part = others[b - 1::B - 1]
        leaves[b, :len(part)] = part
    far[0] = np.asarray(queries).mean(0)      # the one near branch
    idx = dataclasses.replace(
        idx, branch_centroids=jnp.asarray(far, jnp.float32),
        branch_leaves=jnp.asarray(leaves, jnp.int32))
    bm = generate_bitmaps(store, queries, WorkloadSpec(0.5, "none"), seed=4)
    return store, queries, bm, idx


def _check_answers(store, bm, ids):
    ids = np.asarray(ids)
    for q, row in enumerate(ids):
        got = row[row >= 0]
        assert len(got) == len(row), (q, row)         # ≥ k rows pass
        assert len(set(got.tolist())) == len(got), (q, row)
    ok = jax.vmap(probe_bitmap)(bm, jnp.asarray(np.maximum(ids, 0)))
    assert np.asarray(ok).all()


def test_batched_opens_distinct_leaves(uneven):
    store, queries, bm, idx = uneven
    p = SearchParams(k=10, num_leaves_to_search=NL, reorder_factor=4)
    _, ids, stats, trace = scann_search_batch(idx, store, queries, bm, p,
                                              collect_trace=True)
    for q, row in enumerate(np.asarray(trace["leaves"])):
        assert len(set(row.tolist())) == NL, (q, row)
    assert (np.asarray(stats.hops) == NL).all()
    _check_answers(store, bm, ids)


def test_vmapped_answers_repeat_no_id(uneven):
    store, queries, bm, idx = uneven
    p = SearchParams(k=10, num_leaves_to_search=NL, reorder_factor=4)
    _, ids, _ = scann_search_batch_vmapped(idx, store, queries, bm, p)
    _check_answers(store, bm, ids)


def test_both_pipelines_agree(uneven):
    store, queries, bm, idx = uneven
    p = SearchParams(k=10, num_leaves_to_search=NL, reorder_factor=4)
    d0, i0, s0 = scann_search_batch(idx, store, queries, bm, p)
    d1, i1, s1 = scann_search_batch_vmapped(idx, store, queries, bm, p)
    assert np.array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=1e-5,
                               atol=1e-5)
    # both charge B branch centroids and Lb slots per opened branch
    assert np.array_equal(np.asarray(s0.distance_comps
                                     - s0.reorder_rows),
                          np.asarray(s1.distance_comps - s1.reorder_rows))
