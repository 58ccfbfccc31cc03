"""The HNSW build's neighbour selection on the device against the host
recipe (`hnsw_host_recipe`): `_diversity_prune` for the prune and
`_augment_reverse_blocked` for the reverse-edge fill of routed levels."""
import jax.numpy as jnp
import numpy as np
import pytest

import hnsw_host_recipe as recipe
from repro.core import VectorStore
from repro.core import hnsw

N, D, KC, M = 6000, 32, 40, 24


@pytest.fixture(scope="module")
def clustered():
    rng = np.random.default_rng(3)
    centres = rng.standard_normal((24, D)).astype(np.float32) * 3
    x = centres[rng.integers(0, 24, N)] + rng.standard_normal((N, D))
    return x.astype(np.float32)


def _candidates(vectors, members, metric, routed):
    """A level's device candidates, and the same trimmed to what the host
    recipe hands `_diversity_prune`: n rows, min(kc, n - 1) + min(8, n - 1)
    wide."""
    vecs = hnsw._upload_rows(vectors)
    ids, dst, _ = hnsw._knn_device(vecs, members, metric, KC,
                                   np.random.RandomState(5), routed)
    n = len(members)
    w = min(KC, n - 1) + min(8, n - 1)
    return (vecs, ids, dst), (np.asarray(ids)[:n, :w].astype(np.int64),
                              np.asarray(dst)[:n, :w])


def _host_prune(vectors):
    """`_prune_device` by the host twin, on the candidates pulled back."""
    def prune(vecs, members, cand, cand_d, m, metric):
        n = len(members)
        w = min(cand.shape[1] - 8, n - 1) + min(8, n - 1)
        out = np.full((vecs.shape[0], m), -1, np.int32)
        out[:n] = recipe._diversity_prune(
            vectors[members], np.asarray(cand)[:n, :w].astype(np.int64),
            np.asarray(cand_d)[:n, :w], m, metric)
        return jnp.asarray(out), {"kept_share": 0.0, "fill_share": 0.0}
    return prune


def _host_reverse(members, pruned):
    """`_reverse_device` by the host twin: forward edges in global ids,
    then `_augment_reverse_blocked` on a table of the store's rows."""
    m = pruned.shape[1]
    local = np.asarray(pruned)[:len(members)]
    fwd = np.where(local >= 0, members[np.maximum(local, 0)], -1)
    level = np.full((members.max() + 1, m), -1, np.int64)
    level[members] = fwd
    recipe._augment_reverse_blocked(level, members, fwd, m)
    edges = int((fwd >= 0).sum())
    return level[members], int((level[members] >= 0).sum()) - edges, edges


@pytest.mark.parametrize("metric", ["l2", "ip", "cos"])
def test_device_prune_matches_the_host(clustered, metric):
    members = np.arange(N)
    (vecs, cand, cand_d), (ids, dst) = _candidates(clustered, members,
                                                   metric, True)
    got, shares = hnsw._prune_device(vecs, members, cand, cand_d, M, metric)
    got = np.asarray(got)
    assert got.shape == (vecs.shape[0], M) and (got[N:] == -1).all()
    got = got[:N]
    want = recipe._diversity_prune(clustered, ids, dst, M, metric)
    assert (got == want).mean() >= 0.995
    # each row's ids are its own candidates, then -1 padding
    real = got >= 0
    assert (np.sort(~real, axis=1) == ~real).all()
    assert ((got[:, :, None] == ids[:, None, :]).any(2) | ~real).all()
    assert 0.0 < shares["kept_share"] <= 1.0
    assert 0.0 <= shares["fill_share"] <= 1.0
    assert shares["kept_share"] + shares["fill_share"] == pytest.approx(
        real.mean())


def test_device_prune_of_a_small_level(clustered):
    """A level with fewer members than kc: every row's candidates are all
    the other members, the extras, then -1 / inf padding."""
    members = np.arange(0, 5 * 13, 5)              # 13 members of N rows
    (vecs, cand, cand_d), (ids, dst) = _candidates(clustered, members,
                                                   "l2", False)
    assert (np.asarray(cand)[:13, 12 + 8:] == -1).all()
    got, shares = hnsw._prune_device(vecs, members, cand, cand_d, M, "l2")
    want = recipe._diversity_prune(clustered[members], ids, dst, M, "l2")
    np.testing.assert_array_equal(np.asarray(got)[:13], want)
    assert (np.asarray(got)[13:] == -1).all()
    # 12 others, so at most 12 of the 24 slots fill
    assert shares["kept_share"] + shares["fill_share"] == pytest.approx(
        (want >= 0).sum() / (13 * M))
    assert (want >= 0).sum(1).max() <= 12


@pytest.mark.parametrize("m", [16, 32])
def test_device_reverse_fill_matches_the_host(m):
    """Forward edges skewed onto a few hubs, so their free slots overflow;
    members a strided subset of the rows, so local and global ids
    differ; rows from full to empty, so a node takes up to m reverse
    edges."""
    rng = np.random.default_rng(11)
    members = np.arange(1, 3 * 3000, 3)
    n, P = len(members), 4096
    pruned = np.full((P, m), -1, np.int32)
    width = rng.integers(0, m + 1, n)
    hubs = rng.integers(0, n, 12)
    for i in range(n):
        pool = np.setdiff1d(np.concatenate(
            [hubs, rng.integers(0, n, 3 * m)]), [i])
        pruned[i, :width[i]] = rng.permutation(pool)[:width[i]]
    pruned_dev = jnp.asarray(pruned)
    got, placed, edges = hnsw._reverse_device(members, pruned_dev)
    want, want_placed, want_edges = _host_reverse(members, pruned_dev)
    np.testing.assert_array_equal(got, want)
    assert (placed, edges) == (want_placed, want_edges)
    assert edges == (pruned >= 0).sum() and 0 < placed < edges
    assert (got[hubs] >= 0).all()                  # the hubs overflowed


def test_blocked_build_matches_the_host_twins(clustered, monkeypatch):
    """The whole routed build with the device prune and reverse fill
    against the build with their host twins, the same rng stream through
    both."""
    store = VectorStore.build(clustered, metric="l2")
    monkeypatch.setattr(hnsw, "_EXACT_THRESHOLD", 500)

    def build():
        return np.asarray(hnsw.build_graph(
            store, m=12, ef_construction=32, seed=0).neighbors)

    got = build()
    monkeypatch.setattr(hnsw, "_prune_device", _host_prune(clustered))
    monkeypatch.setattr(hnsw, "_reverse_device", _host_reverse)
    want = build()
    assert got.shape == want.shape
    assert (got == want).mean() >= 0.99
