"""The HNSW build's candidate kNN on the device against the host recipe
(`hnsw_host_recipe`): `_knn_among` for exact levels, `_knn_routed` for
routed ones, then the random long-range extras and the stable sort of
both."""
import jax.numpy as jnp
import numpy as np
import pytest

import hnsw_host_recipe as recipe
from repro.core import VectorStore
from repro.core import hnsw

N, D, KC = 6000, 32, 40


@pytest.fixture(scope="module")
def clustered():
    rng = np.random.default_rng(3)
    centres = rng.standard_normal((24, D)).astype(np.float32) * 3
    x = centres[rng.integers(0, 24, N)] + rng.standard_normal((N, D))
    return x.astype(np.float32)


def _host_knn(vectors, members, metric, kc, rng, routed):
    """The host recipe: `_knn_routed` or `_knn_among`, then the extras."""
    mv = vectors[members]
    n = len(members)
    kc = min(kc, n - 1)
    if routed:
        ids, dst = recipe._knn_routed(mv, metric, kc, rng)
    else:
        ids, dst = recipe._knn_among(mv, metric, kc)
    n_rand = min(8, n - 1)
    rnd = rng.randint(0, n, size=(n, n_rand)).astype(np.int64)
    rnd = np.where(rnd == np.arange(n)[:, None], (rnd + 1) % n, rnd)
    ids = np.concatenate([ids, rnd], 1)
    dst = np.concatenate([dst, recipe._rows_dist(mv, rnd, metric)], 1)
    order = np.argsort(dst, axis=1, kind="stable")
    return (np.take_along_axis(ids, order, 1),
            np.take_along_axis(dst, order, 1))


def _on_host(ids, dst, n, kc):
    """A level's device candidates as the host recipe returns them: the
    first n rows, min(kc, n - 1) + min(8, n - 1) wide; the rest of the
    padded output holds only -1 / inf."""
    ids, dst = np.asarray(ids), np.asarray(dst)
    assert ids.shape == dst.shape and ids.shape[1] == kc + 8
    assert ids.shape[0] % hnsw._ROW_CLASS == 0
    w = min(kc, n - 1) + min(8, n - 1)
    rest = np.ones(ids.shape, bool)
    rest[:n, :w] = False
    assert (ids[rest] == -1).all() and np.isinf(dst[rest]).all()
    return ids[:n, :w].astype(np.int64), dst[:n, :w]


def _on_device(ids, dst, P, kc):
    """Host candidates padded to the device's (P, kc + 8) shape."""
    n, w = ids.shape
    out_i = np.full((P, kc + 8), -1, np.int32)
    out_d = np.full((P, kc + 8), np.inf, np.float32)
    out_i[:n, :w], out_d[:n, :w] = ids, dst
    return jnp.asarray(out_i), jnp.asarray(out_d)


def _rng_state(rng):
    _, keys, pos, gauss, cached = rng.get_state()
    return keys.tolist(), pos, gauss, cached


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("routed", [False, True], ids=["exact", "routed"])
def test_device_candidates_match_the_host(clustered, metric, routed):
    members = np.arange(N)
    host_rng, dev_rng = np.random.RandomState(5), np.random.RandomState(5)
    want_i, want_d = _host_knn(clustered, members, metric, KC, host_rng,
                               routed)
    got_i, got_d, counters = hnsw._knn_device(
        hnsw._upload_rows(clustered), members, metric, KC, dev_rng, routed)
    got_i, got_d = _on_host(got_i, got_d, N, KC)
    assert _rng_state(dev_rng) == _rng_state(host_rng)
    assert got_i.shape == want_i.shape == (N, KC + 8)
    assert got_d.dtype == np.float32
    assert not (got_i == members[:, None]).any()          # never self
    real = (got_i >= 0)[:, :, None]
    same = (got_i[:, :, None] == want_i[:, None, :]) & real
    assert same.any(2).sum() >= 0.995 * real.sum()
    # distances of the ids both hold, against the scale of f32 rounding
    # in |x|^2 + |y|^2 - 2 x.y
    sq = (clustered * clustered).sum(1)
    held = same.any(2)
    want_at = np.take_along_axis(want_d, same.argmax(2), 1)
    gap = np.abs(got_d - want_at) / (sq[:, None] + sq[np.maximum(got_i, 0)])
    assert gap[held].max() <= 1e-5
    # short rows: -1 ids with inf distances, trailing, where the host has them
    pad = got_i < 0
    assert (pad == np.isinf(got_d)).all()
    assert (np.sort(pad, axis=1) == pad).all()
    assert (pad.sum(1) == (want_i < 0).sum(1)).all()
    if routed:
        assert pad.any()
    assert counters["tiles"] > 0 and 0.0 <= counters["pad_share"] < 1.0


def test_device_candidates_of_a_small_level(clustered):
    """A level with fewer members than kc: every other member, then the
    extras, as `_knn_among` with kc clipped to n - 1."""
    rows = np.arange(0, 5 * 13, 5)                   # 13 members of N rows
    vecs = hnsw._upload_rows(clustered)
    want = _host_knn(clustered, rows, "l2", KC, np.random.RandomState(1),
                     False)
    got_i, got_d, _ = hnsw._knn_device(vecs, rows, "l2", KC,
                                       np.random.RandomState(1), False)
    got_i, got_d = _on_host(got_i, got_d, 13, KC)
    assert got_i.shape == (13, 12 + 8)
    assert (np.sort(got_i, 1) == np.sort(want[0], 1)).all()
    np.testing.assert_allclose(got_d, want[1], rtol=1e-5, atol=1e-4)


def test_blocked_build_matches_the_host_recipe(clustered, monkeypatch):
    """The whole routed build: the graph from device candidates against the
    graph from the host recipe, the same rng stream through both."""
    store = VectorStore.build(clustered, metric="l2")
    monkeypatch.setattr(hnsw, "_EXACT_THRESHOLD", 500)

    def build():
        return np.asarray(hnsw.build_graph(
            store, m=12, ef_construction=32, seed=0).neighbors)

    got = build()
    monkeypatch.setattr(
        hnsw, "_knn_device",
        lambda vecs, members, metric, kc, rng, routed: _on_device(
            *_host_knn(clustered, members, metric, kc, rng, routed),
            vecs.shape[0], kc) + ({},))
    want = build()
    assert got.shape == want.shape
    assert (got == want).mean() >= 0.99
