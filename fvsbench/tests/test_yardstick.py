"""The data and predicate generators and the reference at tiny sizes."""
import jax.numpy as jnp
import numpy as np
import pytest

from fvsbench import check, data, reference


def tiny(seed=2**40 + 3, n=3000, dim=16, q=8):
    return data.make_vectors(data.key(seed, 0), n, dim, 8, 0.8, "l2", q)


def popcount(words):
    return np.bitwise_count(np.asarray(words)).sum(axis=1)


@pytest.mark.parametrize("sel,corr", [(0.01, "none"), (0.1, "high_pos"),
                                      (0.5, "high_pos"), (0.2, "negative")])
def test_bitmaps_pass_exactly_the_selected_share(sel, corr):
    x, xn, q = tiny()
    bm = data.make_bitmaps(data.key(1, 1), x, xn, q, [(sel, corr, 8)], "l2")
    assert bm.shape == (8, -(-3000 // 32))
    assert (popcount(bm) == max(1, round(sel * 3000))).all()


def test_correlation_orders_passing_rows_by_distance():
    x, xn, q = tiny()
    kinds = [(0.05, "high_pos", 8), (0.05, "none", 8), (0.05, "negative", 8)]
    bm = np.asarray(data.make_bitmaps(
        data.key(1, 1), x, xn, jnp.concatenate([q, q, q]), kinds, "l2"))
    d = np.asarray(reference._dist("l2", jnp.concatenate([q, q, q]), x, xn,
                                   "highest"))
    bits = np.unpackbits(bm.view(np.uint8), bitorder="little",
                         axis=1)[:, :3000].astype(bool)
    mean = [np.mean(d[i][bits[i]]) for i in range(24)]
    pos, none, neg = np.mean(mean[:8]), np.mean(mean[8:16]), np.mean(mean[16:])
    assert pos < none < neg


def test_same_seed_same_data():
    a, b = tiny(seed=7), tiny(seed=7)
    assert all(np.array_equal(u, v) for u, v in zip(a, b))
    assert not np.array_equal(tiny(seed=8)[0], a[0])


def test_reference_matches_numpy_brute_force():
    x, xn, q = tiny()
    bm = data.make_bitmaps(data.key(1, 1), x, xn, q, [(0.1, "none", 8)],
                           "l2")
    reference.ROW_BLOCK, old = 512, reference.ROW_BLOCK
    try:
        d, i = reference.filtered_topk(x, q, bm, 10, "l2")
    finally:
        reference.ROW_BLOCK = old
    xs, qs = np.asarray(x, np.float64), np.asarray(q, np.float64)
    full = ((qs[:, None, :] - xs[None, :, :]) ** 2).sum(-1)
    ok = check.bits_of(np.asarray(bm), np.arange(8),
                       np.tile(np.arange(3000), (8, 1)))
    want = np.argsort(np.where(ok, full, np.inf), axis=1)[:, :10]
    assert (np.sort(i, axis=1) == np.sort(want, axis=1)).all()
    assert np.allclose(d, np.take_along_axis(full, i, 1), rtol=1e-4,
                       atol=1e-5)
