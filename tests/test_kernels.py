"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # optional dev dep (requirements-dev.txt):
    # property tests skip individually; plain tests in this module still run
    def given(*a, **k):
        import pytest
        return lambda f: pytest.mark.skip(
            reason="hypothesis not installed")(f)

    def settings(*a, **k):
        return lambda f: f

    class st:  # stub strategies so decorator arguments still evaluate
        integers = floats = sampled_from = staticmethod(
            lambda *a, **k: None)

from repro.core.types import pack_bool_bitmap
from repro.kernels import ops, ref


@settings(max_examples=12, deadline=None)
@given(q=st.integers(1, 70), n=st.integers(1, 300),
       d=st.integers(1, 160), metric=st.sampled_from(["l2", "ip"]),
       seed=st.integers(0, 99))
def test_distance_matrix_sweep(q, n, d, metric, seed):
    rng = np.random.RandomState(seed)
    qs = jnp.asarray(rng.randn(q, d).astype(np.float32))
    xs = jnp.asarray(rng.randn(n, d).astype(np.float32))
    a = ops.distance_matrix(qs, xs, metric, use_pallas=True)
    b = ref.distance_matrix_ref(qs, xs, metric)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                               rtol=1e-4)


@settings(max_examples=10, deadline=None)
@given(nl=st.integers(1, 6), c=st.integers(1, 40), d=st.integers(1, 100),
       metric=st.sampled_from(["l2", "ip"]), density=st.floats(0.0, 1.0),
       seed=st.integers(0, 99))
def test_leaf_scan_sweep(nl, c, d, metric, density, seed):
    rng = np.random.RandomState(seed)
    n_rows = 1024
    tiles = jnp.asarray(rng.randint(-127, 128, (nl, c, d)).astype(np.int8))
    rowids = rng.permutation(n_rows)[: nl * c].reshape(nl, c).astype(
        np.int32)
    rowids[rng.rand(nl, c) < 0.1] = -1        # padding holes
    scale = jnp.asarray(np.abs(rng.randn(d)).astype(np.float32) * 0.02)
    mean = jnp.asarray(rng.randn(d).astype(np.float32) * 0.05)
    bm = pack_bool_bitmap(rng.rand(n_rows) < density)
    q = jnp.asarray(rng.randn(d).astype(np.float32))
    a = ops.leaf_scan(q, tiles, jnp.asarray(rowids), scale, mean, bm,
                      metric, use_pallas=True)
    b = ref.leaf_scan_ref(q, tiles, jnp.asarray(rowids), scale, mean, bm,
                          metric)
    fa, fb = np.isfinite(np.asarray(a)), np.isfinite(np.asarray(b))
    assert (fa == fb).all()
    np.testing.assert_allclose(np.asarray(a)[fa], np.asarray(b)[fb],
                               atol=2e-3, rtol=1e-3)


def test_leaf_scan_all_filtered():
    """Fully-failing filter -> all +inf (empty result is well-defined)."""
    rng = np.random.RandomState(0)
    tiles = jnp.asarray(rng.randint(-127, 128, (2, 8, 16)).astype(np.int8))
    rowids = jnp.asarray(np.arange(16).reshape(2, 8).astype(np.int32))
    bm = pack_bool_bitmap(np.zeros(64, bool))
    out = ops.leaf_scan(jnp.ones((16,)), tiles, rowids, jnp.ones((16,)),
                        jnp.zeros((16,)), bm, "l2", use_pallas=True)
    assert not np.isfinite(np.asarray(out)).any()
