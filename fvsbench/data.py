"""Vectors and predicates for a cell, made on the device from the seed.

Both generators are copies of the program's own (`src/repro/data/
datasets.py` for the vectors, `src/repro/core/workload.py` for the
predicates), kept here so that a change to the program cannot change the
yardstick.  The recipes are the same; the random streams are JAX's, and
every array is made on the device in a few jitted calls.

Vectors: a clustered Gaussian mixture, `clusters` unit-norm centres,
each row a centre plus N(0, spread^2 / dim) noise per dimension
(rows renormalised for the inner-product metric); queries are drawn the
same way.

Predicates (paper section 4): per query, a set of exactly
max(1, round(selectivity * n)) passing rows, packed into uint32 words as
the program takes them.  `none` draws the set uniformly.  The positive
correlations draw it by a Gumbel-top-k sample from the query's nearest
rows (pool: the closest third for `high_pos`, half for `med_pos`, all for
`low_pos`) with a rank-based softmax bias (the closest pool row e^4 times
likelier than the farthest); `negative` flips the ranking.  Where the set
is larger than the pool, the whole pool passes and the rest is drawn
uniformly from the other rows.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
POOL_FRAC = {"high_pos": 1.0 / 3.0, "med_pos": 0.5, "low_pos": 1.0,
             "negative": 1.0, "none": 1.0}
BETA = 4.0            # rank bias of the correlated kinds, as in the program
QUERY_BLOCK = 16      # queries per predicate-generation call


def key(seed: int, stream: int) -> jax.Array:
    """An independent stream of the run's seed (seeds may exceed 32 bits)."""
    return jax.random.fold_in(jax.random.key(seed), stream)


@partial(jax.jit, static_argnames=("n", "dim", "clusters", "spread",
                                   "metric", "num_queries"))
def make_vectors(k: jax.Array, n: int, dim: int, clusters: int,
                 spread: float, metric: str, num_queries: int):
    """(vectors (n, dim) f32, norms_sq (n,), queries (num_queries, dim))."""
    kc, ka, kx, kqa, kq = jax.random.split(k, 5)
    centers = jax.random.normal(kc, (clusters, dim), jnp.float32)
    centers = centers / jnp.linalg.norm(centers, axis=1, keepdims=True)

    def draw(ks, kn, m):
        a = jax.random.randint(ks, (m,), 0, clusters)
        x = centers[a] + spread * jax.random.normal(
            kn, (m, dim), jnp.float32) / np.sqrt(dim)
        if metric == "ip":
            x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
        return x

    x = draw(ka, kx, n)
    q = draw(kqa, kq, num_queries)
    return x, jnp.sum(x * x, axis=-1), q


def _distances(metric: str, q, x, xn):
    ip = jnp.matmul(q, x.T, precision=HIGHEST)
    if metric == "ip":
        return -ip
    return jnp.sum(q * q, -1)[:, None] + xn[None, :] - 2.0 * ip


def _top_n(keys, n_sel):
    """(Q, n) bool: each row's n_sel largest keys (no ties among finite
    keys), found bit by bit on the keys' order-preserving uint32 image
    (32 counting passes, no sort)."""
    u = jax.lax.bitcast_convert_type(keys, jnp.uint32)
    u = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))

    def bit(i, t):
        cand = t | jnp.left_shift(jnp.uint32(1), (31 - i).astype(jnp.uint32))
        enough = jnp.sum(u >= cand[:, None], axis=1) >= n_sel
        return jnp.where(enough, cand, t)

    t = jax.lax.fori_loop(0, 32, bit, jnp.zeros((keys.shape[0],), jnp.uint32))
    return u >= t[:, None]


@partial(jax.jit, static_argnames=("correlation", "metric"))
def _passing_words(k, vectors, norms_sq, queries, n_sel, correlation: str,
                   metric: str):
    """(Q, ceil(n/32)) uint32 bitmaps of one predicate kind, n_sel passing
    rows each.  The correlated kinds rank the rows by distance with one
    sort and bring the chosen flags back to row order with another."""
    qn, n = queries.shape[0], vectors.shape[0]
    g = jax.random.gumbel(k, (qn, n), jnp.float32)
    if correlation == "none":           # uniform: keys are already per row
        chosen = _top_n(g, n_sel)
    else:
        pool = jnp.minimum(n, jnp.maximum(
            n_sel, int(np.ceil(POOL_FRAC[correlation] * n))))
        iota = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (qn, n))
        _, order = jax.lax.sort_key_val(
            _distances(metric, queries, vectors, norms_sq), iota)
        rank = jnp.arange(n, dtype=jnp.float32)
        r = (pool - 1) - rank if correlation == "negative" else rank
        logit = -BETA * r / jnp.maximum(pool - 1, 1)
        in_pool = rank < pool
        keys = jnp.where(n_sel <= pool,
                         jnp.where(in_pool, logit + g, -jnp.inf),  # biased
                         jnp.where(in_pool, jnp.inf, g))  # pool + uniform
        _, chosen = jax.lax.sort_key_val(
            order, _top_n(keys, n_sel).astype(jnp.int32))
    pad = (-n) % 32
    bits = jnp.pad(chosen.astype(jnp.uint32),
                   ((0, 0), (0, pad))).reshape(qn, -1, 32)
    weights = jnp.left_shift(jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(bits * weights, axis=-1, dtype=jnp.uint32)


def make_bitmaps(k: jax.Array, vectors, norms_sq, queries, kinds,
                 metric: str) -> jax.Array:
    """Bitmaps for `queries`, laid out kind by kind: `kinds` is a list of
    (selectivity, correlation, count), and the first `count` queries get
    the first kind, and so on.  One call per block of QUERY_BLOCK queries;
    returns (Q, words) uint32 on the device."""
    n = vectors.shape[0]
    blocks, start = [], 0
    for j, (sel, corr, count) in enumerate(kinds):
        if corr not in POOL_FRAC:
            raise ValueError(f"unknown correlation {corr!r}")
        if not 0.0 < sel <= 1.0:
            raise ValueError(f"selectivity {sel} outside (0, 1]")
        n_sel = max(1, round(sel * n))
        for b in range(0, count, QUERY_BLOCK):
            m = min(QUERY_BLOCK, count - b)
            rows = start + b + np.minimum(np.arange(QUERY_BLOCK), m - 1)
            words = _passing_words(
                jax.random.fold_in(jax.random.fold_in(k, j), b),
                vectors, norms_sq, queries[jnp.asarray(rows)],
                jnp.int32(n_sel), corr, metric)
            blocks.append(words[:m])
        start += count
    return jnp.concatenate(blocks)
