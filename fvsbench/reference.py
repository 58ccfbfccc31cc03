"""The plain reference: exact filtered top-k by brute force.

Written here in `jax.numpy` float32, independent of the program under
test: it imports nothing of it and takes nothing it made.  Distances are
the L2 expansion ||q||^2 + ||x||^2 - 2 q.x (or -q.x for inner product),
with q.x at `precision="highest"`, computed over blocks of rows with a
running top-k, so a reference over 1M rows needs a few hundred MB.

`precision` also names the control's lower precisions.  They are
emulated explicitly, so a run on the CPU (where XLA ignores matmul
precision) gives the same numbers as the chip:

  "highest"  f32 products: the reference.
  "high"     three bf16 passes (hi.hi + hi.lo + lo.hi, as a TPU's
             `precision="high"` computes an f32 matmul): the control,
             the nearest precision below the reference's.
  "default"  one bf16 pass, a TPU's default f32 matmul.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 1 << 17
QUERY_BLOCK = 64


def _bf16(x):
    """x rounded to bfloat16's 8-bit mantissa, kept in float32 (an
    explicit rounding, which XLA does not elide as it may a round trip
    through the bfloat16 type)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def dot(a, b, precision: str):
    """a @ b.T for a (m, d), b (n, d).  The bf16 passes multiply
    bf16-valued operands exactly and add in f32, as the chip does."""
    mm = partial(jnp.matmul, precision=HIGHEST)
    if precision == "highest":
        return mm(a, b.T)
    ah, bh = _bf16(a), _bf16(b)
    if precision == "default":
        return mm(ah, bh.T)
    if precision == "high":
        al, bl = _bf16(a - ah), _bf16(b - bh)
        return mm(ah, bh.T) + (mm(ah, bl.T) + mm(al, bh.T))
    raise ValueError(f"unknown precision {precision!r}")


def _dist(metric: str, q, x, xn, precision: str):
    ip = dot(q, x, precision)
    if metric == "ip":
        return -ip
    return jnp.sum(q * q, -1)[:, None] + xn[None, :] - 2.0 * ip


def passes(bitmaps, rows):
    """(Q, R) bool: bit `rows[r]` of each query's packed bitmap."""
    word = jnp.take(bitmaps, rows >> 5, axis=1)
    return ((word >> (rows & 31).astype(jnp.uint32)) & 1).astype(bool)


@partial(jax.jit, static_argnames=("k", "metric", "precision"))
def _block_topk(best_d, best_i, q, bitmaps, x, xn, start, k: int,
                metric: str, precision: str):
    rows = start + jnp.arange(x.shape[0], dtype=jnp.int32)
    d = _dist(metric, q, x, xn, precision)
    d = jnp.where(passes(bitmaps, rows), d, jnp.inf)
    cand_d = jnp.concatenate([best_d, d], axis=1)
    cand_i = jnp.concatenate([best_i, jnp.broadcast_to(rows, d.shape)],
                             axis=1)
    neg, pos = jax.lax.top_k(-cand_d, k)
    ids = jnp.take_along_axis(cand_i, pos, axis=1)
    return -neg, jnp.where(jnp.isinf(neg), -1, ids)


def filtered_topk(vectors, queries, bitmaps, k: int, metric: str,
                  precision: str = "highest"):
    """Exact filtered top-k: (dists (Q, k) f32, ids (Q, k) int32) as numpy,
    ids -1 (dists +inf) where fewer than k rows pass.  Queries go in
    blocks of QUERY_BLOCK (the last one padded), rows in ROW_BLOCKs."""
    n, nq = vectors.shape[0], queries.shape[0]
    xn = jnp.sum(vectors * vectors, axis=-1)
    out_d, out_i = [], []
    for s in range(0, nq, QUERY_BLOCK):
        sel = jnp.asarray(np.minimum(np.arange(s, s + QUERY_BLOCK), nq - 1))
        q, bm = queries[sel], bitmaps[sel]
        best_d = jnp.full((QUERY_BLOCK, k), jnp.inf, jnp.float32)
        best_i = jnp.full((QUERY_BLOCK, k), -1, jnp.int32)
        for r in range(0, n, ROW_BLOCK):
            best_d, best_i = _block_topk(
                best_d, best_i, q, bm, vectors[r:r + ROW_BLOCK],
                xn[r:r + ROW_BLOCK], jnp.int32(r), k, metric, precision)
        m = min(QUERY_BLOCK, nq - s)
        out_d.append(np.asarray(best_d)[:m])
        out_i.append(np.asarray(best_i)[:m])
    return np.concatenate(out_d), np.concatenate(out_i)


@partial(jax.jit, static_argnames=("metric",))
def _pair_dist(q, x, metric: str):
    """Per-pair reference distance and its rounding scale, (A, k) each:
    q (A, d), x (A, k, d)."""
    ip = jnp.einsum("ad,akd->ak", q, x, precision=HIGHEST)
    qq = jnp.sum(q * q, -1)[:, None]
    xx = jnp.sum(x * x, -1)
    if metric == "ip":
        return -ip, jnp.sqrt(qq * xx)
    return qq + xx - 2.0 * ip, qq + xx


def distances_of(vectors, queries, ids, metric: str, block: int = 4096):
    """Reference distances of given ids, with the scale their rounding
    error is relative to (||q||^2 + ||x||^2 for L2, ||q|| ||x|| for IP).
    queries (A, d) and ids (A, k) numpy, ids -1-padded (row 0 stands in;
    the caller masks those).  Returns numpy (A, k) each."""
    a = ids.shape[0]
    pad = (-a) % block
    ids = np.pad(np.maximum(ids, 0), ((0, pad), (0, 0)))
    queries = np.pad(queries, ((0, pad), (0, 0)))
    ds, ss = [], []
    for s in range(0, a, block):
        d, sc = _pair_dist(jnp.asarray(queries[s:s + block]),
                           vectors[jnp.asarray(ids[s:s + block])], metric)
        ds.append(np.asarray(d))
        ss.append(np.asarray(sc))
    return np.concatenate(ds)[:a], np.concatenate(ss)[:a]
