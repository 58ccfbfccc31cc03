"""MXU-tiled batched distance-matrix Pallas kernel.

Target: TPU v5e.  The (Q, N) distance matrix is the compute hot-spot of
centroid scoring (ScaNN root/branch levels) and of the workload generator.
Tiling: (BQ, D) × (BN, D) blocks in VMEM, output (BQ, BN); the inner
contraction runs on the MXU via jnp.dot with preferred_element_type=f32.
Block sizes default to 128×128 — MXU-aligned (multiples of 8×128 lanes).

L2 uses the ||q||² + ||x||² − 2q·x expansion so the MXU does all the work;
norms are computed inside the kernel from the resident blocks (cheap VPU
reduction, avoids a second HBM stream).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dist_kernel(q_ref, x_ref, out_ref, *, metric: str):
    q = q_ref[...]                       # (BQ, D) f32
    x = x_ref[...]                       # (BN, D) f32
    ip = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    if metric == "ip":
        out_ref[...] = -ip
    else:
        qn = jnp.sum(q * q, axis=1, keepdims=True)
        xn = jnp.sum(x * x, axis=1)[None, :]
        out_ref[...] = qn + xn - 2.0 * ip


def distance_matrix_pallas(queries: jax.Array, rows: jax.Array,
                           metric: str = "l2", bq: int = 128, bn: int = 128,
                           interpret: bool = False) -> jax.Array:
    """(Q, N) distances. Pads Q/N up to block multiples, D to lane multiple."""
    q0, n0, d0 = queries.shape[0], rows.shape[0], rows.shape[1]
    bq = min(bq, max(8, q0))
    pq, pn, pd = (-q0) % bq, (-n0) % bn, (-d0) % 128
    q = jnp.pad(queries.astype(jnp.float32), ((0, pq), (0, pd)))
    x = jnp.pad(rows.astype(jnp.float32), ((0, pn), (0, pd)))
    grid = (q.shape[0] // bq, x.shape[0] // bn)
    out = pl.pallas_call(
        functools.partial(_dist_kernel, metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, q.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, x.shape[1]), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q.shape[0], x.shape[0]), jnp.float32),
        interpret=interpret,
    )(q, x)
    return out[:q0, :n0]
