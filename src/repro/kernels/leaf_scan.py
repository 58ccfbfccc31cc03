"""Fused filtered quantized leaf-scan Pallas kernels (the paper's Fig. 7 flow).

Two variants share the layout:

`leaf_scan_pallas` — one grid step processes one row tile of one ScaNN
leaf for ONE query: the int8 tile is DMA'd HBM→VMEM (the TPU analogue of
the paper's sequential leaf-page walk), dequantized, scored against the
query, and filter-tested, in a single VMEM-resident pass.  Filtered-out
and padded rows emit +inf.

`leaf_scan_batched_pallas` — the query-batched pipeline (DESIGN.md §4): one
grid step DMAs one int8 leaf row tile into VMEM ONCE and scores it against
the whole query block via a single MXU (Q, d) × (d, C) contraction (Q rides
the 8-sublane axis, C the 128-lane axis).  Precomputed row norms replace
the per-query ||x||² reduction of the single-query kernel.  This is what
amortizes leaf fetch + filter + score across a concurrent query batch,
instead of re-streaming every tile per query under `jax.vmap`.

The grid walks (leaf, row tile): rows are padded to a multiple of the
tile height `_TILE_ROWS` (or of 128 below it), so one step's working set
is (T × d) int8 + (T × d) f32 whatever the leaf capacity — 0.75 MB + 3 MB
at T=512, d=1536, inside the 16 MB/core VMEM of v5e.

Bitmap probe: XLA gathers the word holding each row's bit
(`bitmap[rowid >> 5]`, per query for the batched kernel) before the call,
into a uint32 block shaped like the scores; the kernel keeps only the
shift and the mask (`frontier_scan.bit_pass`).  So the kernel never holds
the n/32-word bitmap, and its VMEM does not grow with the table.  The
pass mask is bit-identical to `ref.leaf_scan_ref` /
`ref.leaf_scan_batched_ref`; the distances agree to f32 rounding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.frontier_scan import _NT, bit_pass

_TILE_ROWS = 512


def _row_tiling(c: int) -> tuple[int, int]:
    """(padded rows, tile height) for a leaf of capacity c."""
    cp = c + (-c) % 128
    tc = min(cp, _TILE_ROWS)
    return cp + (-cp) % tc, tc


def _dequant_scores(q, t, scale, mean, norms, metric: str):
    """(Qb, d) queries against one (T, d) int8 row tile -> (Qb, T)."""
    x = t.astype(jnp.float32) * scale + mean
    ip = jax.lax.dot_general(q, x, _NT, precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    if metric == "ip":
        return -ip
    if norms is None:        # single-query kernel: reduce the resident tile
        ones = jnp.ones((1, x.shape[1]), jnp.float32)
        norms = jax.lax.dot_general(ones, x * x, _NT,
                                    precision=jax.lax.Precision.HIGHEST,
                                    preferred_element_type=jnp.float32)
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    return qn + norms - 2.0 * ip


def _leaf_scan_kernel(q_ref, tile_ref, rowid_ref, scale_ref, mean_ref,
                      word_ref, out_ref, *, metric: str):
    d = _dequant_scores(q_ref[...], tile_ref[0], scale_ref[...],
                        mean_ref[...], None, metric)             # (1, T)
    ok = bit_pass(word_ref[0], rowid_ref[0])
    out_ref[0] = jnp.where(ok, d, jnp.inf)


def leaf_scan_pallas(query: jax.Array, tiles: jax.Array, rowids: jax.Array,
                     scale: jax.Array, mean: jax.Array, bitmap: jax.Array,
                     metric: str = "l2", interpret: bool = False) -> jax.Array:
    """query (d,), tiles (nl, C, d) int8, rowids (nl, C), scale/mean (d,),
    bitmap (W,) uint32 → scores (nl, C) f32 (+inf = filtered/padded)."""
    nl, c, d = tiles.shape
    pd = (-d) % 128
    cp, tc = _row_tiling(c)
    tiles_p = jnp.pad(tiles, ((0, 0), (0, cp - c), (0, pd)))
    rowids_p = jnp.pad(rowids, ((0, 0), (0, cp - c)), constant_values=-1)
    words = bitmap[jnp.maximum(rowids_p, 0) >> 5]
    q = jnp.pad(query.astype(jnp.float32), (0, pd))[None, :]
    s = jnp.pad(scale.astype(jnp.float32), (0, pd))[None, :]
    m = jnp.pad(mean.astype(jnp.float32), (0, pd))[None, :]
    dp = d + pd
    row_tile = pl.BlockSpec((1, 1, tc), lambda i, j: (i, 0, j))
    out = pl.pallas_call(
        functools.partial(_leaf_scan_kernel, metric=metric),
        grid=(nl, cp // tc),
        in_specs=[
            pl.BlockSpec((1, dp), lambda i, j: (0, 0)),          # query
            pl.BlockSpec((1, tc, dp), lambda i, j: (i, j, 0)),   # leaf rows
            row_tile,                                            # rowids
            pl.BlockSpec((1, dp), lambda i, j: (0, 0)),          # scale
            pl.BlockSpec((1, dp), lambda i, j: (0, 0)),          # mean
            row_tile,                                            # words
        ],
        out_specs=row_tile,
        out_shape=jax.ShapeDtypeStruct((nl, 1, cp), jnp.float32),
        interpret=interpret,
    )(q, tiles_p, rowids_p[:, None], s, m, words[:, None])
    return out[:, 0, :c]


def _leaf_scan_batched_kernel(q_ref, tile_ref, rowid_ref, scale_ref,
                              mean_ref, norms_ref, word_ref, out_ref, *,
                              metric: str):
    # MXU: score the whole query block against the resident tile at once
    d = _dequant_scores(q_ref[...], tile_ref[0], scale_ref[...],
                        mean_ref[...], norms_ref[0], metric)     # (Qp, T)
    ok = bit_pass(word_ref[0], rowid_ref[0])
    out_ref[0] = jnp.where(ok, d, jnp.inf)


def leaf_scan_batched_pallas(queries: jax.Array, tiles: jax.Array,
                             rowids: jax.Array, scale: jax.Array,
                             mean: jax.Array, bitmaps: jax.Array,
                             row_norms_sq: jax.Array, metric: str = "l2",
                             interpret: bool = False) -> jax.Array:
    """queries (Q, d) f32, tiles (U, C, d) int8, rowids (U, C) int32,
    scale/mean (d,) f32, bitmaps (Q, W) uint32, row_norms_sq (U, C) f32
    → scores (Q, U, C) f32 (+inf = filtered/padded).

    Grid is (U, row tiles): each step fetches one leaf row tile once and
    scores the whole query batch against it (DESIGN.md §4)."""
    u, c, d = tiles.shape
    nq = queries.shape[0]
    pd = (-d) % 128
    pq = (-nq) % 8
    cp, tc = _row_tiling(c)
    tiles_p = jnp.pad(tiles, ((0, 0), (0, cp - c), (0, pd)))
    rowids_p = jnp.pad(rowids, ((0, 0), (0, cp - c)), constant_values=-1)
    norms_p = jnp.pad(row_norms_sq.astype(jnp.float32), ((0, 0), (0, cp - c)))
    q = jnp.pad(queries.astype(jnp.float32), ((0, pq), (0, pd)))
    s = jnp.pad(scale.astype(jnp.float32), (0, pd))[None, :]
    m = jnp.pad(mean.astype(jnp.float32), (0, pd))[None, :]
    bm = jnp.pad(bitmaps, ((0, pq), (0, 0)))         # padded queries: all 0
    words = jnp.take(bm, jnp.maximum(rowids_p, 0) >> 5,
                     axis=1).transpose(1, 0, 2)       # (U, Qp, Cp)
    qp, dp = nq + pq, d + pd
    row_tile = pl.BlockSpec((1, 1, tc), lambda i, j: (i, 0, j))
    score_tile = pl.BlockSpec((1, qp, tc), lambda i, j: (i, 0, j))
    out = pl.pallas_call(
        functools.partial(_leaf_scan_batched_kernel, metric=metric),
        grid=(u, cp // tc),
        in_specs=[
            pl.BlockSpec((qp, dp), lambda i, j: (0, 0)),         # query block
            pl.BlockSpec((1, tc, dp), lambda i, j: (i, j, 0)),   # leaf rows
            row_tile,                                            # rowids
            pl.BlockSpec((1, dp), lambda i, j: (0, 0)),          # scale
            pl.BlockSpec((1, dp), lambda i, j: (0, 0)),          # mean
            row_tile,                                            # row norms
            score_tile,                                          # words
        ],
        out_specs=score_tile,
        out_shape=jax.ShapeDtypeStruct((u, qp, cp), jnp.float32),
        interpret=interpret,
    )(q, tiles_p, rowids_p[:, None], s, m, norms_p[:, None], words)
    return out.transpose(1, 0, 2)[:nq, :, :c]
