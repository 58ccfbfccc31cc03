"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Their contractions run at `HIGHEST` precision: f32 on the TPU's MXU, as
on the CPU (where the flag changes no bit).  At XLA's default precision a
TPU rounds f32 matmul operands to bf16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def distance_matrix_ref(queries: jax.Array, rows: jax.Array,
                        metric: str = "l2") -> jax.Array:
    """(Q, N) distances; lower = closer. queries (Q, d), rows (N, d) f32."""
    ip = jnp.matmul(queries, rows.T, precision=HIGHEST)
    if metric == "ip":
        return -ip
    qn = jnp.sum(queries * queries, axis=1, keepdims=True)
    rn = jnp.sum(rows * rows, axis=1)[None, :]
    return qn + rn - 2.0 * ip


def probe_bitmap_ref(bitmap: jax.Array, row_ids: jax.Array) -> jax.Array:
    safe = jnp.maximum(row_ids, 0)
    word = bitmap[safe >> 5]
    bit = (word >> (safe & 31).astype(jnp.uint32)) & jnp.uint32(1)
    return jnp.where(row_ids >= 0, bit.astype(bool), False)


def leaf_scan_ref(query: jax.Array, tiles: jax.Array, rowids: jax.Array,
                  scale: jax.Array, mean: jax.Array, bitmap: jax.Array,
                  metric: str = "l2") -> jax.Array:
    """Fused filtered quantized leaf scoring, reference semantics.

    query  (d,) f32           — already PCA-projected if applicable
    tiles  (nl, C, d) int8    — SQ8-quantized leaf rows
    rowids (nl, C) int32      — heap row ids, -1 padded
    scale/mean (d,) f32       — dequantization: x = tile * scale + mean
    bitmap (words,) uint32    — filter bitmap over heap row ids
    returns (nl, C) f32 scores with +inf where padded or filtered out.
    """
    x = tiles.astype(jnp.float32) * scale + mean          # (nl, C, d)
    if metric == "ip":
        d = -jnp.einsum("lcd,d->lc", x, query, precision=HIGHEST)
    else:
        qn = jnp.sum(query * query)
        xn = jnp.sum(x * x, axis=-1)
        d = qn + xn - 2.0 * jnp.einsum("lcd,d->lc", x, query,
                                       precision=HIGHEST)
    ok = probe_bitmap_ref(bitmap, rowids)
    return jnp.where(ok, d, jnp.inf)


def leaf_scan_batched_ref(queries: jax.Array, tiles: jax.Array,
                          rowids: jax.Array, scale: jax.Array,
                          mean: jax.Array, bitmaps: jax.Array,
                          row_norms_sq: jax.Array | None = None,
                          metric: str = "l2") -> jax.Array:
    """Query-batched fused filtered leaf scoring, reference semantics.

    Each leaf tile is read once for the whole query batch and scored via a
    single (Q, d) × (d, C) contraction per leaf (DESIGN.md §4).

    queries (Q, d) f32        — already PCA-projected if applicable
    tiles   (U, C, d) int8    — SQ8-quantized rows of the leaves to scan
    rowids  (U, C) int32      — heap row ids, -1 padded
    scale/mean (d,) f32       — dequantization: x = tile * scale + mean
    bitmaps (Q, words) uint32 — one packed filter bitmap per query
    row_norms_sq (U, C) f32   — optional precomputed ||x||² of the
                                dequantized rows (L2 fast path)
    returns (Q, U, C) f32 scores with +inf where padded or filtered out.
    """
    x = tiles.astype(jnp.float32) * scale + mean          # (U, C, d)
    ip = jnp.einsum("qd,ucd->quc", queries, x, precision=HIGHEST)
    if metric == "ip":
        d = -ip
    else:
        xn = (row_norms_sq if row_norms_sq is not None
              else jnp.sum(x * x, axis=-1))               # (U, C)
        qn = jnp.sum(queries * queries, axis=-1)          # (Q,)
        d = qn[:, None, None] + xn[None] - 2.0 * ip
    ok = jax.vmap(lambda bm: probe_bitmap_ref(bm, rowids))(bitmaps)
    return jnp.where(ok, d, jnp.inf)


def frontier_scan_ref(queries: jax.Array, vecs: jax.Array, norms: jax.Array,
                      ids: jax.Array, bitmaps: jax.Array,
                      metric: str = "l2") -> tuple[jax.Array, jax.Array]:
    """Fused frontier-chunk scoring + filter probe, reference semantics.

    queries (Q, d) f32   — one query per in-flight traversal
    vecs    (Q, C, d) f32 — each query's candidate chunk, gathered from the
                            deduplicated frontier union block (graph engine,
                            DESIGN.md §7)
    norms   (Q, C) f32   — precomputed ||x||² of the chunk rows (L2 path)
    ids     (Q, C) int32 — heap row ids, -1 padded
    bitmaps (Q, W) uint32 — per-query packed filter bitmaps
    returns (dists (Q, C) f32 with +inf at padded slots, pass (Q, C) bool).

    The distance arithmetic deliberately mirrors `types.distance` under
    `jax.vmap` — elementwise product + last-axis sum, never a dot — so the
    frontier engine's scores are bit-identical to the legacy vmapped
    beam search (the equivalence guarantee of tests/test_frontier.py).
    """
    def one(q, x, xn):
        if metric == "ip":
            return -jnp.sum(q * x, axis=-1)
        if metric == "cos":
            qn = jnp.linalg.norm(q, axis=-1) + 1e-12
            vn = jnp.linalg.norm(x, axis=-1) + 1e-12
            return 1.0 - jnp.sum(q * x, axis=-1) / (qn * vn)
        qn = jnp.sum(q * q, axis=-1)
        return qn + xn - 2.0 * jnp.sum(q * x, axis=-1)

    d = jax.vmap(one)(queries, vecs, norms)
    ok = jax.vmap(probe_bitmap_ref)(bitmaps, ids)
    return jnp.where(ids >= 0, d, jnp.inf), ok


def frontier_scan_sq8_ref(queries: jax.Array, qvecs: jax.Array,
                          scale: jax.Array, mean: jax.Array,
                          norms: jax.Array, ids: jax.Array,
                          bitmaps: jax.Array, metric: str = "l2"
                          ) -> tuple[jax.Array, jax.Array]:
    """SQ8 quantized-traversal frontier scoring, reference semantics
    (DESIGN.md §9).

    queries (Q, d) f32    — one query per in-flight traversal
    qvecs   (Q, C, d) int8 — SQ8 shadow rows of each query's chunk
    scale/mean (d,) f32   — dequantization: x̂ = qvecs * scale + mean
    norms   (Q, C) f32    — precomputed ‖x̂‖² (L2 path; the shadow store's
                            build-time `q_norms_sq`)
    ids     (Q, C) int32  — heap row ids, -1 padded
    bitmaps (Q, W) uint32 — per-query packed filter bitmaps
    returns (dists (Q, C) f32 with +inf at padded slots, pass (Q, C) bool).

    Dequantization + distance arithmetic deliberately mirror the legacy
    vmapped engine's quantized gather path (elementwise product +
    last-axis sum on the dequantized rows), so the two graph engines stay
    bit-identical under graph_quant="sq8" (tests/test_graph_quant.py).
    """
    x = qvecs.astype(jnp.float32) * scale + mean          # (Q, C, d)
    return frontier_scan_ref(queries, x, norms, ids, bitmaps, metric)


def excl_keep_mask(dists: jax.Array, excl: jax.Array, tau: jax.Array,
                   ok: jax.Array, margin: float) -> jax.Array:
    """Fused FAVOR keep rule (DESIGN.md §14), shared VERBATIM by the
    Pallas excl kernels and the jnp oracles so the pruning mask is
    bit-identical on both paths.

    All distances are squared l2; the triangle inequality only holds in
    root space, so the rule compares square roots: keep candidate v iff
    it passes the filter, or its exclusion radius e(v) (distance to its
    nearest passing row) satisfies
        sqrt(e) <= margin * (sqrt(d(q, v)) + sqrt(tau)),
    tau being the current W tail (the distance a row must beat to enter
    the result queue).  tau = +inf (W not yet full) keeps everything —
    the pre-fill navigation phase is never pruned.  With exact family
    radii and margin >= 1 the rule provably never fires (the passing row
    that produced tau witnesses the triangle bound); margin < 1 is the
    productive, recall-gated regime.
    """
    dr = jnp.sqrt(jnp.maximum(dists, 0.0))
    er = jnp.sqrt(jnp.maximum(excl, 0.0))
    tr = jnp.sqrt(jnp.maximum(tau, 0.0))
    return ok | (er <= jnp.float32(margin) * (dr + tr))


def frontier_scan_excl_ref(queries: jax.Array, vecs: jax.Array,
                           norms: jax.Array, ids: jax.Array,
                           bitmaps: jax.Array, excl: jax.Array,
                           tau: jax.Array, metric: str = "l2",
                           margin: float = 0.5
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """`frontier_scan_ref` + the fused exclusion keep mask.

    excl (Q, C) f32 — squared exclusion radii of the chunk rows
    tau  (Q, 1) f32 — per-query W tail (squared; +inf until W fills)
    returns (dists, pass, keep (Q, C) bool).  dists/pass are bit-identical
    to `frontier_scan_ref` — the mask is a third output, not a rescore.
    """
    d, ok = frontier_scan_ref(queries, vecs, norms, ids, bitmaps, metric)
    return d, ok, excl_keep_mask(d, excl, tau, ok, margin)


def frontier_scan_excl_sq8_ref(queries: jax.Array, qvecs: jax.Array,
                               scale: jax.Array, mean: jax.Array,
                               norms: jax.Array, ids: jax.Array,
                               bitmaps: jax.Array, excl: jax.Array,
                               tau: jax.Array, metric: str = "l2",
                               margin: float = 0.5
                               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """`frontier_scan_sq8_ref` + the fused exclusion keep mask (the mask
    compares the QUANTIZED distances against the full-precision radii —
    the same distances the pool insertion uses, so prune decisions and
    scores always agree)."""
    d, ok = frontier_scan_sq8_ref(queries, qvecs, scale, mean, norms, ids,
                                  bitmaps, metric)
    return d, ok, excl_keep_mask(d, excl, tau, ok, margin)
