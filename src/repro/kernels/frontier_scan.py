"""Fused frontier-scan Pallas kernels (graph engine superstep, DESIGN.md §7).

One superstep of the batch-synchronous graph engine scores, per in-flight
query, a chunk of candidate nodes compacted out of the frontier's
neighborhood (only the candidates the strategy actually needs — unvisited
for traversal-first, passing/unvisited 2-hop for filter-first).  The
candidate vectors arrive already gathered through the deduplicated
frontier-union block (each distinct node is fetched from HBM once per
superstep, however many queries touch it); this kernel fuses the remaining
hot work in one VMEM-resident pass per query:

  * distance of the query against its (C, d) candidate chunk — one MXU
    (1, d) × (d, C) contraction, plus the precomputed-norm L2 completion
    (the per-row ‖x‖² never recomputes inside the step);
  * the filter bit test.  The bitmap word of every candidate row,
    `bitmaps[q, id >> 5]`, is gathered by XLA before the call and enters
    as a (Q, C) uint32 block aligned with the ids; the kernel keeps only
    the shift and the mask.  The kernel never sees the (Q, n/32) bitmap,
    so its VMEM footprint does not grow with the table.

Every per-query operand is laid out (Q, 1, X) and blocked (1, 1, X): the
last two block dimensions equal the array's, which is what the TPU
lowering requires of a block that is one row tall.

Outputs are the raw distances (+inf only at id padding — strategies decide
how filtering gates insertion, so the pass mask is returned separately as
int8) — semantics mirrored by `ref.frontier_scan_ref`, the jnp oracle the
engine uses by default and the allclose target of the parity tests (the
pass mask is bit-identical).

Variants, all one kernel body:
  * `frontier_scan_sq8_pallas` (DESIGN.md §9): the chunk arrives as SQ8
    int8 rows straight from the shadow heap — 4× less HBM→VMEM traffic
    per candidate — and is dequantized in-kernel (x = t·scale + mean)
    before the same contraction; ‖x̂‖² is precomputed at quantization.
  * `frontier_scan_excl[_sq8]_pallas` (DESIGN.md §14): a third output,
    the FAVOR keep mask, computed by the same `excl_keep_mask` ops as the
    oracle so it is bit-identical given equal distances.

VMEM per grid step (f32 chunk): query d + chunk C×d + five C-wide rows;
for C=128, d=1536: 0.8 MB, double-buffered well inside v5e's 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import excl_keep_mask

_NT = (((1,), (1,)), ((), ()))          # contract the last dim of both sides


def probe_words(bitmaps: jax.Array, ids: jax.Array) -> jax.Array:
    """(Q, W) packed bitmaps, (Q, C) row ids -> (Q, C) uint32: the word
    holding each row's bit (row 0's word at padding ids)."""
    return jnp.take_along_axis(bitmaps, jnp.maximum(ids, 0) >> 5, axis=1)


def bit_pass(words, rid):
    """The in-kernel half of the bitmap probe: shift + mask + id validity."""
    shift = (jnp.maximum(rid, 0) & 31).astype(jnp.uint32)
    return (((words >> shift) & jnp.uint32(1)) == 1) & (rid >= 0)


def _frontier_kernel(*refs, metric: str, sq8: bool, excl: bool,
                     margin: float):
    refs = list(refs)
    q_ref, vec_ref = refs.pop(0), refs.pop(0)
    scale_ref, mean_ref = (refs.pop(0), refs.pop(0)) if sq8 else (None, None)
    norm_ref, id_ref, word_ref = refs.pop(0), refs.pop(0), refs.pop(0)
    excl_ref, tau_ref = (refs.pop(0), refs.pop(0)) if excl else (None, None)
    dist_ref, pass_ref = refs.pop(0), refs.pop(0)
    keep_ref = refs.pop(0) if excl else None

    q = q_ref[0]                                     # (1, d) f32
    x = vec_ref[0]                                   # (C, d)
    if sq8:
        x = x.astype(jnp.float32) * scale_ref[...] + mean_ref[...]
    ip = jax.lax.dot_general(q, x, _NT, precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)  # (1, C)
    if metric == "ip":
        d = -ip
    else:
        qn = jnp.sum(q * q, axis=1, keepdims=True)   # (1, 1)
        d = qn + norm_ref[0] - 2.0 * ip
    rid = id_ref[0]                                  # (1, C) int32
    ok = bit_pass(word_ref[0], rid)
    dfin = jnp.where(rid >= 0, d, jnp.inf)
    dist_ref[0] = dfin
    pass_ref[0] = ok.astype(jnp.int8)
    if excl:
        keep = excl_keep_mask(dfin, excl_ref[0], tau_ref[0], ok, margin)
        keep_ref[0] = keep.astype(jnp.int8)


def _frontier_call(queries, vecs, norms, ids, bitmaps, *, metric: str,
                   interpret: bool, scale=None, mean=None, excl=None,
                   tau=None, margin: float = 0.5):
    """Pad to (8·, 128·) tiles, lay per-query operands out as (Q, 1, X),
    gather the probe words, and run the grid-(Q,) kernel."""
    sq8, has_excl = scale is not None, excl is not None
    nq, c, d = vecs.shape
    pd = (-d) % 128
    pc = (-c) % 128          # C is the lane axis of the (1, C) rows
    cp, dp = c + pc, d + pd

    def row(a):              # (Q, C) -> (Q, 1, Cp)
        return jnp.pad(a, ((0, 0), (0, pc)))[:, None]

    idp = jnp.pad(ids, ((0, 0), (0, pc)), constant_values=-1)
    args = [jnp.pad(queries.astype(jnp.float32), ((0, 0), (0, pd)))[:, None],
            jnp.pad(vecs if sq8 else vecs.astype(jnp.float32),
                    ((0, 0), (0, pc), (0, pd)))]
    per_q_row = pl.BlockSpec((1, 1, cp), lambda i: (i, 0, 0))
    specs = [pl.BlockSpec((1, 1, dp), lambda i: (i, 0, 0)),
             pl.BlockSpec((1, cp, dp), lambda i: (i, 0, 0))]
    if sq8:
        for v in (scale, mean):
            args.append(jnp.pad(v.astype(jnp.float32), (0, pd))[None, :])
            specs.append(pl.BlockSpec((1, dp), lambda i: (0, 0)))
    args += [row(norms.astype(jnp.float32)), idp[:, None],
             probe_words(bitmaps, idp)[:, None]]
    specs += [per_q_row] * 3
    if has_excl:
        args += [row(excl.astype(jnp.float32)),
                 tau.astype(jnp.float32).reshape(nq, 1, 1)]
        specs += [per_q_row, pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0))]
    n_out = 3 if has_excl else 2
    outs = pl.pallas_call(
        functools.partial(_frontier_kernel, metric=metric, sq8=sq8,
                          excl=has_excl, margin=margin),
        grid=(nq,),
        in_specs=specs,
        out_specs=[per_q_row] * n_out,
        out_shape=[jax.ShapeDtypeStruct((nq, 1, cp), jnp.float32)]
        + [jax.ShapeDtypeStruct((nq, 1, cp), jnp.int8)] * (n_out - 1),
        interpret=interpret,
    )(*args)
    dist = outs[0][:, 0, :c]
    masks = [m[:, 0, :c].astype(bool) for m in outs[1:]]
    return (dist, *masks)


def frontier_scan_pallas(queries: jax.Array, vecs: jax.Array,
                         norms: jax.Array, ids: jax.Array,
                         bitmaps: jax.Array, metric: str = "l2",
                         interpret: bool = False
                         ) -> tuple[jax.Array, jax.Array]:
    """queries (Q, d), vecs (Q, C, d) f32, norms (Q, C), ids (Q, C) int32,
    bitmaps (Q, W) uint32 → (dists (Q, C) f32, pass (Q, C) bool).

    Grid is (Q,): one step fuses one query's chunk scoring + bit test."""
    return _frontier_call(queries, vecs, norms, ids, bitmaps, metric=metric,
                          interpret=interpret)


def frontier_scan_sq8_pallas(queries: jax.Array, qvecs: jax.Array,
                             scale: jax.Array, mean: jax.Array,
                             norms: jax.Array, ids: jax.Array,
                             bitmaps: jax.Array, metric: str = "l2",
                             interpret: bool = False
                             ) -> tuple[jax.Array, jax.Array]:
    """queries (Q, d) f32, qvecs (Q, C, d) int8 (SQ8 shadow rows),
    scale/mean (d,) f32, norms (Q, C) f32 (precomputed ‖dequant‖²),
    ids (Q, C) int32, bitmaps (Q, W) uint32
    → (dists (Q, C) f32, pass (Q, C) bool).

    Same grid/fusion as `frontier_scan_pallas`, with the dequantization
    folded into the kernel so only int8 rows cross HBM→VMEM."""
    return _frontier_call(queries, qvecs, norms, ids, bitmaps, metric=metric,
                          interpret=interpret, scale=scale, mean=mean)


def frontier_scan_excl_pallas(queries: jax.Array, vecs: jax.Array,
                              norms: jax.Array, ids: jax.Array,
                              bitmaps: jax.Array, excl: jax.Array,
                              tau: jax.Array, metric: str = "l2",
                              margin: float = 0.5, interpret: bool = False
                              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """`frontier_scan_pallas` + the fused FAVOR keep mask (DESIGN.md §14).

    Extra inputs: excl (Q, C) f32 squared exclusion radii of the chunk
    rows (gathered alongside the vectors — zero extra HBM round trips)
    and tau (Q, 1) f32 per-query W tail.  Third output: keep (Q, C) bool,
    computed by the SAME `excl_keep_mask` ops as the jnp oracle.
    dists/pass semantics unchanged.
    """
    return _frontier_call(queries, vecs, norms, ids, bitmaps, metric=metric,
                          interpret=interpret, excl=excl, tau=tau,
                          margin=margin)


def frontier_scan_excl_sq8_pallas(queries: jax.Array, qvecs: jax.Array,
                                  scale: jax.Array, mean: jax.Array,
                                  norms: jax.Array, ids: jax.Array,
                                  bitmaps: jax.Array, excl: jax.Array,
                                  tau: jax.Array, metric: str = "l2",
                                  margin: float = 0.5,
                                  interpret: bool = False
                                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """`frontier_scan_sq8_pallas` + the fused FAVOR keep mask: int8 chunk
    rows dequantized in-kernel, keep rule applied to the quantized
    distances (the distances pool insertion uses)."""
    return _frontier_call(queries, qvecs, norms, ids, bitmaps, metric=metric,
                          interpret=interpret, scale=scale, mean=mean,
                          excl=excl, tau=tau, margin=margin)
