"""Jitted public wrappers for the Pallas kernels.

`_interpret()` is the one place that decides how a kernel runs: compiled
by Mosaic when JAX's default backend is a TPU, and in Pallas interpret
mode (the kernel body executed with the same blocking, on any backend)
everywhere else — which is how the CPU test suite exercises them.
`use_pallas=False` falls through to the jnp oracles — tests compare both
paths.  tests/test_tpu_compile.py compiles the kernels for a described
v5e chip, since interpret mode cannot see tiling or VMEM refusals.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels.distance import distance_matrix_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.frontier_scan import (frontier_scan_excl_pallas,
                                         frontier_scan_excl_sq8_pallas,
                                         frontier_scan_pallas,
                                         frontier_scan_sq8_pallas)
from repro.kernels.leaf_scan import leaf_scan_batched_pallas, leaf_scan_pallas


def _interpret() -> bool:
    """True off the TPU: Pallas kernels then run in interpret mode."""
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("metric", "use_pallas"))
def distance_matrix(queries, rows, metric: str = "l2",
                    use_pallas: bool = True):
    if use_pallas:
        return distance_matrix_pallas(queries, rows, metric,
                                      interpret=_interpret())
    return ref.distance_matrix_ref(queries, rows, metric)


@partial(jax.jit, static_argnames=("metric", "use_pallas"))
def leaf_scan(query, tiles, rowids, scale, mean, bitmap, metric: str = "l2",
              use_pallas: bool = True):
    if use_pallas:
        return leaf_scan_pallas(query, tiles, rowids, scale, mean, bitmap,
                                metric, interpret=_interpret())
    return ref.leaf_scan_ref(query, tiles, rowids, scale, mean, bitmap,
                             metric)


@partial(jax.jit, static_argnames=("metric", "use_pallas"))
def leaf_scan_batched(queries, tiles, rowids, scale, mean, bitmaps,
                      row_norms_sq, metric: str = "l2",
                      use_pallas: bool = True):
    """Query-batched fused leaf scan: each tile is fetched once and scored
    against the whole query block (DESIGN.md §4). Returns (Q, U, C)."""
    if use_pallas:
        return leaf_scan_batched_pallas(queries, tiles, rowids, scale, mean,
                                        bitmaps, row_norms_sq, metric,
                                        interpret=_interpret())
    return ref.leaf_scan_batched_ref(queries, tiles, rowids, scale, mean,
                                     bitmaps, row_norms_sq, metric)


@partial(jax.jit, static_argnames=("metric", "use_pallas"))
def frontier_scan(queries, vecs, norms, ids, bitmaps, metric: str = "l2",
                  use_pallas: bool = False):
    """Fused frontier-chunk scoring + filter probe for the graph engine
    (DESIGN.md §7).  Returns (dists (Q, C), pass (Q, C)).

    Unlike the other wrappers this defaults to the jnp oracle: its
    elementwise+sum arithmetic is the bit-identical mirror of the legacy
    beam search (the frontier engine's equivalence contract), while the
    MXU kernel is allclose-only — opt into it explicitly.  The cos metric
    has no kernel (like the batched leaf scan) and always routes through
    the oracle."""
    if use_pallas and metric != "cos":
        return frontier_scan_pallas(queries, vecs, norms, ids, bitmaps,
                                    metric, interpret=_interpret())
    return ref.frontier_scan_ref(queries, vecs, norms, ids, bitmaps, metric)


@partial(jax.jit, static_argnames=("metric", "use_pallas"))
def frontier_scan_sq8(queries, qvecs, scale, mean, norms, ids, bitmaps,
                      metric: str = "l2", use_pallas: bool = False):
    """SQ8 frontier-chunk scoring + filter probe (DESIGN.md §9): the chunk
    arrives int8 from the shadow heap and is dequantized in-kernel.
    Returns (dists (Q, C), pass (Q, C)).

    Like `frontier_scan`, defaults to the jnp oracle — its dequant +
    elementwise arithmetic is the bit-identical mirror of the legacy
    vmapped engine's quantized gather path; the MXU kernel is
    allclose-only.  cos always routes through the oracle."""
    if use_pallas and metric != "cos":
        return frontier_scan_sq8_pallas(queries, qvecs, scale, mean, norms,
                                        ids, bitmaps, metric,
                                        interpret=_interpret())
    return ref.frontier_scan_sq8_ref(queries, qvecs, scale, mean, norms,
                                     ids, bitmaps, metric)


@partial(jax.jit, static_argnames=("metric", "margin", "use_pallas"))
def frontier_scan_excl(queries, vecs, norms, ids, bitmaps, excl, tau,
                       metric: str = "l2", margin: float = 0.5,
                       use_pallas: bool = False):
    """Frontier-chunk scoring + filter probe + fused FAVOR keep mask
    (DESIGN.md §14).  excl (Q, C) squared exclusion radii of the chunk
    rows, tau (Q, 1) current W tail.  Returns (dists, pass, keep).

    dists/pass follow `frontier_scan`'s contract exactly (oracle default,
    bit-identical to the legacy engine); keep is computed by the shared
    `excl_keep_mask` ops on both paths so the mask is bit-identical
    kernel-vs-oracle."""
    if use_pallas and metric != "cos":
        return frontier_scan_excl_pallas(queries, vecs, norms, ids, bitmaps,
                                         excl, tau, metric, margin,
                                         interpret=_interpret())
    return ref.frontier_scan_excl_ref(queries, vecs, norms, ids, bitmaps,
                                      excl, tau, metric, margin)


@partial(jax.jit, static_argnames=("metric", "margin", "use_pallas"))
def frontier_scan_excl_sq8(queries, qvecs, scale, mean, norms, ids, bitmaps,
                           excl, tau, metric: str = "l2",
                           margin: float = 0.5, use_pallas: bool = False):
    """SQ8 variant of `frontier_scan_excl`: int8 chunk dequantized
    in-kernel, keep rule applied to the quantized distances.
    Returns (dists, pass, keep)."""
    if use_pallas and metric != "cos":
        return frontier_scan_excl_sq8_pallas(queries, qvecs, scale, mean,
                                             norms, ids, bitmaps, excl, tau,
                                             metric, margin,
                                             interpret=_interpret())
    return ref.frontier_scan_excl_sq8_ref(queries, qvecs, scale, mean, norms,
                                          ids, bitmaps, excl, tau, metric,
                                          margin)


def flash_attention_fused(q, k, v, causal: bool = True):
    """Pallas flash attention, shard_map-wrapped when a mesh is active:
    batch shards over (pod, data), kv heads over `model` (when divisible).
    Interpret mode on non-TPU backends."""
    mesh = jax.sharding.get_abstract_mesh()
    interp = _interpret()
    if mesh is None or mesh.empty:
        return flash_attention_pallas(q, k, v, causal=causal,
                                      interpret=interp)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    baxes = tuple(a for a in ("pod", "data") if a in sizes)
    bsz = 1
    for a in baxes:
        bsz *= sizes[a]
    bspec = (baxes if len(baxes) > 1 else baxes[0]) \
        if baxes and q.shape[0] % bsz == 0 else None
    kvspec = "model" if ("model" in sizes
                         and k.shape[2] % sizes["model"] == 0) else None
    qs = P(bspec, None, kvspec, None)
    fn = jax.shard_map(
        lambda q_, k_, v_: flash_attention_pallas(q_, k_, v_, causal=causal,
                                                  interpret=interp),
        mesh=mesh, in_specs=(qs, qs, qs), out_specs=qs, check_vma=False)
    return fn(q, k, v)
