"""Core datatypes for the filter-agnostic FVS framework.

Mirrors the paper's object model:
  - a vector collection stored in fixed-size "pages" (TPU analogue: dense
    HBM tiles; see DESIGN.md §3),
  - per-query filter *bitmaps* produced by the workload generator (§4 of the
    paper): the index never sees predicates, only row-id bitmaps,
  - per-query system counters (distance computations, filter checks, hops,
    page accesses) exactly matching the columns of the paper's Table 6.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Page geometry has one owner: the storage layer (DESIGN.md §8).  The
# names are re-exported here for backward compatibility — every historical
# consumer imported them from core.types.
from repro.storage.pages import (HEAP_PAGE_BYTES,  # noqa: F401
                                 heap_pages_per_vector,
                                 quant_heap_pages_per_vector)

Array = jax.Array

# Metrics supported by the paper's datasets (Table 2): L2 and inner product.
METRIC_L2 = "l2"
METRIC_IP = "ip"
METRIC_COS = "cos"


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class VectorStore:
    """A vector collection, optionally with a quantized shadow copy.

    vectors: (N, d) float32 full-precision rows ("heap" in the paper).
    norms_sq: (N,) precomputed squared norms (L2 fast path).

    The SQ8 shadow (DESIGN.md §9) is the quantized-traversal tier of the
    graph engine: per-dimension affine int8 rows (the same quantizer the
    ScaNN leaves use) plus build-time ||dequant(x)||² so the L2 fast path
    never recomputes norms during traversal.  None until `quantize_store`
    attaches it; the full-precision rows stay authoritative (exact rerank,
    reordering, ground truth).
    """

    vectors: Array
    norms_sq: Array
    metric: str = dataclasses.field(metadata=dict(static=True), default=METRIC_L2)
    # SQ8 shadow (graph_quant="sq8"): dequant is x = q_vectors*q_scale+q_mean
    q_vectors: Optional[Array] = None      # (N, d) int8
    q_scale: Optional[Array] = None        # (d,) f32
    q_mean: Optional[Array] = None         # (d,) f32
    q_norms_sq: Optional[Array] = None     # (N,) f32 of the dequantized rows

    @property
    def n(self) -> int:
        return self.vectors.shape[0]

    @property
    def dim(self) -> int:
        return self.vectors.shape[1]

    @property
    def has_sq8(self) -> bool:
        return self.q_vectors is not None

    @staticmethod
    def build(vectors: Array | np.ndarray, metric: str = METRIC_L2) -> "VectorStore":
        vectors = jnp.asarray(vectors, jnp.float32)
        norms_sq = jnp.sum(vectors * vectors, axis=-1)
        return VectorStore(vectors=vectors, norms_sq=norms_sq, metric=metric)


def sq8_quantize(x: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-dimension affine SQ8 over a dataset (the one quantizer in the
    repo — the ScaNN leaf builder and the graph shadow store share it).

    Returns (q (n, d) int8, scale (d,) f32, mean (d,) f32) with
    dequantization x̂ = q * scale + mean.
    """
    x = np.asarray(x, np.float32)
    scale, mean = sq8_params(x.min(0), x.max(0))
    return sq8_codes(x, scale, mean), scale, mean


def sq8_params(lo, hi):
    """(scale, mean) of `sq8_quantize` from the data's per-dimension
    minimum and maximum: numpy arrays, or jax arrays for a quantizer
    applied on the device."""
    xp = np if isinstance(lo, np.ndarray) else jnp
    return (xp.maximum((hi - lo) / 254.0, 1e-8).astype(np.float32),
            ((hi + lo) / 2.0).astype(np.float32))


def sq8_codes(x, scale, mean):
    """int8 codes of rows `x` (numpy or jax) under `sq8_params`."""
    xp = np if isinstance(x, np.ndarray) else jnp
    return xp.clip(xp.round((x - mean) / scale), -127, 127).astype(np.int8)


def quantize_store(store: "VectorStore") -> "VectorStore":
    """Attach the SQ8 shadow to a store (idempotent).  The shadow norms are
    computed with the same dequant + reduction arithmetic the frontier
    kernels/oracles apply, so precomputed and inline norms agree."""
    if store.has_sq8:
        return store
    q, scale, mean = sq8_quantize(np.asarray(store.vectors))
    qj = jnp.asarray(q)
    scale_j, mean_j = jnp.asarray(scale), jnp.asarray(mean)
    deq = qj.astype(jnp.float32) * scale_j + mean_j
    return dataclasses.replace(
        store, q_vectors=qj, q_scale=scale_j, q_mean=mean_j,
        q_norms_sq=jnp.sum(deq * deq, axis=-1))


def distance(metric: str, q: Array, x: Array, x_norm_sq: Optional[Array] = None) -> Array:
    """Distance between query q (..., d) and rows x (..., d). Lower is closer."""
    if metric == METRIC_L2:
        if x_norm_sq is None:
            x_norm_sq = jnp.sum(x * x, axis=-1)
        qn = jnp.sum(q * q, axis=-1)
        return qn + x_norm_sq - 2.0 * jnp.sum(q * x, axis=-1)
    if metric == METRIC_IP:
        return -jnp.sum(q * x, axis=-1)
    if metric == METRIC_COS:
        qn = jnp.linalg.norm(q, axis=-1) + 1e-12
        xn = jnp.linalg.norm(x, axis=-1) + 1e-12
        return 1.0 - jnp.sum(q * x, axis=-1) / (qn * xn)
    raise ValueError(f"unknown metric {metric!r}")


# ---------------------------------------------------------------------------
# Filter bitmaps.  The workload generator (workload.py) emits, per query, the
# set of row ids satisfying the (simulated) relational predicate.  Probing
# the bitmap during traversal == the paper's "filter check".
# ---------------------------------------------------------------------------

def pack_bitmap(passing_rows: np.ndarray | Array, n: int) -> Array:
    """Pack row-id set into a (ceil(n/32),) uint32 bitmap."""
    bits = np.zeros(n, dtype=bool)
    bits[np.asarray(passing_rows)] = True
    return pack_bool_bitmap(bits)


def pack_bool_bitmap(bits: np.ndarray | Array) -> Array:
    bits = np.asarray(bits, dtype=bool)
    n = bits.shape[-1]
    pad = (-n) % 32
    if pad:
        bits = np.concatenate([bits, np.zeros(bits.shape[:-1] + (pad,), bool)], -1)
    words = bits.reshape(bits.shape[:-1] + (-1, 32))
    weights = (1 << np.arange(32, dtype=np.uint64)).astype(np.uint32)
    packed = (words.astype(np.uint32) * weights).sum(-1, dtype=np.uint32)
    return jnp.asarray(packed)


def probe_bitmap(bitmap: Array, row_ids: Array) -> Array:
    """Vectorized filter check: bitmap probe per row id. Negative ids -> False."""
    row_ids = jnp.asarray(row_ids)
    safe = jnp.maximum(row_ids, 0)
    word = bitmap[safe >> 5]
    bit = (word >> (safe & 31).astype(jnp.uint32)) & jnp.uint32(1)
    return jnp.where(row_ids >= 0, bit.astype(bool), False)


def unpack_bitmap(bitmap: np.ndarray | Array, n: int) -> np.ndarray:
    words = np.asarray(bitmap)
    bits = (words[..., :, None] >> np.arange(32, dtype=np.uint32)) & 1
    return bits.reshape(words.shape[:-1] + (-1,))[..., :n].astype(bool)


def bitmap_andnot(bitmap: Array, minus: Array) -> Array:
    """bitmap ∧ ¬minus over packed uint32 words — the tombstone
    composition (DESIGN.md §12): `minus` is the delete bitmap, and the
    result is the live filter every executor actually probes, so deleted
    rows vanish from all strategies without touching their indexes.
    `minus` may be shorter (or longer) than the filter's word count —
    words past either end pass through unchanged (a missing word deletes
    nothing)."""
    bm = jnp.asarray(bitmap)
    mi = jnp.asarray(minus, jnp.uint32)
    w = min(bm.shape[-1], mi.shape[-1])
    return bm.at[..., :w].set(bm[..., :w] & ~mi[..., :w])


# ---------------------------------------------------------------------------
# Packed bitsets over row ids.  The filter bitmaps above are the read-only
# instance; the frontier graph engine also keeps its per-query *visited* set
# in the same uint32-word layout (8x less in-flight state than an (n,) bool
# array) and probes it with the same `probe_bitmap`.
# ---------------------------------------------------------------------------

def bitset_words(n: int) -> int:
    """Words needed for a packed bitset over n row ids."""
    return (n + 31) // 32


def bitset_zeros(n: int) -> Array:
    return jnp.zeros((bitset_words(n),), jnp.uint32)


def bitset_mark(words: Array, row_ids: Array, mask: Array) -> Array:
    """Set the bits of `row_ids[mask]` in a packed bitset.

    Contract: the masked ids must be distinct and currently unset (the
    scatter adds each bit's weight, so a repeated or already-set bit would
    carry into neighboring bits).  Every engine call site guarantees this:
    marked nodes are filtered through an unvisited mask and deduplicated
    first.  Negative ids are ignored regardless of `mask`.
    """
    live = mask & (row_ids >= 0)
    safe = jnp.maximum(row_ids, 0)
    bit = jnp.where(live, jnp.uint32(1) << (safe & 31).astype(jnp.uint32),
                    jnp.uint32(0))
    return words.at[(safe >> 5).reshape(-1)].add(bit.reshape(-1))


# ---------------------------------------------------------------------------
# Search statistics — the exact columns of the paper's Table 6, carried as a
# pytree through every jitted search loop.
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SearchStats:
    distance_comps: Array          # scored candidates
    filter_checks: Array           # bitmap probes
    hops: Array                    # graph hops / (leaves scanned for ScaNN)
    page_accesses_index: Array     # index-page analogue accesses (metadata)
    page_accesses_heap: Array      # heap-page analogue accesses (vector rows)
    tmap_lookups: Array            # translation-map lookups (Fig. 13 ablation)
    reorder_rows: Array            # ScaNN reordering candidates (Table 6 col)

    @staticmethod
    def zeros(dtype=jnp.int32) -> "SearchStats":
        z = jnp.zeros((), dtype)
        return SearchStats(z, z, z, z, z, z, z)

    def __add__(self, other: "SearchStats") -> "SearchStats":
        return jax.tree.map(lambda a, b: a + b, self, other)

    def as_dict(self) -> dict[str, Any]:
        return {f.name: np.asarray(getattr(self, f.name)).tolist()
                for f in dataclasses.fields(self)}


@dataclasses.dataclass(frozen=True)
class SearchParams:
    """Run-time knobs (paper §5 'Hyperparameter Tuning')."""

    k: int = 10
    ef_search: int = 64            # result-queue width (HNSW ef / W size)
    beam_width: int = 64           # candidate pool width
    max_hops: int = 512            # safety cap on traversal length
    strategy: str = "sweeping"     # sweeping|acorn|navix|iterative_scan|scann|...
    two_hop: bool = True           # filter-first 2-hop expansion (ACORN/NaviX)
    adaptive_skip_2hop: bool = True  # the paper's "hardened ACORN" optimization
    translation_map: bool = True   # paper §3.1 optimization (i); Fig. 13 ablation
    navix_heuristic: str = "adaptive"  # blind|directed|onehop|adaptive
    # Graph execution engine (DESIGN.md §7): "frontier" advances the whole
    # query batch one superstep at a time with deduplicated union fetches,
    # packed visited bitsets, and chunked need-only scoring; "vmapped" is
    # the legacy per-query beam loop kept as the bit-identical oracle.
    graph_exec_mode: str = "frontier"
    # Quantized graph traversal (DESIGN.md §9): "sq8" makes BOTH graph
    # engines navigate over the store's SQ8 shadow rows (int8 fetches,
    # in-kernel dequant on the Pallas path) and exactly re-score the final
    # result beam from the full-precision heap (ScaNN-reorder-style,
    # counted in reorder_rows + full-width heap pages).  "none" is the
    # classic full-precision traversal — bit-identical to the
    # pre-quantization engines.  Requires a `quantize_store`d VectorStore.
    graph_quant: str = "none"
    # Frontier-engine chunk sizes (DESIGN.md §7): candidates that actually
    # need scoring are compacted and scored `chunk` at a time.  0 = score
    # the full candidate width in one pass (no compaction) — the right
    # call for the (2M,)-wide 1-hop stage, where compaction machinery
    # costs more than the gathers it saves; `frontier_chunk2` sizes the
    # lazy 2-hop chunks of the filter-first strategies, whose (2M·2M)
    # candidate block is mostly never scored.
    frontier_chunk: int = 0
    frontier_chunk2: int = 64
    # ScaNN knobs:
    num_leaves_to_search: int = 32
    reorder_factor: int = 4        # rescoring budget = k * reorder_factor
    # Index-page accounting for the batched ScaNN pipeline (DESIGN.md §5):
    # "batch" charges each quantized leaf page once per opened leaf per
    # query *batch* (attributed to the first query that opens it); the
    # legacy "per_query" mode charges every query for every leaf it opens
    # (the pre-batching semantics — use for Fig. 10/13 reproduction).
    scann_page_accounting: str = "batch"
    # Query-block tiling for the batched ScaNN pipeline (DESIGN.md §4
    # "Scaling envelope"): the (Q, U, C) union-scan block is processed in
    # query tiles of this size so huge batches stay VMEM/HBM-bounded.
    # 0 = one tile (the whole batch).  ids/dists are tile-size-invariant;
    # "batch" index-page accounting amortizes per tile (DESIGN.md §5).
    scann_query_block: int = 0
    # Iterative-scan knobs (pgvector max_scan_tuples analogue):
    batch_tuples: int = 128
    max_rounds: int = 16
    # Anytime budgets (DESIGN.md §10).  0 / 0.0 disables a budget and the
    # jitted programs are identical to the unbudgeted ones (the predicate
    # is only traced when a budget is set, so zero-budget runs stay
    # bit-identical to pre-budget behavior).  A query that stops on a
    # budget keeps its best-so-far beam; the executor surfaces per-query
    # truncation flags in SearchResult.anytime (costmodel.evaluate_anytime).
    page_budget: int = 0           # stop once index+heap page accesses >= budget
    hop_budget: int = 0            # stop once hops >= budget (< max_hops cap)
    deadline_cycles: float = 0.0   # stop once modeled cycles >= deadline
    # Exact full-precision rerank of the SQ8 beam (DESIGN.md §9).  False is
    # the "sq8-no-rerank" degradation rung: quantized distances are
    # returned as-is, saving the full-width heap fetch per result row.
    sq8_rerank: bool = True
    # Mesh-sharded traversal (DESIGN.md §13): all-gather the per-shard
    # top-k beams every E supersteps.  1 = lockstep mode — every candidate
    # is resolved collectively each hop and results are bit-identical to
    # the single-device engine for any shard count; E > 1 lets each shard
    # drift on its induced subgraph between exchanges (cheaper collectives,
    # approximate results).  Ignored by single-device executors.
    beam_exchange_interval: int = 1
    # FAVOR-style exclusion pruning (DESIGN.md §14): "prune" gates pool
    # insertion in the sweeping frontier engine on precomputed per-node
    # exclusion radii (core/exclusion.py) — a candidate whose nearest
    # passing row provably (in root space, up to `exclusion_margin`) cannot
    # beat the current W tail is dropped before it is ever popped, so its
    # whole branch costs no filter checks, no expansions, no pages.
    # "none" traces nothing and is bit-identical to the pre-exclusion
    # engine (the graph_quant="none" convention).  "prune_exact" is the
    # same traversal with FAVOR's probe-free accounting: the radius test
    # replaces the bitmap probe for pruned candidates, so they are not
    # charged filter checks — sound ONLY with family-exact radii (e = 0
    # iff the row passes; the caller owns that contract).  l2 + frontier
    # + sweeping only; requires `excl=` radii at the search_batch call.
    exclusion: str = "none"
    # Prune aggressiveness: keep a candidate v iff pass(v) or
    # sqrt(e(v)) <= margin * (sqrt(d(q,v)) + sqrt(tau)), tau = W tail.
    # margin >= 1.0 with exact family radii provably never prunes
    # (triangle inequality); < 1.0 trades recall for pruned branches.
    exclusion_margin: float = 0.5


@dataclasses.dataclass
class AnytimeInfo:
    """Per-query anytime-execution flags (DESIGN.md §10), derived
    host-side from the final SearchStats counters (`costmodel.
    evaluate_anytime`) — never carried through a jitted loop.

    truncated: the query stopped before its stop condition converged
    (budget hit OR the max_hops/max_rounds safety cap fired); its
    ids/dists are still the best-so-far beam.
    budget_exhausted: a user-set budget (page/hop/deadline or a
    plan-level clamp) specifically caused the stop.
    completion: fraction of the k result slots holding a valid row id —
    the uniform "how much of the answer did I get" measure across all
    executors (1.0 = full top-k, possibly still truncated-but-converged).
    """

    truncated: np.ndarray          # (Q,) bool
    budget_exhausted: np.ndarray   # (Q,) bool
    completion: np.ndarray         # (Q,) f32 in [0, 1]

    def as_dict(self) -> dict[str, Any]:
        return dict(truncated=self.truncated.tolist(),
                    budget_exhausted=self.budget_exhausted.tolist(),
                    completion=self.completion.tolist())


@dataclasses.dataclass
class SearchResult:
    """Unified return convention of every executor (DESIGN.md §6).

    ids/dists: (Q, k), ids -1-padded where fewer than k rows pass.
    stats: per-query SearchStats ((Q,) leaves), or None when the backend
    cannot carry counters (e.g. the collective distributed path).
    strategy: the strategy that actually executed (for the AdaptivePlanner
    this is the *chosen* fixed strategy, not "adaptive").
    plan: the SearchPlan that produced this result (selectivity estimates,
    predicted cycles — executor.py).
    storage: measured storage telemetry (storage.StorageStats) when the
    executor ran with a StorageEngine attached; None otherwise.
    anytime: per-query AnytimeInfo flags when the executor derives them
    (all local executors do); None on backends without counters.
    """

    dists: Array
    ids: Array
    stats: Optional[SearchStats]
    strategy: str
    plan: Any = None
    storage: Any = None
    anytime: Any = None


def topk_smallest(values: Array, k: int) -> tuple[Array, Array]:
    """(values, indices) of the k smallest entries. jnp.top_k on negated vals."""
    neg, idx = jax.lax.top_k(-values, k)
    return -neg, idx


@partial(jax.jit, static_argnames=("k",))
def merge_topk(dists_a: Array, ids_a: Array, dists_b: Array, ids_b: Array,
               k: int) -> tuple[Array, Array]:
    """K-way merge of two top-k result sets into one (Q, k) top-k — the
    `MergedResult` primitive fusing a base executor's answer with the
    delta tier's exact scan (DESIGN.md §12).

    Inputs are (Q, ka)/(Q, kb) dists with -1-padded ids; padded slots must
    carry +inf dists (every executor's contract).  Concat order is
    (a then b): `lax.top_k` breaks exact ties by position, and since base
    ids are always < delta ids, passing the base result as `a` reproduces
    the id-ascending tie order of a from-scratch rebuild oracle —
    bit-identical merges, not approximately-equal ones.  Slots beyond the
    number of finite candidates come back as (+inf, -1)."""
    dists = jnp.concatenate([dists_a, dists_b], axis=-1)
    ids = jnp.concatenate([ids_a, ids_b], axis=-1)
    best, pos = topk_smallest(dists, k)
    out_ids = jnp.take_along_axis(ids, pos, axis=-1)
    return best, jnp.where(jnp.isinf(best), -1, out_ids)


@partial(jax.jit, static_argnames=("k",))
def recall_at_k(found_ids: Array, true_ids: Array, k: int) -> Array:
    """|found ∩ true| / k for one query. ids may contain -1 padding."""
    f = found_ids[..., :k]
    t = true_ids[..., :k]
    eq = (f[..., :, None] == t[..., None, :]) & (f[..., :, None] >= 0)
    return eq.any(-1).sum(-1).astype(jnp.float32) / k
