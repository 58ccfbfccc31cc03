"""System-tax cost model (paper §3.4, §6.2, Fig. 10).

Two modes share one set of per-operation constants:

  post-hoc     — `cycle_breakdown` translates MEASURED SearchStats counters
                 into modeled CPU cycles (Fig. 10 bars, Table 7 rows);
  predictive   — `predict_counters`/`predict_cycles` produce closed-form
                 EXPECTED counters per strategy as a function of
                 (n, dim, selectivity estimate, correlation proxy, index
                 shape), before running anything.  This is what turns the
                 paper's "the best strategy is a system-aware decision"
                 finding (Fig. 1 crossover, §6.2) into an actual planner:
                 `executor.AdaptivePlanner` evaluates `predict_cycles` for
                 every registered strategy per query batch and dispatches
                 to the argmin.  Equations in DESIGN.md §6.

The constants translate counters into cycles under two regimes:

  SYSTEM  — PostgreSQL-like page engine: every page access pays buffer-pool
            lookup + pin + shared lock + release; every scored vector pays
            tuple materialization (palloc + copy); heaptid resolution costs
            a translation-map hash probe (if enabled) or an index-page
            access (if not — the Fig. 13 ablation).
  LIBRARY — HNSWLib-like flat memory: neighbor access is a pointer
            dereference, no locks, unified ids (no translation).

Defaults are calibrated so an OpenAI-5M-shaped workload (d=1536, graph
M=32) reproduces the paper's Fig. 10 component shares (system overheads
dominating; vector-retrieval ≈ 300M cycles for Sweeping at 1 % selectivity)
and Table 2's Dist/Filt relative-cost column. The same counters under the
two regimes reproduce Fig. 1's crossover-point shift.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Optional

import numpy as np

from repro import obs
from repro.core.types import (AnytimeInfo, SearchParams, SearchStats,
                              heap_pages_per_vector,
                              quant_heap_pages_per_vector)


@dataclasses.dataclass(frozen=True)
class CostConstants:
    page_access: float          # buffer HIT: pin + lock + read + release
    tuple_materialize: float    # palloc + copy, per byte
    distance_per_dim: float     # SIMD distance cycles per dimension
    filter_check: float         # bitmap probe
    tmap_lookup: float          # in-memory hash probe
    reorder_sort_per_row: float  # reordering sort/merge work
    # Buffer-pool MISS multiplier (DESIGN.md §8): a missed page costs
    # page_access * page_miss_extra (read into shared buffers from the
    # OS cache / storage).  1.0 = flat memory, no pool.
    page_miss_extra: float = 1.0
    # Mesh-sharded traversal (DESIGN.md §13): cycles per byte moved by
    # the beam-exchange collectives.  ICI roofline is ~6 B/cycle
    # (~0.17 cy/B); padded for launch latency + the small-message regime
    # the per-hop reductions live in.  Single-device predictions never
    # read it (the collective volume is 0 at num_shards == 1).
    collective_per_byte: float = 0.5


# Calibrated to reproduce Fig. 10 / Table 2 shapes (see module docstring).
SYSTEM = CostConstants(
    page_access=2400.0,        # buffer lookup ~ few hundred ns @ ~3 GHz
    tuple_materialize=0.25,    # per byte copied into query context
    distance_per_dim=2.0,      # scalar-ish per-dim cost inside PG fmgr
    filter_check=18.0,
    tmap_lookup=40.0,
    reorder_sort_per_row=60.0,
    page_miss_extra=10.0,      # OS-page-cache read ~ few µs vs ~100s ns hit
)

LIBRARY = CostConstants(
    page_access=12.0,          # pointer dereference + cache miss amortized
    tuple_materialize=0.0,     # zero-copy
    distance_per_dim=0.5,      # SIMD-optimized distance
    filter_check=15.0,         # bitmap probe cost is architecture-neutral
    tmap_lookup=0.0,           # unified identifiers
    reorder_sort_per_row=30.0,
    page_miss_extra=1.0,       # flat memory: nothing to miss
)


GRAPH_STRATEGIES = ("unfiltered", "sweeping", "acorn", "navix",
                    "iterative_scan")

# Frontier-engine page-cost amortization (DESIGN.md §7): the batch-
# synchronous engine fetches each superstep's candidate union once for the
# whole batch (measured unique-fetch fraction ≈ 0.83–0.93 for 32 distinct
# queries on the bench workloads) and runs the fetch+probe as batched
# gathers instead of Q per-query scalar chains — together the effective
# per-page cost lands at roughly half the per-query engine's (the ≥3×
# wall-clock win in BENCH_frontier.json is page/fetch-side; distance FLOPs
# and filter probes are counter-for-counter unchanged).  A single query
# amortizes nothing (engine_scale returns None at batch_q ≤ 1).
FRONTIER_PAGE_AMORT = 0.5
# The unique-fetch fraction FRONTIER_PAGE_AMORT was calibrated against
# (measured 0.83–0.93 for 32 distinct queries — DESIGN.md §7; midpoint).
# When a StorageEngine measures the batch's actual page-sharing
# (StorageStats.unique_fraction), the amortization becomes a per-batch
# measurement: amort = FRONTIER_PAGE_AMORT · measured / CALIB — e.g. a
# centroid-routed batch whose queries share most pages measures a low
# unique fraction and earns a proportionally deeper discount
# (ROADMAP "storage-engine follow-ups").
FRONTIER_CALIB_UNIQUE = 0.88


def engine_scale(strategy: str, params: SearchParams,
                 batch_q: int = 1,
                 measured_unique_frac: Optional[float] = None
                 ) -> Optional[dict[str, float]]:
    """Per-component cycle multipliers for the execution engine that will
    actually run `strategy` (None = legacy per-query costs).  Applied
    identically by the planner's predictions and the post-hoc breakdowns
    so regret accounting stays in one currency.

    `measured_unique_frac` — a pool-measured per-batch unique-fetch
    fraction (StorageStats.unique_fraction) — replaces the
    FRONTIER_PAGE_AMORT constant with the measured amortization, anchored
    at the constant's calibration point (FRONTIER_CALIB_UNIQUE)."""
    if strategy not in GRAPH_STRATEGIES or batch_q <= 1:
        return None
    if params.graph_exec_mode != "frontier":
        return None
    amort = FRONTIER_PAGE_AMORT
    if measured_unique_frac is not None:
        amort = min(1.0, max(
            0.05, FRONTIER_PAGE_AMORT * measured_unique_frac
            / FRONTIER_CALIB_UNIQUE))
    return {"index_page_access": amort, "vector_retrieval": amort}


def component_cycles(counters: Mapping[str, float], dim: int,
                     constants: CostConstants = SYSTEM,
                     scale: Optional[Mapping[str, float]] = None,
                     graph_quant: str = "none") -> dict[str, float]:
    """Per-component modeled cycles for one query from a counter mapping
    (the Table 6 column names).  Shared by the post-hoc path (measured
    counters) and the predictive path (closed-form expected counters).
    `scale` (see `engine_scale`) multiplies named components — the
    engine-mode-aware weights.

    `graph_quant="sq8"` (DESIGN.md §9) prices the quantized-traversal
    tier: traversal rows materialize 1 byte/dim (int8 shadow rows)
    instead of 4, while the `reorder_rows` exact-rerank fetches stay
    full-width — page *hit* costs are unchanged (a logical access pins a
    page either way); the density win lands in the measured/predicted
    MISS side (`cache_miss_penalty`)."""
    vec_bytes = dim * 4
    if graph_quant == "sq8":
        rr = counters["reorder_rows"]
        trav_dc = max(counters["distance_comps"] - rr, 0.0)
        materialize = (trav_dc * dim + rr * vec_bytes) \
            * constants.tuple_materialize
    else:
        materialize = counters["distance_comps"] * vec_bytes \
            * constants.tuple_materialize
    comp = {
        "index_page_access": counters["page_accesses_index"]
        * constants.page_access,
        "vector_retrieval": counters["page_accesses_heap"]
        * constants.page_access + materialize,
        "distance_compute": counters["distance_comps"] * dim
        * constants.distance_per_dim,
        "filter_checks": counters["filter_checks"] * constants.filter_check,
        "translation_map": counters["tmap_lookups"] * constants.tmap_lookup,
        "reordering": counters["reorder_rows"]
        * constants.reorder_sort_per_row,
    }
    if scale:
        for k, f in scale.items():
            comp[k] *= f
    comp["total"] = sum(comp.values())
    return comp


# Which page segment (storage/engine.py) holds a strategy's *index* pages;
# every strategy's row fetches hit the "heap" segment.
def index_segment(strategy: str) -> Optional[str]:
    if strategy == "scann":
        return "scann"
    if strategy in GRAPH_STRATEGIES:
        return "graph"
    return None                     # bruteforce: seqscan, no index


def cache_miss_penalty(counters: Mapping[str, float], strategy: str,
                       pool_state, constants: CostConstants = SYSTEM,
                       graph_quant: str = "none",
                       dim: Optional[int] = None) -> float:
    """Expected extra cycles from buffer-pool misses, per query
    (DESIGN.md §8).  `pool_state` is a storage.BufferPoolState; the
    expected miss fraction of a segment's accesses is 1 − residency
    (uniform-touch approximation).  With page_miss_extra == 1 (LIBRARY)
    or a fully warm pool this is 0 and predictions reduce to the classic
    ones.

    Under graph_quant="sq8" (needs `dim`), the traversal's row fetches
    probe the dense "qheap" shadow segment — 4× fewer pages, so it warms
    ~4× faster and its residency-driven miss fraction drops sooner —
    while the rerank's full-width fetches (`reorder_rows` pages) probe
    "heap" (DESIGN.md §9)."""
    if pool_state is None or constants.page_miss_extra <= 1.0:
        return 0.0
    extra = constants.page_access * (constants.page_miss_extra - 1.0)
    if graph_quant == "sq8" and dim is not None:
        rr_pages = counters["reorder_rows"] * heap_pages_per_vector(dim)
        trav_pages = max(counters["page_accesses_heap"] - rr_pages, 0.0)
        pen = trav_pages * pool_state.miss_fraction("qheap") * extra \
            + rr_pages * pool_state.miss_fraction("heap") * extra
    else:
        pen = counters["page_accesses_heap"] * \
            pool_state.miss_fraction("heap") * extra
    seg = index_segment(strategy)
    if seg is not None:
        pen += counters["page_accesses_index"] * \
            pool_state.miss_fraction(seg) * extra
    return pen


def measured_miss_penalty(storage_stats, batch_q: int,
                          constants: CostConstants = SYSTEM) -> float:
    """Per-query extra cycles from MEASURED pool misses (a
    storage.StorageStats) — the post-hoc currency matching
    `cache_miss_penalty`'s predictions, for warm-cache regret accounting
    (benchmarks/bench_storage.py)."""
    extra = constants.page_access * (constants.page_miss_extra - 1.0)
    return storage_stats.miss_total * extra / max(batch_q, 1)


def cycle_breakdown(stats: SearchStats, dim: int,
                    constants: CostConstants = SYSTEM,
                    scale: Optional[Mapping[str, float]] = None,
                    graph_quant: str = "none") -> dict[str, float]:
    """Per-component modeled cycles for one query (Fig. 10 bars)."""
    s = {k: float(np.asarray(v).mean()) for k, v in stats.as_dict().items()} \
        if _is_batched(stats) else {k: float(np.asarray(v))
                                    for k, v in stats.as_dict().items()}
    return component_cycles(s, dim, constants, scale, graph_quant)


def _is_batched(stats: SearchStats) -> bool:
    return np.asarray(stats.distance_comps).ndim > 0


def modeled_qps(stats: SearchStats, dim: int,
                constants: CostConstants = SYSTEM,
                clock_hz: float = 3.0e9, threads: int = 16,
                thread_overhead: Mapping[int, float] | None = None) -> float:
    """Modeled queries/second at a given concurrency.

    `thread_overhead` models the paper's Table 7 contention amplification
    (cycles inflate with concurrency); default +50 % at 16T.
    """
    cycles = cycle_breakdown(stats, dim, constants)["total"]
    amp = 1.0
    if threads > 1:
        amp = (thread_overhead or {16: 1.5}).get(threads, 1.5)
    per_query_s = cycles * amp / clock_hz
    return threads / per_query_s


def stats_table_row(stats: SearchStats) -> dict[str, float]:
    """Mean counters over a query batch — one row of the paper's Table 6."""
    return {k: float(np.asarray(v).mean())
            for k, v in stats.as_dict().items()}


# ---------------------------------------------------------------------------
# Mesh-sharded traversal terms (DESIGN.md §13).
#
# The sharded frontier engine's extra cost over 1/S of the single-device
# cycles is pure collective volume, in two regimes:
#
#   lockstep (E=1):  every superstep all-reduces the candidate block's
#       owner-masked distances (pmin, f32) and adjacency entries (pmax,
#       int32) — 8 B per scored candidate, moved ~2·(S-1)/S times by a
#       ring all-reduce.  distance_comps counts exactly those candidates.
#   drift (E>1):     every E supersteps each shard all-gathers the other
#       shards' (dist, id) beams — ef_search · 8 B · (S-1) received per
#       exchange, ceil(hops/E) exchanges.
# ---------------------------------------------------------------------------

def beam_exchange_bytes(counters: Mapping[str, float], params: SearchParams,
                        num_shards: int) -> float:
    """Per-query collective bytes of the sharded frontier engine."""
    S = int(num_shards)
    if S <= 1:
        return 0.0
    E = max(1, int(params.beam_exchange_interval))
    if E == 1:
        return 8.0 * counters["distance_comps"] * 2.0 * (S - 1) / S
    exchanges = -(-counters["hops"] // E)
    return 8.0 * params.ef_search * exchanges * (S - 1)


def sharded_cycle_summary(stats: SearchStats, params: SearchParams,
                          dim: int, num_shards: int,
                          constants: CostConstants = SYSTEM,
                          graph_quant: str = "none",
                          per_shard_storage=None, batch_q: int = 1,
                          clock_hz: float = 3.0e9, threads: int = 16
                          ) -> dict[str, float]:
    """Aggregate modeled cost of one sharded batch (bench_sharding.py).

    The single-device cycle total parallelizes across shards (each shard
    scores/fetches only its owned rows); on top ride the beam-exchange
    collective term and — when the per-shard StorageStats from a
    `ShardedStorageAccountant` replay are given — a straggler term: the
    batch finishes with the SLOWEST shard's measured miss penalty, not
    the mean (`max - mean` of the per-shard penalties).  Returns the
    per-point record the sharding bench emits: cycles/query, collective
    bytes + cycles, straggler extra, and aggregated modeled QPS."""
    row = stats_table_row(stats)
    base = component_cycles(row, dim, constants,
                            graph_quant=graph_quant)["total"]
    cbytes = beam_exchange_bytes(row, params, num_shards)
    ccycles = cbytes * constants.collective_per_byte
    straggler = 0.0
    if per_shard_storage:
        pens = [measured_miss_penalty(p, batch_q, constants)
                for p in per_shard_storage]
        straggler = max(pens) - float(np.mean(pens))
    cycles = base / max(int(num_shards), 1) + ccycles + straggler
    amp = 1.0 if threads <= 1 else 1.5
    qps = threads / (cycles * amp / clock_hz)
    return {"cycles_per_query": cycles, "base_cycles": base,
            "collective_bytes": cbytes, "collective_cycles": ccycles,
            "straggler_cycles": straggler, "modeled_qps": qps}


# ---------------------------------------------------------------------------
# Predictive mode (DESIGN.md §6).
#
# Closed-form EXPECTED Table 6 counters per strategy, as a function of the
# dataset/index shape, a per-batch selectivity estimate s (bitmap popcount
# / n) and a correlation proxy γ (local selectivity around the query ÷
# global selectivity; >1 = positively correlated predicate).  The effective
# selectivity s̃ = clip(s·γ, 1/n, 1) is what graph traversal locally sees.
#
# Calibration anchors (measured on the repo's strategies, see
# tests/test_executor.py and DESIGN.md §6 for the derivations):
#   * sweeping visits ~ef/s̃ hops before W fills with passing rows;
#   * iterative scan emits ~k/s̃ candidates before k pass the post-filter,
#     in batches of `batch_tuples`;
#   * each traversal hop newly scores ~GRAPH_NEW_PER_HOP rows (the rest of
#     the 2M neighborhood is already visited);
#   * filter-first checks all 2M 1-hop neighbors per hop and 2M more per
#     EXPANDED branch — non-passing branches under the hardened-ACORN skip,
#     a heuristic-gated fraction for NaviX.
# ---------------------------------------------------------------------------

GRAPH_NEW_PER_HOP = 2.5     # newly scored rows per hop (visited overlap)
SWEEP_FC_PER_DC = 0.6       # would-enter-W checks per scored row
NAVIX_EXPAND_FRAC = 0.5     # adaptive-heuristic 2-hop gating vs ACORN's 1.0
FILTER_FIRST_HOPS = 1.06    # hops ≈ FILTER_FIRST_HOPS · ef when connected
FILTER_FIRST_POOL = 0.7     # subgraph-exhaustion cap: hops ≤ 0.7·n·s̃
ITER_HOP_FACTOR = 1.6       # iterative-scan hops per emitted candidate
ITER_HOP_BASE = 40.0        # beam settle-down tail per scan round-trip

# Selectivity-aware tiers (DESIGN.md §14).  The exclusion-pruned sweeping
# law scales sweeping's hop count by an expected keep fraction: pruning
# only bites when the predicate is spatially clustered (γ > 1 — exclusion
# radii carry signal exactly when passing rows cluster), and bites harder
# the sparser the predicate.  EXCL_PRUNE_MAX is calibrated against the
# bench_filtercost clustered-family measurements (hop ratios 0.52–0.68 at
# s ∈ {0.02, 0.05}, margin 0.3).  At γ ≤ 1 the law degrades EXACTLY to
# sweeping's — an uncorrelated bitmap carries no exclusion signal, and the
# prediction must not promise savings the radii cannot deliver.
EXCL_PRUNE_MAX = 0.4        # asymptotic pruned hop fraction (γ → ∞)
# The partitioned tier's plan-time family match compares each query's
# bitmap against every registered family, word by word; the planner has
# no handle on the family count at predict time, so the law prices a
# nominal catalog.
PART_FAMILIES_EST = 4.0     # families assumed registered, for match fc
# One-off subgraph build work (≈ rows · ef_construction · 2 distance
# comps per inserted row), amortized per query over the horizon a hot
# predicate family is expected to serve before the partition goes stale.
PART_BUILD_DC_PER_ROW = 64.0
PART_AMORT_QUERIES = 50_000.0

PREDICTABLE_STRATEGIES = ("bruteforce", "scann", "sweeping", "acorn",
                          "navix", "iterative_scan", "unfiltered",
                          "sweeping_excl", "partitioned")

# Predictive-kind → graph-strategy family, for engine/quant/segment
# resolution: the exclusion tier runs the sweeping machinery, the
# partitioned tier runs unfiltered machinery on a subgraph.
GRAPH_KIND_ALIAS = {"sweeping_excl": "sweeping", "partitioned": "unfiltered"}


@dataclasses.dataclass(frozen=True)
class IndexShape:
    """Static shape facts the predictive model needs (SYSTEM-agnostic)."""

    n: int
    dim: int
    graph_m: int = 16                    # HNSW M; level-0 degree = 2M
    scann_leaves: Optional[int] = None   # L
    scann_rows_per_leaf: Optional[int] = None    # C (capacity, padded)
    scann_cent_scored: Optional[int] = None      # centroids scored (①+②)
    scann_pages_per_leaf: int = 1


def predict_counters(strategy: str, shape: IndexShape, params: SearchParams,
                     selectivity: float, correlation: float = 1.0,
                     batch_q: int = 1) -> dict[str, float]:
    """Expected per-query Table 6 counters for `strategy` (DESIGN.md §6).

    `batch_q` matters for scann under "batch" page accounting (DESIGN.md
    §5): the batched pipeline opens each leaf once per *batch*, so the
    expected per-query index pages shrink to E[unique leaves]/Q — with
    leaf choices modeled as uniform draws, E[unique] = L·(1−(1−nl/L)^Q).
    All other counters are per-query quantities under both modes."""
    n, k = shape.n, params.k
    ppv = heap_pages_per_vector(shape.dim)
    s = min(max(selectivity, 1.0 / n), 1.0)
    s_eff = min(max(s * max(correlation, 1e-3), 1.0 / n), 1.0)
    c = dict(distance_comps=0.0, filter_checks=0.0, hops=0.0,
             page_accesses_index=0.0, page_accesses_heap=0.0,
             tmap_lookups=0.0, reorder_rows=0.0)

    if strategy == "bruteforce":
        # seqscan over the bitmap: probe every row, fetch+score the passing
        c["filter_checks"] = float(n)
        c["distance_comps"] = s * n
        c["page_accesses_heap"] = s * n * ppv
        return c

    if strategy == "scann":
        if shape.scann_leaves is None or shape.scann_rows_per_leaf is None:
            raise ValueError("scann prediction needs scann_* shape facts")
        nl = min(params.num_leaves_to_search, shape.scann_leaves)
        rows = nl * shape.scann_rows_per_leaf
        r = min(k * params.reorder_factor, rows)
        cent = shape.scann_cent_scored or shape.scann_leaves
        c["filter_checks"] = float(rows)
        c["distance_comps"] = s_eff * rows + cent + r
        c["hops"] = float(nl)
        leaves_per_q = float(nl)
        if params.scann_page_accounting == "batch" and batch_q > 1:
            lf = float(shape.scann_leaves)
            uniq = lf * (1.0 - (1.0 - nl / lf) ** batch_q)
            leaves_per_q = min(uniq / batch_q, float(nl))
        c["page_accesses_index"] = leaves_per_q * shape.scann_pages_per_leaf
        c["page_accesses_heap"] = float(r * ppv)
        c["reorder_rows"] = float(r)
        return c

    deg = 2.0 * shape.graph_m
    ef = max(params.ef_search, 2 * k)
    tm = 1.0 if params.translation_map else 0.0

    def graph_quant_rerank(c: dict, r: float) -> dict:
        """SQ8 quantized-traversal transform (DESIGN.md §9): traversal
        rows fetch shadow pages (quant ppv), and the exact rerank of ~r
        beam entries adds r distance comps + r full-width heap pages,
        counted in reorder_rows — mirroring the engines' accounting."""
        if params.graph_quant != "sq8":
            return c
        qppv = quant_heap_pages_per_vector(shape.dim)
        trav_rows = c["page_accesses_heap"] / ppv
        c["page_accesses_heap"] = trav_rows * qppv + r * ppv
        c["distance_comps"] += r
        c["reorder_rows"] = r
        return c

    if strategy in ("sweeping", "unfiltered"):
        # traversal-first: W fills once ~ef passing rows were seen, and the
        # traversal sees passing rows at rate s̃ → ~ef/s̃ hops (capped by
        # max_hops and by graph exhaustion: ≲ n/NEW hops score all n rows).
        s_nav = 1.0 if strategy == "unfiltered" else s_eff
        hops = min(ef / s_nav, float(params.max_hops), n / GRAPH_NEW_PER_HOP)
        dc = min(GRAPH_NEW_PER_HOP * hops + ef, float(n))
        fc = 0.0 if strategy == "unfiltered" else SWEEP_FC_PER_DC * dc
        c.update(distance_comps=dc, filter_checks=fc, hops=hops,
                 page_accesses_index=hops + (1 - tm) * fc,
                 page_accesses_heap=dc * ppv, tmap_lookups=tm * fc)
        return graph_quant_rerank(c, float(ef))

    if strategy == "sweeping_excl":
        # FAVOR exclusion-pruned sweeping (DESIGN.md §14): sweeping's law
        # with hops scaled by the expected keep fraction.  corr_gain → 0
        # at γ ≤ 1 (uncorrelated radii prune nothing, the tier prices
        # exactly like sweeping) and → 1 as γ → ∞; sparser predicates
        # prune a larger branch fraction.  fc takes the same keep-fraction
        # discount — the prune_exact accounting's eliminated probes.
        corr_gain = max(0.0, 1.0 - 1.0 / max(correlation, 1.0))
        prune = EXCL_PRUNE_MAX * corr_gain * (1.0 - s)
        hops = min(ef / s_eff, float(params.max_hops),
                   n / GRAPH_NEW_PER_HOP) * (1.0 - prune)
        dc = min(GRAPH_NEW_PER_HOP * hops + ef, float(n))
        fc = SWEEP_FC_PER_DC * dc * (1.0 - prune)
        c.update(distance_comps=dc, filter_checks=fc, hops=hops,
                 page_accesses_index=hops + (1 - tm) * fc,
                 page_accesses_heap=dc * ppv, tmap_lookups=tm * fc)
        return graph_quant_rerank(c, float(ef))

    if strategy == "partitioned":
        # JAG attribute-partitioned subgraph (DESIGN.md §14): unfiltered
        # traversal over a private graph of n_f = s·n passing rows.  The
        # only filter work is the plan-time family match (every query's
        # bitmap against ~PART_FAMILIES_EST family bitmaps, n/32 words
        # each); per-candidate checks are gone by construction.  Build
        # amortization rides in predict_cycles (a cycle, not a counter).
        n_f = max(s * n, float(k))
        hops = min(float(ef), float(params.max_hops),
                   n_f / GRAPH_NEW_PER_HOP)
        dc = min(GRAPH_NEW_PER_HOP * hops + ef, n_f)
        fc = PART_FAMILIES_EST * math.ceil(n / 32)
        c.update(distance_comps=dc, filter_checks=fc, hops=hops,
                 page_accesses_index=hops,
                 page_accesses_heap=dc * ppv)
        return graph_quant_rerank(c, float(ef))

    if strategy == "iterative_scan":
        # pgvector post-filter: emit batches of `batch_tuples` unfiltered
        # candidates until k pass — E[emitted] ≈ k/s̃, rounded up to whole
        # batches, capped by the round budget.
        bt = params.batch_tuples
        emitted = float(min(bt * np.ceil((k / s_eff) / bt),
                            bt * params.max_rounds))
        hops = min(ITER_HOP_FACTOR * emitted + ITER_HOP_BASE,
                   float(params.max_hops), n / GRAPH_NEW_PER_HOP)
        dc = min(GRAPH_NEW_PER_HOP * hops, float(n))
        c.update(distance_comps=dc, filter_checks=emitted, hops=hops,
                 page_accesses_index=hops + (1 - tm) * emitted,
                 page_accesses_heap=dc * ppv, tmap_lookups=tm * emitted)
        return graph_quant_rerank(
            c, float(min(k * params.reorder_factor, emitted)))

    if strategy in ("acorn", "navix"):
        # filter-first: traversal stays on the predicate subgraph — hop
        # count is ~ef until the subgraph runs out of nodes; every hop
        # checks the full 1-hop neighborhood and 2M more per expanded
        # branch (hardened-ACORN expands the non-passing (1-s̃) fraction,
        # NaviX's adaptive heuristic a further NAVIX_EXPAND_FRAC of that).
        gate = 1.0 if strategy == "acorn" else NAVIX_EXPAND_FRAC
        if strategy == "navix" and s_eff > 0.35:
            gate = 0.05                      # adaptive-local: onehop zone
        hops = min(FILTER_FIRST_HOPS * ef, FILTER_FIRST_POOL * n * s_eff)
        hops = max(hops, 1.0)
        expand = deg * (1.0 - s_eff) * gate  # branches expanded per hop
        fc = hops * (deg + expand * deg)
        dc = min(hops * GRAPH_NEW_PER_HOP * (1.0 + gate), float(n))
        c.update(distance_comps=dc, filter_checks=fc, hops=hops,
                 page_accesses_index=hops * (1.0 + expand) + (1 - tm) * fc,
                 page_accesses_heap=dc * ppv, tmap_lookups=tm * fc)
        return graph_quant_rerank(c, float(ef))

    raise ValueError(f"no predictive model for strategy {strategy!r}")


def predict_cycles(strategy: str, shape: IndexShape, params: SearchParams,
                   selectivity: float, correlation: float = 1.0,
                   constants: CostConstants = SYSTEM,
                   batch_q: int = 1, pool_state=None,
                   measured_unique_frac: Optional[float] = None,
                   num_shards: int = 1) -> float:
    """Expected per-query modeled cycles (the planner's ranking metric).

    `batch_q` is the size of the query batch the plan will execute with:
    graph strategies under the frontier engine amortize page costs across
    the batch (`engine_scale`), and scann under "batch" accounting opens
    each leaf once per batch (`predict_counters`), so the planner's
    graph-vs-scann decision boundary tracks the engines that will
    actually run.

    `pool_state` (a storage.BufferPoolState) makes the prediction
    warm-cache-aware: expected buffer-pool misses — scaled by each
    segment's current residency — pay `page_miss_extra` on top of the hit
    cost (`cache_miss_penalty`).  None keeps the classic cold-blind
    prediction.

    `measured_unique_frac` feeds a pool-measured per-batch page-sharing
    fraction into `engine_scale`, replacing the FRONTIER_PAGE_AMORT
    constant with the measured amortization for frontier-engine graph
    strategies.  `params.graph_quant` ("sq8") prices the quantized
    traversal tier: cheaper int8 materialization + rerank surcharge
    (`component_cycles`), shadow-segment miss modeling
    (`cache_miss_penalty`)."""
    counters = predict_counters(strategy, shape, params, selectivity,
                                correlation, batch_q)
    # the selectivity-aware tiers run existing graph machinery (exclusion
    # = sweeping engine, partitioned = unfiltered on a subgraph), so
    # engine amortization, quant pricing, and segment attribution all
    # resolve through the aliased family
    gstrat = GRAPH_KIND_ALIAS.get(strategy, strategy)
    gq = params.graph_quant if gstrat in GRAPH_STRATEGIES else "none"
    base = component_cycles(
        counters, shape.dim, constants,
        engine_scale(gstrat, params, batch_q, measured_unique_frac),
        graph_quant=gq)["total"]
    total = base + cache_miss_penalty(counters, gstrat, pool_state,
                                      constants, graph_quant=gq,
                                      dim=shape.dim)
    if strategy == "partitioned":
        # one-off subgraph build work amortized per served query — keeps
        # the tier honest against a strategy that needs no extra artifact
        n_f = max(selectivity * shape.n, float(params.k))
        total += n_f * PART_BUILD_DC_PER_ROW * shape.dim \
            * constants.distance_per_dim / PART_AMORT_QUERIES
    if num_shards > 1 and gstrat in GRAPH_STRATEGIES:
        # Mesh-sharded frontier (DESIGN.md §13): scoring, fetches, and
        # the per-shard page streams all parallelize by row ownership;
        # the beam-exchange collective volume is the serial residue.
        total = total / num_shards \
            + beam_exchange_bytes(counters, params, num_shards) \
            * constants.collective_per_byte
    return total


# ---------------------------------------------------------------------------
# Anytime budgets (DESIGN.md §10).
#
# The deadline budget needs a cycle estimate INSIDE the jitted traversal
# loops, so it is priced with a pure linear form of the Table 6 counters —
# exactly `component_cycles` at scale=None / graph_quant="none", whose
# terms are all counter-proportional.  The post-hoc flag derivation
# (`evaluate_anytime`) applies the SAME weights to the final counters, so
# "the loop's deadline predicate fired" and "linear_cycles >= deadline"
# agree bit-for-bit for full-precision traversal.  Under sq8-with-rerank
# the post-loop exact rerank adds counters after the budget check, so the
# budget covers TOTAL per-query work and the flags are conservative
# (never a missed truncation; see DESIGN.md §10).
# ---------------------------------------------------------------------------

def budget_cycle_weights(dim: int, constants: CostConstants = SYSTEM
                         ) -> dict[str, float]:
    """Per-counter cycle weights of the linear cost form: cycles =
    Σ counter · weight.  Matches component_cycles(scale=None,
    graph_quant="none") exactly.  Plain python floats — safe to close
    over inside a jitted loop predicate."""
    return {
        "distance_comps": dim * constants.distance_per_dim
        + dim * 4 * constants.tuple_materialize,
        "filter_checks": constants.filter_check,
        "hops": 0.0,
        "page_accesses_index": constants.page_access,
        "page_accesses_heap": constants.page_access,
        "tmap_lookups": constants.tmap_lookup,
        "reorder_rows": constants.reorder_sort_per_row,
    }


def linear_cycles(stats: SearchStats, dim: int,
                  constants: CostConstants = SYSTEM) -> np.ndarray:
    """Per-query modeled cycles under the linear budget form — the
    post-hoc mirror of the in-loop deadline predicate (same float32
    arithmetic in the same term order, so flag derivation and the loop's
    stop decision agree at the boundary)."""
    w = budget_cycle_weights(dim, constants)
    d = stats.as_dict()
    out = None
    for name, weight in w.items():
        term = np.asarray(d[name], np.float32) * np.float32(weight)
        out = term if out is None else out + term
    return np.atleast_1d(out)


def evaluate_anytime(stats: Optional[SearchStats], params: SearchParams,
                     dim: int, ids, constants: CostConstants = SYSTEM,
                     hop_cap: Optional[int] = None,
                     extra_truncated: Optional[np.ndarray] = None,
                     extra_budget: Optional[np.ndarray] = None
                     ) -> AnytimeInfo:
    """Derive per-query AnytimeInfo flags from final counters (host-side).

    The graph loops check their stop predicates BEFORE each step, so at
    exit `hops == max_hops` iff the safety cap fired and
    `pages >= page_budget` iff the page predicate fired — the derivation
    is exact for graph_quant="none" (and conservative under
    sq8-with-rerank, whose post-loop rerank counters also count).

    hop_cap: the engine's safety cap (params.max_hops for graph
    executors); None for executors whose `hops` counter is not a
    traversal length (ScaNN counts leaves, bruteforce passing rows).
    extra_truncated / extra_budget: executor-supplied per-query masks for
    truncation the counters cannot show (e.g. a plan-level leaf clamp or
    a bruteforce partial-scan row cap).
    """
    with obs.span("executor.anytime"):
        ids = np.asarray(ids)
        completion = np.mean(ids >= 0, axis=-1, dtype=np.float32)
        completion = np.atleast_1d(completion)
        q = completion.shape[0]
        budget = np.zeros(q, bool)
        truncated = np.zeros(q, bool)
        if stats is not None:
            hops = np.atleast_1d(np.asarray(stats.hops, np.int64))
            pages = np.atleast_1d(
                np.asarray(stats.page_accesses_index, np.int64)
                + np.asarray(stats.page_accesses_heap, np.int64))
            if params.page_budget > 0:
                budget |= pages >= params.page_budget
            if params.hop_budget > 0:
                budget |= hops >= params.hop_budget
            if params.deadline_cycles > 0:
                budget |= linear_cycles(stats, dim, constants) \
                    >= params.deadline_cycles
            if hop_cap is not None:
                truncated |= hops >= hop_cap
        if extra_budget is not None:
            budget |= np.atleast_1d(np.asarray(extra_budget, bool))
        truncated |= budget
        if extra_truncated is not None:
            truncated |= np.atleast_1d(np.asarray(extra_truncated, bool))
        return AnytimeInfo(truncated=truncated, budget_exhausted=budget,
                           completion=completion)


def queueing_delay_cycles(offered_per_cycle: float, service_cycles: float,
                          servers: int) -> float:
    """Expected queueing wait (modeled cycles) at an open-loop arrival
    rate of `offered_per_cycle` requests/cycle against `servers` slots
    each taking `service_cycles` per request.

    Sakasegawa's M/M/c approximation, Lq ≈ ρ^{√(2(c+1))} / (1 − ρ) with
    ρ = λ·S/c and Wq = Lq/λ, halved toward M/D/c since slot service times
    are tightly clustered within a deadline bucket.  Returns 0.0 when the
    system is idle (λ = 0) and +inf at or past saturation (ρ ≥ 1) — the
    admission gate treats an unstable operating point as an immediate
    reject, the same way a sub-floor deadline is (DESIGN.md §11)."""
    if offered_per_cycle <= 0.0 or service_cycles <= 0.0:
        return 0.0
    c = max(int(servers), 1)
    rho = offered_per_cycle * service_cycles / c
    if rho >= 1.0:
        return float("inf")
    lq = rho ** math.sqrt(2.0 * (c + 1)) / (1.0 - rho)
    return 0.5 * lq / offered_per_cycle


def queue_aware_floor(floor: float, queued: int, servers: int,
                      service_cycles: float) -> float:
    """Deadline admission floor inflated by the wait already visible in
    the arrival queue: `queued` requests ahead drain at roughly
    `servers` per `service_cycles`, so a request that would only meet
    its deadline on an empty queue is rejected instead of admitted to
    expire in line.  Degenerates to the plain `admission_floor` when the
    queue is empty."""
    if queued <= 0 or service_cycles <= 0.0:
        return floor
    return floor + (queued / max(int(servers), 1)) * service_cycles


def fault_penalty(storage_stats, batch_q: int,
                  constants: CostConstants = SYSTEM) -> float:
    """Per-query extra cycles from injected storage faults (a
    storage.StorageStats with fault counters) — recovery cost in the
    paper's own currency, matching `measured_miss_penalty`: every retry
    re-pays a miss-grade read and every latency spike pays the same
    page_miss_extra-style surcharge on top of the access it slowed."""
    extra = constants.page_access * (constants.page_miss_extra - 1.0)
    events = getattr(storage_stats, "retries", 0) \
        + getattr(storage_stats, "spikes", 0)
    return events * extra / max(batch_q, 1)


# ---------------------------------------------------------------------------
# Streaming mutability (DESIGN.md §12): the planner's price for a growing
# delta tier, and the write-side system-cost accounting.
# ---------------------------------------------------------------------------

def delta_scan_counters(n_delta: int, dim: int, selectivity: float,
                        k: int = 10) -> dict[str, float]:
    """Expected per-query Table-6 counters of the delta tier's exact scan
    (core.executor.DeltaExecutor) — seqscan semantics over the live delta
    rows: probe every one, fetch+score the passing."""
    ppv = heap_pages_per_vector(dim)
    s = min(max(selectivity, 0.0), 1.0)
    return dict(distance_comps=s * n_delta, filter_checks=float(n_delta),
                hops=0.0, page_accesses_index=0.0,
                page_accesses_heap=s * n_delta * ppv,
                tmap_lookups=0.0, reorder_rows=0.0)


def delta_scan_cycles(n_delta: int, dim: int, selectivity: float,
                      k: int = 10,
                      constants: CostConstants = SYSTEM) -> float:
    """Modeled per-query cycles the delta scan ADDS to whatever base
    strategy runs (the merge itself is O(k) and free at this scale).
    This is the term that makes a growing delta tier visible to the
    planner: every query pays it regardless of base strategy, so the
    compaction policy (`should_compact`) can weigh it against the one-off
    rebuild cost."""
    c = delta_scan_counters(n_delta, dim, selectivity, k)
    return component_cycles(c, dim, constants)["total"]


def write_amplification(user_bytes: int, page_writes: int,
                        wal_bytes: int = 0) -> float:
    """Physical-write bytes per logical user byte — the LSM tax, in the
    paper's page currency: (WAL bytes + 8 KB · page write-backs) /
    user payload bytes.  `page_writes` is the pool's write-back counter
    (PoolCounters.page_writes: dirty evictions + flushes), so checkpoint
    and compaction I/O land in the numerator exactly when they land on
    storage.  Returns inf when nothing was logically written but pages
    were, 1.0 when idle."""
    phys = wal_bytes + page_writes * PAGE_BYTES_WA
    if user_bytes <= 0:
        return float("inf") if phys > 0 else 1.0
    return phys / user_bytes


PAGE_BYTES_WA = 8192            # storage.pages.PAGE_BYTES (no import cycle)


def should_compact(n_delta: int, delta_capacity: int, n_base: int,
                   dim: int, selectivity: float,
                   queries_per_epoch: float = 1024.0,
                   fill_trigger: float = 0.75,
                   constants: CostConstants = SYSTEM) -> bool:
    """Compaction policy: fold the delta when (a) the tier is nearly full
    (capacity pressure — inserts would soon block), or (b) the scan tax
    the NEXT epoch of queries will pay on the delta exceeds the modeled
    one-off cost of rewriting the folded base (write amortization wins).
    The rebuild cost is priced as rewriting every base+delta heap page
    once at miss-grade cost — a deliberate underestimate of index
    rebuild work, so the policy leans eager the way LSM compactors do."""
    if n_delta <= 0:
        return False
    if n_delta >= fill_trigger * delta_capacity:
        return True
    scan_tax = queries_per_epoch * delta_scan_cycles(
        n_delta, dim, selectivity, constants=constants)
    ppv = heap_pages_per_vector(dim)
    rebuild = (n_base + n_delta) * ppv \
        * constants.page_access * constants.page_miss_extra
    return scan_tax > rebuild
