"""Unified executor/planner layer — every search strategy behind one API.

The paper's central finding is that the best filter-agnostic strategy is a
*system-aware decision* (Fig. 1 crossover, §6.2): it flips with
selectivity, vector-predicate correlation, and the per-architecture access
costs.  The repo's strategies historically lived behind three divergent
entry points (`graph_search.search_batch`, `scann.scann_search_batch[...]`,
`bruteforce.filtered_knn`) with three return conventions; this module
collapses them into one protocol so callers — benchmarks, serving, launch —
never hard-code an index again:

    Executor.plan(queries, bitmaps, params)  -> SearchPlan
    Executor.execute(plan)                   -> SearchResult
    Executor.search(queries, bitmaps, params) = execute(plan(...))

Fixed executors (`GraphExecutor`, `ScannExecutor`, `BruteForceExecutor`)
are thin, *bit-identical* ports of the legacy entry points — same jitted
kernels, same SearchStats counters (equivalence-tested in
tests/test_executor.py).  `AdaptivePlanner` is where the paper's finding
becomes machinery: per query batch it estimates selectivity from bitmap
popcounts, estimates correlation from the bitmap density inside the
query's nearest ScaNN leaves, runs `costmodel.predict_cycles` for every
registered candidate, and dispatches to the cheapest recall-feasible one
(decision boundaries in DESIGN.md §6).
"""
from __future__ import annotations

import dataclasses
import zlib
from functools import partial
from typing import Any, Mapping, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import costmodel
from repro.core.bruteforce import filtered_knn, filtered_knn_partial
from repro.core.exclusion import ExclusionIndex, match_families, select_radii
from repro.core.graph_search import (FrontierState, frontier_finalize,
                                     frontier_idle, frontier_init,
                                     frontier_write_slot, search_batch,
                                     step_supersteps)
from repro.core.hnsw import HNSWGraph, PartitionedGraph
from repro.core.scann import (ScannIndex, _quant_pages_per_leaf,
                              leaves_within_budget, project_query,
                              scann_search_batch,
                              scann_search_batch_vmapped)
from repro.core.types import (SearchParams, SearchResult, SearchStats,
                              VectorStore, distance, heap_pages_per_vector,
                              pack_bool_bitmap, probe_bitmap, quantize_store,
                              topk_smallest)
from repro.storage.engine import (StorageEngine, TRACE_UNTOUCHED,
                                  merge_storage_stats)

GRAPH_STRATEGIES = costmodel.GRAPH_STRATEGIES


@dataclasses.dataclass(frozen=True)
class SearchPlan:
    """What an executor decided to run for one query batch."""

    strategy: str                  # resolved strategy name
    params: SearchParams           # resolved knobs (strategy field set)
    queries: Any                   # (Q, d)
    bitmaps: Any                   # (Q, words) uint32
    # Planner annotations (None for fixed executors):
    est_selectivity: Optional[np.ndarray] = None    # (Q,) popcount/n
    correlation_proxy: Optional[float] = None       # local/global density
    predicted_cycles: Optional[Mapping[str, float]] = None
    # Plan-level adjustments (DESIGN.md §10), e.g. a budget-driven ScaNN
    # leaf clamp or a bruteforce partial-scan row cap — surfaced so the
    # executor can flag the affected queries budget_exhausted.
    notes: Any = None


@runtime_checkable
class Executor(Protocol):
    """Anything that can plan and execute filtered top-k search."""

    name: str
    store: VectorStore

    def plan(self, queries, bitmaps, params: SearchParams) -> SearchPlan: ...

    def execute(self, plan: SearchPlan) -> SearchResult: ...

    def search(self, queries, bitmaps,
               params: SearchParams) -> SearchResult: ...


class BaseExecutor:
    """plan/execute split with the one-call convenience wrapper."""

    name: str = "base"

    def search(self, queries, bitmaps, params: SearchParams) -> SearchResult:
        """plan, then execute.  The result's plan keeps what was decided
        and drops the batch it was given: a caller that keeps results
        would otherwise keep every batch's filter bitmaps, n/8 bytes a
        query, on the device."""
        with obs.span("executor.plan"):
            plan = self.plan(queries, bitmaps, params)
        with obs.span("executor.execute"):
            res = self.execute(plan)
        if res.plan is None:
            return res
        return dataclasses.replace(res, plan=dataclasses.replace(
            res.plan, queries=None, bitmaps=None))

    def plan(self, queries, bitmaps, params: SearchParams) -> SearchPlan:
        raise NotImplementedError

    def execute(self, plan: SearchPlan) -> SearchResult:
        raise NotImplementedError


class GraphExecutor(BaseExecutor):
    """All five graph strategies (paper §2.3) behind the executor API.

    Bit-identical port of `graph_search.search_batch` — the same jitted
    vmapped beam search runs underneath.  With a `storage` engine
    attached, the frontier engine's deduplicated union fetches are
    replayed through the buffer pool (DESIGN.md §8): the search runs with
    trace collection on (ids/dists/stats unchanged) and the result
    carries measured StorageStats."""

    def __init__(self, graph: HNSWGraph, store: VectorStore,
                 strategy: str = "sweeping", use_pallas: bool = False,
                 storage: Optional[StorageEngine] = None,
                 graph_quant: str = "none",
                 exclusion: Optional[ExclusionIndex] = None):
        if strategy not in GRAPH_STRATEGIES:
            raise ValueError(f"unknown graph strategy {strategy!r}")
        if graph_quant not in ("none", "sq8"):
            raise ValueError(f"unknown graph_quant {graph_quant!r}")
        if storage is not None and storage.graph is None:
            raise ValueError("storage engine lacks a graph adjacency "
                             "layout; build it with graph=")
        if graph_quant == "sq8":
            if store.q_vectors is None:
                raise ValueError("graph_quant='sq8' needs a quantize_store'd"
                                 " VectorStore (SQ8 shadow missing)")
            if storage is not None and storage.qheap is None:
                raise ValueError("storage engine lacks the qheap (SQ8 "
                                 "shadow) segment; build it from the "
                                 "quantized store")
        if exclusion is not None:
            # FAVOR pruned traversal (DESIGN.md §14): the keep rule is a
            # triangle-inequality argument in l2 root space, composed
            # with the sweeping engine's W-tail threshold — no other
            # strategy/metric carries the proof.
            if strategy != "sweeping":
                raise ValueError("exclusion pruning only composes with the "
                                 "sweeping strategy")
            if store.metric != "l2":
                raise ValueError("exclusion pruning needs metric='l2'")
            if exclusion.n != store.n:
                raise ValueError(
                    f"exclusion index built over n={exclusion.n} rows but "
                    f"store has n={store.n} (stale radii)")
        self.graph = graph
        self.store = store
        self.strategy = strategy
        self.use_pallas = use_pallas
        self.storage = storage
        self.graph_quant = graph_quant
        self.exclusion = exclusion
        base = strategy if exclusion is None else f"{strategy}_excl"
        self.name = base if graph_quant == "none" \
            else f"{base}_{graph_quant}"

    def resolve_params(self, params: SearchParams) -> SearchParams:
        """Plan-time strategy/quant coercion as a reusable helper.

        External steppers (serving/continuous.py) must resolve params
        exactly the way `plan` does — the resolved object is the jit
        cache key, so resolving differently would compile a second
        stepper for the same logical plan."""
        if params.strategy != self.strategy or \
                params.graph_quant != self.graph_quant:
            params = dataclasses.replace(params, strategy=self.strategy,
                                         graph_quant=self.graph_quant)
        if self.exclusion is None and params.exclusion != "none":
            # an exclusion mode only means something on an executor that
            # owns radii — coerce back so the legacy path stays inert
            params = dataclasses.replace(params, exclusion="none")
        return params

    def plan(self, queries, bitmaps, params: SearchParams) -> SearchPlan:
        params = self.resolve_params(params)
        notes = None
        if self.exclusion is not None:
            # Per-batch radii selection (DESIGN.md §14): family-exact rows
            # where the whole batch hits registered families — that is the
            # regime where "prune_exact" (FAVOR's eliminated filter probe)
            # is sound, because a family radius is 0 iff the row passes.
            # Any non-matching query demotes the batch to the ladder rungs
            # with full fc charging ("prune").
            fam = np.asarray(match_families(self.exclusion, bitmaps))
            mode = "prune_exact" if fam.size and (fam >= 0).all() \
                else "prune"
            params = dataclasses.replace(params, exclusion=mode)
            notes = {"excl": select_radii(self.exclusion, bitmaps)}
        return SearchPlan(self.strategy, params, queries, bitmaps,
                          notes=notes)

    # ---- stepped frontier driver (DESIGN.md §11) --------------------
    # Thin delegates so the continuous-batching scheduler never imports
    # graph_search directly; trace collection follows the storage
    # attachment the same way `execute` does.

    def _no_stepped_exclusion(self):
        if self.exclusion is not None:
            raise ValueError("exclusion pruning is not supported by the "
                             "stepped frontier driver (radii don't ride in "
                             "FrontierState); use the one-shot search path")

    def idle_frontier(self, params: SearchParams, width: int
                      ) -> FrontierState:
        self._no_stepped_exclusion()
        return frontier_idle(self.graph, self.store,
                             self.resolve_params(params), width,
                             collect_trace=self.storage is not None)

    def init_frontier(self, queries, bitmaps, params: SearchParams,
                      deadlines=None) -> FrontierState:
        self._no_stepped_exclusion()
        return frontier_init(self.graph, self.store, queries, bitmaps,
                             self.resolve_params(params),
                             collect_trace=self.storage is not None,
                             deadlines=deadlines)

    def write_frontier_slot(self, state: FrontierState,
                            lane: FrontierState, slot: int) -> FrontierState:
        return frontier_write_slot(state, lane, slot)

    def step_frontier(self, state: FrontierState, params: SearchParams,
                      n_hops: int, dynamic_deadline: bool = False
                      ) -> FrontierState:
        return step_supersteps(self.graph, self.store, state,
                               self.resolve_params(params), n_hops,
                               use_pallas=self.use_pallas,
                               dynamic_deadline=dynamic_deadline)

    def finalize_frontier(self, state: FrontierState,
                          params: SearchParams):
        return frontier_finalize(self.graph, self.store, state,
                                 self.resolve_params(params))

    def execute(self, plan: SearchPlan) -> SearchResult:
        excl = None if plan.notes is None else plan.notes.get("excl")
        if self.storage is None:
            d, ids, stats = search_batch(self.graph, self.store,
                                         plan.queries, plan.bitmaps,
                                         plan.params,
                                         use_pallas=self.use_pallas,
                                         excl=excl)
            return SearchResult(dists=d, ids=ids, stats=stats,
                                strategy=self.strategy, plan=plan,
                                anytime=costmodel.evaluate_anytime(
                                    stats, plan.params, self.store.dim, ids,
                                    hop_cap=plan.params.max_hops))
        if plan.params.graph_exec_mode != "frontier":
            raise ValueError("storage accounting needs the frontier "
                             "engine (graph_exec_mode='frontier')")
        d, ids, stats, trace = search_batch(
            self.graph, self.store, plan.queries, plan.bitmaps, plan.params,
            use_pallas=self.use_pallas, collect_trace=True, excl=excl)
        rr = trace.get("rerank_rows")
        sstats = self.storage.account_graph(
            np.asarray(trace["heap_steps"]),
            np.asarray(trace["index_steps"]),
            rerank_rows=None if rr is None else np.asarray(rr),
            quant=self.graph_quant == "sq8")
        return SearchResult(dists=d, ids=ids, stats=stats,
                            strategy=self.strategy, plan=plan,
                            storage=sstats,
                            anytime=costmodel.evaluate_anytime(
                                stats, plan.params, self.store.dim, ids,
                                hop_cap=plan.params.max_hops))


def _allpass_bitmap(n: int) -> jax.Array:
    """(W,) uint32 bitmap passing exactly rows [0, n)."""
    return jnp.asarray(pack_bool_bitmap(np.ones(n, bool)))


def _scatter_storage_stats(stats, qsel: np.ndarray, q: int):
    """Widen a query-subset StorageStats to the full batch: per-query
    arrays scatter to their global slots (zeros/False elsewhere) so
    `merge_storage_stats` can sum same-shaped parts."""
    def scatter(arr, fill):
        full = np.full(q, fill, np.asarray(arr).dtype)
        full[qsel] = np.asarray(arr)
        return full

    return dataclasses.replace(
        stats,
        index_pages=scatter(stats.index_pages, 0),
        heap_pages=scatter(stats.heap_pages, 0),
        faulted=(None if stats.faulted is None
                 else scatter(stats.faulted, False)))


class PartitionedGraphExecutor(BaseExecutor):
    """JAG-style attribute-partitioned graphs (DESIGN.md §14) behind the
    executor API.

    Each registered predicate *family* owns a private subgraph built over
    exactly its passing rows (`hnsw.build_graph_partitioned`).  A query
    whose bitmap equals a family bitmap word-for-word runs UNFILTERED on
    that subgraph — the filter is the partition, so per-candidate filter
    checks vanish (the JAG claim); the only fc charged is the plan-time
    family match (F·words word comparisons per query).  Queries matching
    no family fall back to the wrapped base executor on the full graph;
    a store grown past `built_n` (stale partitions) demotes the whole
    batch to the fallback.

    With a `storage` engine attached, matched queries' subgraph traces
    are scattered back to GLOBAL row ids and replayed through the base
    heap/adjacency layout — exact for heap pages (same rows, same pages),
    conservative for index pages (a family's private adjacency is packed
    denser than the base layout it is charged through)."""

    def __init__(self, partitions: PartitionedGraph, store: VectorStore,
                 base: Optional[Executor] = None, use_pallas: bool = False,
                 storage: Optional[StorageEngine] = None,
                 graph_quant: str = "none"):
        if graph_quant not in ("none", "sq8"):
            raise ValueError(f"unknown graph_quant {graph_quant!r}")
        if not partitions.partitions:
            raise ValueError("PartitionedGraph holds no partitions")
        if graph_quant == "sq8" and any(
                p.store.q_vectors is None for p in partitions.partitions):
            raise ValueError("graph_quant='sq8' needs partitions built from "
                             "a quantize_store'd VectorStore (SQ8 shadow "
                             "missing in a partition)")
        if storage is not None and storage.graph is None:
            raise ValueError("storage engine lacks a graph adjacency "
                             "layout; build it with graph=")
        self.partitions = partitions
        self.store = store
        self.base = base
        self.use_pallas = use_pallas
        self.storage = storage
        self.graph_quant = graph_quant
        self.strategy = "partitioned"
        self.name = "partitioned" if graph_quant == "none" \
            else f"partitioned_{graph_quant}"

    def plan(self, queries, bitmaps, params: SearchParams) -> SearchPlan:
        stale = self.partitions.built_n != self.store.n
        match = np.full(int(queries.shape[0]), -1, np.int32) if stale \
            else np.asarray(self.partitions.match(bitmaps))
        # the sub-searches run the unfiltered strategy: the partition IS
        # the filter, so traversal gating and the final check both drop
        sub = dataclasses.replace(params, strategy="unfiltered",
                                  graph_quant=self.graph_quant,
                                  exclusion="none")
        return SearchPlan("partitioned", sub, queries, bitmaps,
                          notes={"match": match, "caller_params": params})

    def execute(self, plan: SearchPlan) -> SearchResult:
        match = plan.notes["match"]
        q, k = int(plan.queries.shape[0]), plan.params.k
        unmatched = np.flatnonzero(match < 0)
        if unmatched.size and self.base is None:
            raise ValueError(
                f"{unmatched.size} queries match no partition family and "
                "no base executor is attached for fallback")
        dists = np.full((q, k), np.inf, np.float32)
        ids = np.full((q, k), -1, np.int32)
        counters = {f.name: np.zeros(q, np.int32)
                    for f in dataclasses.fields(SearchStats)}
        sparts = []
        tracing = self.storage is not None
        for f_idx in np.unique(match[match >= 0]):
            part = self.partitions.partitions[int(f_idx)]
            qsel = np.flatnonzero(match == f_idx)
            bm = jnp.broadcast_to(_allpass_bitmap(part.store.n),
                                  (qsel.size,
                                   (part.store.n + 31) // 32))
            out = search_batch(part.graph, part.store,
                               plan.queries[qsel], bm, plan.params,
                               use_pallas=self.use_pallas,
                               collect_trace=tracing)
            d, lids, stats = out[:3]
            rows = np.asarray(part.rows)
            lids = np.asarray(lids)
            dists[qsel] = np.asarray(d)
            ids[qsel] = np.where(lids >= 0,
                                 rows[np.maximum(lids, 0)], -1)
            for name in counters:
                counters[name][qsel] = np.asarray(getattr(stats, name))
            if tracing:
                sparts.append(_scatter_storage_stats(
                    self._account_partition(out[3], rows, qsel), qsel, q))
        if unmatched.size:
            fres = self.base.search(plan.queries[unmatched],
                                    plan.bitmaps[unmatched],
                                    plan.notes["caller_params"])
            dists[unmatched] = np.asarray(fres.dists)[:, :k]
            ids[unmatched] = np.asarray(fres.ids)[:, :k]
            if fres.stats is not None:
                for name in counters:
                    counters[name][unmatched] = np.asarray(
                        getattr(fres.stats, name))
            if fres.storage is not None:
                sparts.append(_scatter_storage_stats(fres.storage,
                                                     unmatched, q))
        # plan-time family match: each DISTINCT predicate bitmap in the
        # batch is compared against all F family bitmaps, words at a time
        # (PartitionedGraph.match dedupes the same way) — the only filter
        # work a matched query ever pays (the JAG accounting claim).  The
        # charge lands on each distinct bitmap's first query; queries
        # sharing the bitmap ride the memoized match.
        _, first = np.unique(np.asarray(plan.bitmaps), axis=0,
                             return_index=True)
        counters["filter_checks"][first] += (
            len(self.partitions.partitions) * int(plan.bitmaps.shape[1]))
        stats = SearchStats(**{name: jnp.asarray(v)
                               for name, v in counters.items()})
        sstats = merge_storage_stats(sparts) if sparts else None
        jd, ji = jnp.asarray(dists), jnp.asarray(ids)
        return SearchResult(dists=jd, ids=ji, stats=stats,
                            strategy="partitioned", plan=plan,
                            storage=sstats,
                            anytime=costmodel.evaluate_anytime(
                                stats, plan.params, self.store.dim, ji,
                                hop_cap=plan.params.max_hops))

    def _account_partition(self, trace, rows: np.ndarray,
                           qsel: np.ndarray):
        """Scatter a subgraph trace's first-touch stamps (Qg, n_f) to
        global row ids (Qg, n) and replay through the base layout."""
        n = self.store.n
        hs = np.asarray(trace["heap_steps"])
        isteps = np.asarray(trace["index_steps"])
        heap_g = np.full((qsel.size, n), TRACE_UNTOUCHED, np.int32)
        idx_g = np.full((qsel.size, n), TRACE_UNTOUCHED, np.int32)
        heap_g[:, rows] = hs
        idx_g[:, rows] = isteps
        rr = trace.get("rerank_rows")
        rr_g = None
        if rr is not None:
            rr = np.asarray(rr)
            rr_g = np.where(rr >= 0, rows[np.maximum(rr, 0)], -1)
        return self.storage.account_graph(heap_g, idx_g, rerank_rows=rr_g,
                                          quant=self.graph_quant == "sq8")


class ScannExecutor(BaseExecutor):
    """Filtered ScaNN (paper §2.3.7) behind the executor API.

    pipeline="batched" is the query-batched union-scan hot path
    (DESIGN.md §4, with optional query-block tiling); "vmapped" is the
    legacy per-query path kept as the equivalence oracle."""

    def __init__(self, index: ScannIndex, store: VectorStore,
                 pipeline: str = "batched", use_pallas: bool = False,
                 storage: Optional[StorageEngine] = None):
        if pipeline not in ("batched", "vmapped"):
            raise ValueError(f"unknown scann pipeline {pipeline!r}")
        if storage is not None:
            if pipeline != "batched":
                raise ValueError("storage accounting needs the batched "
                                 "scann pipeline")
            if storage.scann is None:
                raise ValueError("storage engine lacks a scann leaf "
                                 "layout; build it with index=")
        self.index = index
        self.store = store
        self.pipeline = pipeline
        self.use_pallas = use_pallas
        self.storage = storage
        self.name = "scann" if pipeline == "batched" else "scann_vmapped"

    def plan(self, queries, bitmaps, params: SearchParams) -> SearchPlan:
        if params.strategy != "scann":
            params = dataclasses.replace(params, strategy="scann")
        # Anytime budgets (DESIGN.md §10): ScaNN's leaf count is a static
        # shape, so budget enforcement is plan-time — clamp
        # num_leaves_to_search to what the budgets afford and flag the
        # batch via plan.notes.  Zero budgets short-circuit to (nl, False)
        # and the params object is untouched (bit-identicality).
        nl, clamped = leaves_within_budget(self.index, self.store, params)
        notes = None
        if clamped:
            params = dataclasses.replace(params, num_leaves_to_search=nl)
            notes = {"leaf_clamp": nl}
        return SearchPlan("scann", params, queries, bitmaps, notes=notes)

    def _anytime(self, plan: SearchPlan, ids):
        # flags come from the plan-time clamp, not the counters: the
        # clamped plan fits the budget by construction, so counter-derived
        # predicates would never fire (stats=None skips them)
        q = np.asarray(ids).shape[0]
        clamped = plan.notes is not None and "leaf_clamp" in plan.notes
        return costmodel.evaluate_anytime(
            None, plan.params, self.store.dim, ids,
            extra_budget=np.full((q,), clamped, bool))

    def execute(self, plan: SearchPlan) -> SearchResult:
        if self.storage is not None:
            d, ids, stats, trace = scann_search_batch(
                self.index, self.store, plan.queries, plan.bitmaps,
                plan.params, use_pallas=self.use_pallas, collect_trace=True)
            sstats = self.storage.account_scann(
                np.asarray(trace["leaves"]), np.asarray(trace["cand_rows"]),
                np.asarray(trace["cand_ok"]),
                accounting=plan.params.scann_page_accounting,
                query_block=plan.params.scann_query_block)
            return SearchResult(dists=d, ids=ids, stats=stats,
                                strategy="scann", plan=plan, storage=sstats,
                                anytime=self._anytime(plan, ids))
        fn = scann_search_batch if self.pipeline == "batched" \
            else scann_search_batch_vmapped
        d, ids, stats = fn(self.index, self.store, plan.queries,
                           plan.bitmaps, plan.params,
                           use_pallas=self.use_pallas)
        return SearchResult(dists=d, ids=ids, stats=stats, strategy="scann",
                            plan=plan, anytime=self._anytime(plan, ids))


@jax.jit
def _bitmap_popcount(bitmaps):
    """Per-query popcount over packed bitmap words. (Q, W) -> (Q,) int32."""
    return jax.lax.population_count(bitmaps).sum(axis=-1).astype(jnp.int32)


def _mask_bitmap_prefix(bm: np.ndarray, probes: np.ndarray) -> np.ndarray:
    """Zero every bitmap bit at row id >= probes[q] — the part of the
    seqscan a partial (budgeted) scan never reached, so the storage
    replay only charges pages the scan actually touched."""
    words = bm.shape[1]
    keep = np.clip(probes[:, None].astype(np.int64)
                   - np.arange(words, dtype=np.int64)[None, :] * 32, 0, 32)
    mask = np.where(keep >= 32, np.uint32(0xFFFFFFFF),
                    ((np.uint64(1) << keep.astype(np.uint64)) - 1)
                    .astype(np.uint32))
    return (bm & mask).astype(np.uint32)


def index_shape(store: VectorStore, index: Optional[ScannIndex] = None,
                graph_m: int = 16) -> costmodel.IndexShape:
    """Static shape facts for the predictive cost model — the public
    derivation shared by AdaptivePlanner and the benchmarks."""
    kw = dict(n=store.n, dim=store.dim, graph_m=graph_m)
    if index is not None:
        L, C, _ = index.leaf_tiles.shape
        if index.levels >= 2:
            B, Lb = index.branch_leaves.shape
            nb = max(1, -(-32 * 2 * B // L))
            cent = B + nb * Lb
        else:
            cent = L
        # average VALID rows per leaf (padded capacity C over-counts:
        # the stats only charge rowids >= 0)
        fill = max(1, round(store.n / L))
        kw.update(scann_leaves=L, scann_rows_per_leaf=min(fill, C),
                  scann_cent_scored=cent,
                  scann_pages_per_leaf=_quant_pages_per_leaf(index))
    return costmodel.IndexShape(**kw)


class BruteForceExecutor(BaseExecutor):
    """Exact filtered KNN (`bruteforce.filtered_knn`) with seqscan-semantic
    counters: every row is filter-checked; passing rows are fetched from
    the heap and scored.  Ground-truth recall by construction — the
    planner's refuge at very low selectivity, where (paper Fig. 9, left
    edge) every index strategy pays more than a scan of the survivors."""

    name = "bruteforce"

    def __init__(self, store: VectorStore,
                 storage: Optional[StorageEngine] = None):
        self.store = store
        self.storage = storage

    def plan(self, queries, bitmaps, params: SearchParams) -> SearchPlan:
        if params.strategy != "bruteforce":
            params = dataclasses.replace(params, strategy="bruteforce")
        # Anytime budgets (DESIGN.md §10): a page or deadline budget caps
        # how many passing rows the scan can afford to fetch+score — a
        # static row cap resolved at plan time (hop_budget has no meaning
        # for a seqscan and is ignored).  At least k rows always scan so
        # the last ladder rung returns a usable, flagged top-k.
        max_rows = self._budget_rows(params)
        notes = {"max_rows": max_rows} if max_rows is not None else None
        return SearchPlan("bruteforce", params, queries, bitmaps,
                          notes=notes)

    def _budget_rows(self, params: SearchParams) -> Optional[int]:
        if params.page_budget <= 0 and params.deadline_cycles <= 0:
            return None
        n = self.store.n
        ppv = heap_pages_per_vector(self.store.dim)
        rows = n
        if params.page_budget > 0:
            rows = min(rows, params.page_budget // ppv)
        if params.deadline_cycles > 0:
            w = costmodel.budget_cycle_weights(self.store.dim)
            per_row = w["distance_comps"] + ppv * w["page_accesses_heap"]
            fixed = n * w["filter_checks"]
            rows = min(rows, int(max(params.deadline_cycles - fixed, 0.0)
                                 // max(per_row, 1e-9)))
        rows = max(min(rows, n), params.k)
        return None if rows >= n else rows

    def execute(self, plan: SearchPlan) -> SearchResult:
        q = plan.queries.shape[0]
        n = self.store.n
        ppv = heap_pages_per_vector(self.store.dim)
        z = jnp.zeros((q,), jnp.int32)
        max_rows = (plan.notes or {}).get("max_rows")
        if max_rows is None:
            d, ids = filtered_knn(self.store, plan.queries, plan.bitmaps,
                                  plan.params.k)
            npass = _bitmap_popcount(plan.bitmaps)          # (Q,)
            stats = SearchStats(
                distance_comps=npass, filter_checks=z + n, hops=z,
                page_accesses_index=z, page_accesses_heap=npass * ppv,
                tmap_lookups=z, reorder_rows=z)
            truncated = np.zeros((q,), bool)
            scan_bitmaps = np.asarray(plan.bitmaps)
        else:
            d, ids, n_scored, probes, trunc = filtered_knn_partial(
                self.store, plan.queries, plan.bitmaps, plan.params.k,
                max_rows)
            stats = SearchStats(
                distance_comps=n_scored, filter_checks=probes, hops=z,
                page_accesses_index=z, page_accesses_heap=n_scored * ppv,
                tmap_lookups=z, reorder_rows=z)
            truncated = np.asarray(trunc)
            # the storage replay must see only the scanned prefix
            scan_bitmaps = _mask_bitmap_prefix(np.asarray(plan.bitmaps),
                                               np.asarray(probes))
        sstats = None
        if self.storage is not None:
            # the bitmap IS the seqscan trace: passing rows in row-id order
            sstats = self.storage.account_seqscan(scan_bitmaps)
        return SearchResult(dists=d, ids=ids, stats=stats,
                            strategy="bruteforce", plan=plan,
                            storage=sstats,
                            anytime=costmodel.evaluate_anytime(
                                None, plan.params, self.store.dim, ids,
                                extra_budget=truncated))


# ---------------------------------------------------------------------------
# The mutable delta tier's executor (DESIGN.md §12).
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k", "metric", "base_n"))
def _delta_scan(vectors, norms_sq, count, queries, bitmaps, k: int,
                metric: str, base_n: int):
    """Exact filtered scan of the capacity-padded delta buffer.

    The buffer has STATIC shape (capacity, dim) and only `count` (a
    traced scalar) changes as the tier fills — one compile per capacity,
    never per mutation.  Rows >= count and rows failing the bitmap (probed
    at their GLOBAL ids, so the caller's tombstone-composed filter bitmap
    applies unchanged) score +inf.  The distance expression is the same
    elementwise-plus-last-axis-sum `distance()` the bruteforce oracle
    evaluates, so merged results are bit-identical to a from-scratch
    rebuild, not approximately equal."""
    cap = vectors.shape[0]
    local = jnp.arange(cap)
    gids = base_n + local
    live = local < count
    passing = jax.vmap(lambda bm: probe_bitmap(bm, gids))(bitmaps) \
        & live[None, :]
    d = distance(metric, queries[:, None, :], vectors[None, :, :],
                 norms_sq[None, :])
    d = jnp.where(passing, d, jnp.inf)
    dists, idx = topk_smallest(d, min(k, cap))
    ids = jnp.where(jnp.isinf(dists), -1, base_n + idx)
    if k > cap:                       # static pad: tier smaller than k
        dists = jnp.pad(dists, ((0, 0), (0, k - cap)),
                        constant_values=jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, k - cap)), constant_values=-1)
    return dists, ids, passing.sum(1).astype(jnp.int32)


class DeltaExecutor(BaseExecutor):
    """Exact scan over the LSM delta tier (storage.delta.DeltaTier) —
    the unindexed mutable tail every base strategy's top-k merges with
    (`core.mutable.MutableIndex` / `types.merge_topk`).

    Seqscan counter semantics scaled to the tier: every live delta row is
    filter-checked, passing rows are fetched full-width and scored
    (`costmodel.delta_scan_counters`).  With a `storage` engine attached
    (built with delta_capacity=) the per-query scan replays through the
    pool's "delta" segment."""

    name = "delta"

    def __init__(self, tier, metric: str,
                 storage: Optional[StorageEngine] = None):
        self.tier = tier
        self.metric = metric
        self.storage = storage

    def plan(self, queries, bitmaps, params: SearchParams) -> SearchPlan:
        if params.strategy != "delta":
            params = dataclasses.replace(params, strategy="delta")
        # snapshot the mutable tier at plan time: a consistent
        # (count, base_n, rows) view even if mutations land mid-request
        notes = {"count": int(self.tier.count),
                 "base_n": int(self.tier.base_n),
                 "vectors": np.array(self.tier.vectors, np.float32)}
        return SearchPlan("delta", params, queries, bitmaps, notes=notes)

    def execute(self, plan: SearchPlan) -> SearchResult:
        notes = plan.notes
        vecs = jnp.asarray(notes["vectors"])
        # eager per-row norms, the exact expression VectorStore.build uses
        nsq = jnp.sum(vecs * vecs, axis=-1)
        count = notes["count"]
        d, ids, npass = _delta_scan(vecs, nsq, jnp.int32(count),
                                    plan.queries, plan.bitmaps,
                                    plan.params.k, self.metric,
                                    notes["base_n"])
        q = plan.queries.shape[0]
        z = jnp.zeros((q,), jnp.int32)
        ppv = heap_pages_per_vector(vecs.shape[1])
        stats = SearchStats(
            distance_comps=npass, filter_checks=z + count, hops=z,
            page_accesses_index=z, page_accesses_heap=npass * ppv,
            tmap_lookups=z, reorder_rows=z)
        sstats = None
        if self.storage is not None:
            sstats = self.storage.account_delta_scan(count, q)
        return SearchResult(dists=d, ids=ids, stats=stats,
                            strategy="delta", plan=plan, storage=sstats,
                            anytime=costmodel.evaluate_anytime(
                                None, plan.params, vecs.shape[1], ids))


# ---------------------------------------------------------------------------
# The system-aware adaptive planner.
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("probe_leaves",))
def _leaf_local_selectivity(index: ScannIndex, queries, bitmaps,
                            probe_leaves: int):
    """Bitmap density inside each query's nearest `probe_leaves` ScaNN
    leaves — the correlation proxy numerator.  (Q,) float32.

    The centroid scan here is repeated by ScannExecutor when the planner
    picks scann — accepted cost (O(Q·L·dp), trivial next to the leaf
    scan) so the fixed executors' jitted entry points stay byte-for-byte
    the legacy ones (the equivalence guarantee)."""
    qp = project_query(index, queries)                        # (Q, dp)
    cents = index.leaf_centroids
    cn = jnp.sum(cents * cents, -1)
    d = (jnp.sum(qp * qp, -1)[:, None] + cn[None, :]
         - 2.0 * qp @ cents.T)                                # (Q, L)
    _, leaves = topk_smallest(d, probe_leaves)                # (Q, P)
    rows = index.leaf_rowids[leaves]                          # (Q, P, C)
    ok = jax.vmap(lambda bm, r: probe_bitmap(bm, r))(
        bitmaps, rows.reshape(rows.shape[0], -1))
    valid = (rows >= 0).reshape(rows.shape[0], -1)
    return (ok & valid).sum(-1) / jnp.maximum(valid.sum(-1), 1)


class AdaptivePlanner(BaseExecutor):
    """Per-batch system-aware strategy selection (DESIGN.md §6).

    plan():  s_q   = popcount(bitmap_q)/n           (exact, ~n/32 word reads)
             γ     = mean local leaf density / mean s  (ScaNN-probe proxy;
                     1.0 when no ScaNN index is registered)
             pick  = argmin over recall-feasible candidates of
                     predict_cycles(strategy, shape, params, s̄, γ)
    execute(): delegates to the chosen fixed executor, then adds the
    planning overhead to the counters (n/32 filter-word reads per query
    plus the proxy's centroid scans + leaf probes) so regret accounting
    stays honest.

    With a `storage` engine attached the dispatch becomes
    warm-cache-aware (DESIGN.md §8): plan() snapshots the buffer pool's
    per-segment residency (`BufferPoolState`) and every candidate's
    predicted cycles include its expected miss penalty — a strategy whose
    index pages are already resident gets cheaper, which is the paper's
    "system-aware decision" made literal at the buffer-manager level.
    """

    name = "adaptive"

    def __init__(self, candidates: Mapping[str, Executor],
                 store: VectorStore,
                 constants: costmodel.CostConstants = costmodel.SYSTEM,
                 graph_m: int = 16, probe_leaves: int = 4,
                 recall_margin: float = 2.0,
                 scann_recall_margin: float = 10.0,
                 storage: Optional[StorageEngine] = None):
        if not candidates:
            raise ValueError("AdaptivePlanner needs at least one candidate")
        for name, ex in candidates.items():
            kind = _strategy_kind(ex)
            if kind not in costmodel.PREDICTABLE_STRATEGIES:
                raise ValueError(
                    f"candidate {name!r} ({kind!r}) has no predictive "
                    f"model; supported: {costmodel.PREDICTABLE_STRATEGIES}")
        self.candidates = dict(candidates)
        self.store = store
        self.constants = constants
        self.graph_m = graph_m
        self.probe_leaves = probe_leaves
        self.recall_margin = recall_margin
        self.scann_recall_margin = scann_recall_margin
        self.storage = storage
        self._scann = next((ex for ex in self.candidates.values()
                            if isinstance(ex, ScannExecutor)), None)
        # Pool-measured per-batch unique-fetch fraction of the last graph
        # dispatch (StorageStats.unique_fraction): replaces the
        # FRONTIER_PAGE_AMORT calibration constant in subsequent
        # predictions (costmodel.engine_scale) — the ROADMAP
        # "per-batch measurement instead of a constant" follow-up.
        self._measured_unique: Optional[float] = None
        # Memoized per-batch (selectivity, γ) — see _selectivity_proxy.
        self._proxy_key: Optional[tuple] = None
        self._proxy_val: Optional[tuple] = None

    # -- shape facts for the predictive model --------------------------------
    def _shape(self) -> costmodel.IndexShape:
        return index_shape(
            self.store,
            self._scann.index if self._scann is not None else None,
            self.graph_m)

    def _recall_feasible(self, strategy: str, shape: costmodel.IndexShape,
                         params: SearchParams, s_eff: float) -> bool:
        """Cheap guards against picking a strategy whose expected candidate
        pool cannot even contain k passing rows (decision boundaries,
        DESIGN.md §6).  bruteforce is always feasible (exact)."""
        k = params.k * self.recall_margin
        if strategy == "scann":
            # the opened leaves must hold comfortably more passing rows
            # than k — ScaNN's recall collapses quietly when the predicate
            # is sparse/anti-correlated (few survivors land in the nearest
            # leaves), so the margin is deliberately wide
            nl = min(params.num_leaves_to_search, shape.scann_leaves or 1)
            return s_eff * nl * (shape.scann_rows_per_leaf or 0) >= \
                params.k * self.scann_recall_margin
        if strategy in ("acorn", "navix"):
            # predicate subgraph must hold at least ~ef nodes to navigate
            return shape.n * s_eff * costmodel.FILTER_FIRST_POOL >= \
                max(params.ef_search, k)
        if strategy in ("sweeping", "iterative_scan", "sweeping_excl"):
            # traversal must reach k passing rows within the hop budget
            # (pruning never drops a passing candidate, so the exclusion
            # tier inherits sweeping's reachability law unchanged)
            hops = min(max(params.ef_search, 2 * params.k) / max(s_eff, 1e-9),
                       float(params.max_hops))
            return costmodel.GRAPH_NEW_PER_HOP * hops * s_eff >= k
        return True

    def _batch_feasible(self, ex: Executor, bitmaps) -> bool:
        """Batch-shape feasibility the closed-form laws can't see: the
        partitioned tier answers a batch only when EVERY query's bitmap
        equals a registered family bitmap and the partitions are fresh —
        anything else would silently route through its fallback and the
        prediction would price the wrong machinery."""
        if isinstance(ex, PartitionedGraphExecutor):
            if ex.partitions.built_n != ex.store.n:
                return False
            return bool((np.asarray(ex.partitions.match(bitmaps)) >= 0)
                        .all())
        return True

    def _selectivity_proxy(self, queries, bitmaps):
        """Memoized (per-query selectivity, correlation proxy γ) for one
        batch, keyed by a crc of the raw bytes.  Regret sweeps and serving
        loops replan the same workload as the candidate menu grows, and
        the popcount + leaf-probe proxies are menu-independent — one
        computation per distinct batch keeps planning cost flat from the
        6-candidate menu to the 9-candidate one.  The CHARGED overhead
        (filter-word reads + probe fc/dc in execute()) is a property of
        the proxy computation, not the menu, and is unchanged."""
        key = (zlib.crc32(np.asarray(bitmaps).tobytes()),
               zlib.crc32(np.ascontiguousarray(
                   np.asarray(queries, np.float32)).tobytes()))
        if self._proxy_key == key:
            return self._proxy_val
        n = self.store.n
        sel = np.asarray(_bitmap_popcount(bitmaps)).astype(np.float64) / n
        gamma = 1.0
        if self._scann is not None:
            local = np.asarray(_leaf_local_selectivity(
                self._scann.index, queries, bitmaps, self.probe_leaves))
            gamma = float(np.clip(local.mean()
                                  / max(float(sel.mean()), 1.0 / n),
                                  0.05, 20.0))
        self._proxy_key, self._proxy_val = key, (sel, gamma)
        return sel, gamma

    def plan(self, queries, bitmaps, params: SearchParams) -> SearchPlan:
        n = self.store.n
        sel, gamma = self._selectivity_proxy(queries, bitmaps)
        s_mean = float(sel.mean())
        shape = self._shape()
        s_eff = min(max(s_mean * gamma, 1.0 / n), 1.0)
        batch_q = int(queries.shape[0])
        pool_state = self.storage.state() if self.storage is not None \
            else None
        # predict with each candidate's RESOLVED params (strategy +
        # graph_quant), so e.g. the sweeping_sq8 candidate is priced on
        # the quantized tier it would actually execute
        preds = {name: costmodel.predict_cycles(
            _strategy_kind(ex), shape, _candidate_params(ex, params),
            s_mean, gamma, self.constants, batch_q=batch_q,
            pool_state=pool_state,
            measured_unique_frac=self._measured_unique)
            for name, ex in self.candidates.items()}
        feasible = {name: p for name, p in preds.items()
                    if self._recall_feasible(_strategy_kind(
                        self.candidates[name]), shape, params, s_eff)
                    and self._batch_feasible(self.candidates[name], bitmaps)}
        # never empty: fall back to argmin, but a batch-infeasible
        # candidate (partitioned with an unmatched query) stays out even
        # then — executing it would route the wrong machinery
        pool = feasible \
            or {nm: p for nm, p in preds.items()
                if self._batch_feasible(self.candidates[nm], bitmaps)} \
            or preds
        chosen = min(pool, key=pool.get)
        inner = self.candidates[chosen].plan(queries, bitmaps, params)
        return SearchPlan(strategy=chosen, params=inner.params,
                          queries=queries, bitmaps=bitmaps,
                          est_selectivity=sel, correlation_proxy=gamma,
                          predicted_cycles=preds, notes=inner.notes)

    def execute(self, plan: SearchPlan) -> SearchResult:
        chosen = self.candidates[plan.strategy]
        res = self.candidates[plan.strategy].execute(plan)
        if res.storage is not None and isinstance(chosen, GraphExecutor) \
                and chosen.graph_quant == "none":
            # full-precision graph batch ran through the pool: keep its
            # measured page-sharing for the next plan's engine_scale.
            # Only the f32 tier updates it — FRONTIER_CALIB_UNIQUE was
            # calibrated on f32 heap geometry, and the 4×-denser qheap
            # shares pages structurally more (a sq8 measurement would
            # wrongly discount every f32 candidate too).
            self._measured_unique = res.storage.unique_fraction()
        if res.stats is not None:
            # planning overhead: popcount reads every bitmap word (n/32
            # filter-word probes) + the proxy's centroid scan and leaf
            # probes — charged so the regret curve includes the planner.
            words = int(plan.bitmaps.shape[1])
            probe_fc = 0
            probe_dc = 0
            if self._scann is not None:
                idx = self._scann.index
                probe_fc = self.probe_leaves * idx.leaf_rowids.shape[1]
                probe_dc = idx.leaf_centroids.shape[0]
            st = res.stats
            stats = dataclasses.replace(
                st,
                filter_checks=st.filter_checks + words + probe_fc,
                distance_comps=st.distance_comps + probe_dc)
            res = dataclasses.replace(res, stats=stats, plan=plan)
        return res


def _strategy_kind(ex: Executor) -> str:
    """Predictive-model strategy key for an executor instance (quant
    variants of a graph strategy share its predictive model; the
    exclusion and partitioned tiers have their own laws)."""
    if isinstance(ex, ScannExecutor):
        return "scann"
    if isinstance(ex, PartitionedGraphExecutor):
        return "partitioned"
    if isinstance(ex, GraphExecutor) and ex.exclusion is not None:
        return "sweeping_excl"
    return getattr(ex, "strategy", ex.name)


def _candidate_params(ex: Executor, params: SearchParams) -> SearchParams:
    """The params the candidate would resolve in plan() — what its
    prediction must be priced on (strategy + graph_quant for graph
    executors)."""
    if isinstance(ex, PartitionedGraphExecutor):
        return dataclasses.replace(params, strategy="unfiltered",
                                   graph_quant=ex.graph_quant,
                                   exclusion="none")
    if isinstance(ex, GraphExecutor):
        return dataclasses.replace(
            params, strategy=ex.strategy, graph_quant=ex.graph_quant,
            exclusion="none" if ex.exclusion is None else "prune")
    return params


# ---------------------------------------------------------------------------
# Registry — the one dispatch point for benchmarks/serving/launch.
# ---------------------------------------------------------------------------

GRAPH_SQ8_METHODS = tuple(f"{s}_sq8" for s in GRAPH_STRATEGIES)
# Selectivity-aware tiers (DESIGN.md §14): exclusion-pruned sweeping and
# the attribute-partitioned graph, each with an SQ8 shadow variant.
EXCL_METHODS = ("sweeping_excl", "sweeping_excl_sq8")
PARTITIONED_METHODS = ("partitioned", "partitioned_sq8")
REGISTERED_METHODS = GRAPH_STRATEGIES + GRAPH_SQ8_METHODS + EXCL_METHODS \
    + PARTITIONED_METHODS + ("scann", "scann_vmapped", "bruteforce",
                             "adaptive")


def _parse_graph_method(method: str) -> tuple[str, str]:
    """"sweeping_sq8" -> ("sweeping", "sq8"); plain names pass through."""
    if method.endswith("_sq8") and method[:-4] in GRAPH_STRATEGIES:
        return method[:-4], "sq8"
    return method, "none"


def make_executor(method: str, store: VectorStore, *,
                  graph: Optional[HNSWGraph] = None,
                  index: Optional[ScannIndex] = None,
                  use_pallas: bool = False,
                  constants: costmodel.CostConstants = costmodel.SYSTEM,
                  graph_m: int = 16,
                  storage: Optional[StorageEngine] = None,
                  exclusion: Optional[ExclusionIndex] = None,
                  partitions: Optional[PartitionedGraph] = None,
                  planner_candidates: tuple[str, ...] = (
                      "bruteforce", "scann", "sweeping", "sweeping_sq8",
                      "navix", "iterative_scan")) -> Executor:
    """Build the executor for `method`.

    Graph strategies need `graph`; their "<strategy>_sq8" variants run
    the SQ8 quantized-traversal tier (DESIGN.md §9 — the store is
    shadow-quantized here if it isn't already); "scann"/"scann_vmapped"
    need `index`; the selectivity-aware tiers (DESIGN.md §14) need their
    build artifacts: "sweeping_excl[_sq8]" needs `exclusion=`
    (core.exclusion.build_exclusion) and "partitioned[_sq8]" needs
    `partitions=` (hnsw.build_graph_partitioned, with `graph=` as the
    unmatched-query fallback).  "adaptive" builds every candidate the
    provided components support — name the new tiers in
    `planner_candidates` to put them on the menu.  `storage` attaches a
    paged storage engine (DESIGN.md §8): results carry measured
    StorageStats, and for "adaptive" ONE shared pool backs every
    candidate AND feeds residency + measured per-batch page sharing into
    the planner's predictions (warm-cache-aware, engine-amortization-
    aware dispatch)."""
    def _excl_executor(quant: str, st: VectorStore) -> GraphExecutor:
        if graph is None or exclusion is None:
            raise ValueError("'sweeping_excl' variants need graph= and "
                             "exclusion=")
        return GraphExecutor(graph, st, strategy="sweeping",
                             use_pallas=use_pallas, storage=storage,
                             graph_quant=quant, exclusion=exclusion)

    def _part_executor(quant: str, st: VectorStore) -> Executor:
        if partitions is None:
            raise ValueError("'partitioned' variants need partitions=")
        fallback = None if graph is None else GraphExecutor(
            graph, st, strategy="sweeping", use_pallas=use_pallas,
            storage=storage, graph_quant=quant)
        return PartitionedGraphExecutor(partitions, st, base=fallback,
                                        use_pallas=use_pallas,
                                        storage=storage, graph_quant=quant)

    if method in EXCL_METHODS:
        quant = "sq8" if method.endswith("_sq8") else "none"
        return _excl_executor(quant, quantize_store(store)
                              if quant == "sq8" else store)
    if method in PARTITIONED_METHODS:
        quant = "sq8" if method.endswith("_sq8") else "none"
        return _part_executor(quant, quantize_store(store)
                              if quant == "sq8" else store)
    base, quant = _parse_graph_method(method)
    if base in GRAPH_STRATEGIES:
        if graph is None:
            raise ValueError(f"{method!r} needs graph=")
        if quant == "sq8":
            store = quantize_store(store)
        return GraphExecutor(graph, store, strategy=base,
                             use_pallas=use_pallas, storage=storage,
                             graph_quant=quant)
    if method in ("scann", "scann_vmapped"):
        if index is None:
            raise ValueError(f"{method!r} needs index=")
        return ScannExecutor(index, store,
                             pipeline="batched" if method == "scann"
                             else "vmapped", use_pallas=use_pallas,
                             storage=storage)
    if method == "bruteforce":
        return BruteForceExecutor(store, storage=storage)
    if method == "adaptive":
        if any(_parse_graph_method(n)[1] == "sq8" or n.endswith("_sq8")
               for n in planner_candidates) and graph is not None:
            store = quantize_store(store)
        cands: dict[str, Executor] = {}
        for name in planner_candidates:
            cbase, cquant = _parse_graph_method(name)
            if name == "bruteforce":
                cands[name] = BruteForceExecutor(store, storage=storage)
            elif name in EXCL_METHODS:
                if graph is not None and exclusion is not None:
                    cands[name] = _excl_executor(
                        "sq8" if name.endswith("_sq8") else "none", store)
            elif name in PARTITIONED_METHODS:
                if partitions is not None:
                    cands[name] = _part_executor(
                        "sq8" if name.endswith("_sq8") else "none", store)
            elif cbase in GRAPH_STRATEGIES and graph is not None:
                cands[name] = GraphExecutor(graph, store, strategy=cbase,
                                            use_pallas=use_pallas,
                                            storage=storage,
                                            graph_quant=cquant)
            elif name in ("scann", "scann_vmapped") and index is not None:
                cands[name] = ScannExecutor(
                    index, store, pipeline="batched" if name == "scann"
                    else "vmapped", use_pallas=use_pallas,
                    storage=storage if name == "scann" else None)
        return AdaptivePlanner(cands, store, constants=constants,
                               graph_m=graph_m, storage=storage)
    raise ValueError(
        f"unknown method {method!r}; registered: {REGISTERED_METHODS}")
