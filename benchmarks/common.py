"""Shared benchmark infra: container-scale datasets, cached indexes, the
95%-recall tuning ladder, and CSV emission (one row per measured config)."""
from __future__ import annotations

import os
import pickle
import sys
import time
import zlib

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (SYSTEM, SearchParams, WorkloadSpec,
                        assign_family_bitmaps, build_exclusion, build_graph,
                        build_scann, cycle_breakdown, engine_scale,
                        filtered_knn, generate_bitmaps, generate_families,
                        make_executor, measured_miss_penalty, quantize_store,
                        recall_at_k, stats_table_row)
from repro.data import DatasetSpec, make_dataset

CACHE_DIR = os.path.join(os.path.dirname(__file__), ".cache")
NUM_QUERIES = 16

# Container-scale stand-ins for the paper's four datasets (Table 2 shapes).
BENCH_DATASETS = {
    "sift10m": DatasetSpec("sift10m", 20_000, 128, "l2", clusters=64),
    "openai5m": DatasetSpec("openai5m", 8_000, 768, "ip", clusters=32),
    "cohere10m": DatasetSpec("cohere10m", 16_000, 256, "l2", clusters=48),
    "text2image10m": DatasetSpec("text2image10m", 16_000, 200, "l2",
                                 clusters=64, ood_queries=True),
}

GRAPH_METHODS = ("navix", "acorn", "sweeping", "iterative_scan")
ALL_METHODS = GRAPH_METHODS + ("scann",)
EF_LADDER = (64, 128, 256)
LEAVES_LADDER = (16, 32, 64)


def _cache(key: str, builder):
    os.makedirs(CACHE_DIR, exist_ok=True)
    path = os.path.join(CACHE_DIR, key + ".pkl")
    if os.path.exists(path):
        with open(path, "rb") as f:
            return pickle.load(f)
    val = builder()
    with open(path, "wb") as f:
        pickle.dump(jax.tree.map(np.asarray, val), f)
    return val


def _qtag(quant: str) -> str:
    """Cache-key suffix for the graph quant mode: artifacts built while a
    quantized tier is in play live in their own key space, so
    graph_quant="sq8" runs can never collide with cached f32 artifacts
    (nor vice versa) even if quantization ever perturbs a build input."""
    return "" if quant in (None, "none") else f"_{quant}"


def _method_quant(method: str) -> str:
    """Graph quant mode a benchmark method name implies."""
    return "sq8" if method.endswith("_sq8") else "none"


def get_dataset(name: str, quant: str = "none"):
    spec = BENCH_DATASETS[name]
    store, queries = make_dataset(spec, num_queries=NUM_QUERIES, seed=0)
    if quant == "sq8":
        store = quantize_store(store)
    return store, jnp.asarray(queries)


def get_graph(name: str, quant: str = "none"):
    from repro.core.hnsw import HNSWGraph
    store, _ = get_dataset(name, quant)

    def build():
        g = build_graph(store, m=16, ef_construction=64, seed=0)
        return (g.neighbors, g.node_level, g.entry_point)

    nb, lv, ep = _cache(f"graph_{name}{_qtag(quant)}", build)
    return HNSWGraph(neighbors=jnp.asarray(nb), node_level=jnp.asarray(lv),
                     entry_point=jnp.asarray(ep), m=16)


def get_scann(name: str, pca: bool = False, quant: str = "none"):
    from repro.core.scann import ScannIndex
    store, _ = get_dataset(name, quant)
    spec = BENCH_DATASETS[name]
    pca_dims = max(spec.dim // 8, 32) if (pca and spec.dim >= 256) else None

    def build():
        idx = build_scann(store, num_leaves=max(64, store.n // 128),
                          levels=2, pca_dims=pca_dims, seed=0)
        return jax.tree.map(np.asarray, idx)

    idx = _cache(f"scann_{name}_{'pca' if pca_dims else 'raw'}"
                 f"{_qtag(quant)}", build)
    return jax.tree.map(jnp.asarray, idx)


FAMILY_COUNT = 4


def _ftag(sel: float, num_families: int, seed: int) -> str:
    """Cache-key suffix for family-scoped artifacts (DESIGN.md §14):
    exclusion radii and partitioned graphs are built against a specific
    family catalog, so the catalog parameters ride the key the same way
    `_qtag` isolates quantized builds from f32 ones."""
    return f"_fam{num_families}_s{sel:g}_fs{seed}"


def get_families(name: str, sel: float, num_families: int = FAMILY_COUNT,
                 seed: int = 0) -> dict:
    """Cached clustered predicate families (tag -> packed bitmap)."""
    store, _ = get_dataset(name)
    return _cache(f"fams_{name}{_ftag(sel, num_families, seed)}",
                  lambda: generate_families(store, sel,
                                            num_families=num_families,
                                            seed=seed))


def get_family_bitmaps(name: str, sel: float,
                       num_families: int = FAMILY_COUNT, seed: int = 0,
                       quant: str = "none"):
    """((Q, W) bitmaps, (Q,) family assignment) for the bench queries —
    each query carries its family's bitmap verbatim (the exact-match
    contract of the selectivity-aware tiers)."""
    fams = get_families(name, sel, num_families, seed)
    _, queries = get_dataset(name, quant)
    bm, assign = assign_family_bitmaps(fams, int(queries.shape[0]),
                                       seed=seed + 1)
    return jnp.asarray(bm), assign


def get_exclusion(name: str, sel: float,
                  num_families: int = FAMILY_COUNT, seed: int = 0,
                  quant: str = "none"):
    """Cached FAVOR exclusion index (ladder + family-exact radii)."""
    store, _ = get_dataset(name, quant)
    fams = get_families(name, sel, num_families, seed)
    return _cache(f"excl_{name}{_ftag(sel, num_families, seed)}"
                  f"{_qtag(quant)}",
                  lambda: build_exclusion(store, families=fams))


def get_partitions(name: str, sel: float,
                   num_families: int = FAMILY_COUNT, seed: int = 0,
                   quant: str = "none"):
    """Cached JAG partitioned graph.  Only the per-family adjacency and
    row maps are pickled; the gathered sub-stores are rebuilt from the
    base store on load (`hnsw.gather_substore`) — same convention as
    `get_graph`, which caches (neighbors, level, entry) rather than the
    dataclass."""
    from repro.core.hnsw import (GraphPartition, HNSWGraph,
                                 PartitionedGraph, build_graph_partitioned,
                                 gather_substore)
    store, _ = get_dataset(name, quant)
    fams = get_families(name, sel, num_families, seed)

    def build():
        pg = build_graph_partitioned(store, fams, m=16, ef_construction=64,
                                     seed=0)
        return [(p.tag, np.asarray(p.bitmap), np.asarray(p.rows),
                 np.asarray(p.graph.neighbors),
                 np.asarray(p.graph.node_level),
                 np.asarray(p.graph.entry_point))
                for p in pg.partitions]

    raw = _cache(f"parts_{name}{_ftag(sel, num_families, seed)}"
                 f"{_qtag(quant)}", build)
    parts = tuple(GraphPartition(
        tag=tag, bitmap=bm, rows=rows, store=gather_substore(store, rows),
        graph=HNSWGraph(neighbors=jnp.asarray(nb),
                        node_level=jnp.asarray(lv),
                        entry_point=jnp.asarray(ep), m=16))
        for tag, bm, rows, nb, lv, ep in raw)
    return PartitionedGraph(partitions=parts, built_n=store.n)


def family_ground_truth(name: str, sel: float,
                        num_families: int = FAMILY_COUNT, seed: int = 0,
                        k: int = 10):
    store, queries = get_dataset(name)
    bm, _ = get_family_bitmaps(name, sel, num_families, seed)
    return filtered_knn(store, queries, bm, k)


def get_bitmaps(name: str, sel: float, corr: str, quant: str = "none"):
    store, queries = get_dataset(name, quant)

    # stable digest: hash() varies with PYTHONHASHSEED, which would make
    # cached bitmaps silently disagree with freshly generated ones; the
    # seed is part of the cache key so stale old-seed caches are ignored
    seed = zlib.crc32(repr((sel, corr)).encode()) % 9973

    def build():
        return np.asarray(generate_bitmaps(store, queries,
                                           WorkloadSpec(sel, corr),
                                           seed=seed))

    return jnp.asarray(_cache(f"bm_{name}_{sel}_{corr}_s{seed}"
                              f"{_qtag(quant)}", build))


def ground_truth(name: str, sel: float, corr: str, k: int = 10):
    store, queries = get_dataset(name)
    bm = get_bitmaps(name, sel, corr)
    return filtered_knn(store, queries, bm, k)


def mean_recall(ids, tid, k=10) -> float:
    return float(np.mean(np.asarray(
        jax.vmap(lambda f, t: recall_at_k(f, t, k))(ids, tid))))


def get_executor(name: str, method: str, use_pallas: bool = False,
                 storage=None, exclusion=None, partitions=None,
                 planner_candidates=None):
    """Executor-registry dispatch for a benchmark dataset: builds (cached)
    whichever components `method` needs and returns the executor.
    `storage` attaches a StorageEngine (build one with
    `get_storage_engine`) for measured page accounting.  The
    selectivity-aware tiers need their artifacts passed in (`exclusion=`
    from `get_exclusion`, `partitions=` from `get_partitions`) — they are
    family-catalog-scoped, so the registry can't build them from the
    method name alone.

    "scann_distributed" runs the mesh-sharded executor on this host's
    devices (leaves sharded, queries replicated) with per-query
    SearchStats riding the all-gather — so table6/fig10 can tabulate the
    distributed path next to the local ones.  No storage accounting
    (the collective pipeline carries counters, not page traces).

    "<strategy>_sq8" methods run the SQ8 quantized-traversal tier
    (DESIGN.md §9) — their dataset/graph artifacts use the quant-tagged
    cache keys."""
    quant = _method_quant(method)
    store, _ = get_dataset(name, quant)
    if method == "scann_distributed":
        # cached per dataset: re-sharding the index and dropping the
        # executor's jit cache at every grid point would re-compile the
        # collective program for identical params over and over
        ex = _DISTRIBUTED_EXECUTORS.get(name)
        if ex is None:
            from repro.core.distributed import (DistributedScannExecutor,
                                                shard_index)
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((jax.device_count(),), ("data",))
            sharded = shard_index(get_scann(name), store, mesh, "data")
            ex = _DISTRIBUTED_EXECUTORS[name] = \
                DistributedScannExecutor(sharded)
        return ex
    graph = index = None
    if method in ("scann", "scann_vmapped", "adaptive"):
        index = get_scann(name)
    if method not in ("scann", "scann_vmapped", "bruteforce"):
        graph = get_graph(name, quant)
    kw = {} if planner_candidates is None \
        else {"planner_candidates": tuple(planner_candidates)}
    return make_executor(method, store, graph=graph, index=index,
                         use_pallas=use_pallas, graph_m=16, storage=storage,
                         exclusion=exclusion, partitions=partitions, **kw)


_DISTRIBUTED_EXECUTORS: dict = {}


def get_sharded_executor(name: str, num_shards: int,
                         strategy: str = "sweeping", quant: str = "none",
                         storage=None):
    """Mesh-sharded graph executor over a benchmark dataset (DESIGN.md
    §13), cached per (dataset, shard count, strategy, quant) like
    `_DISTRIBUTED_EXECUTORS`: re-blocking the adjacency/heap/shadow tiers
    at every grid point would redo the host-side shard packing, and a
    fresh instance per point would thrash nothing (the collective jit
    cache is module-level) but waste the packing.

    `storage` attaches a ShardedStorageAccountant (build one with
    `get_sharded_storage`); storage-attached executors are NOT cached —
    the accountant carries mutable pool state owned by the caller — and
    at container bench scale the repacking they redo is trivial."""
    from repro.core.distributed import ShardedGraphExecutor
    key = (name, int(num_shards), strategy, quant)
    ex = _SHARDED_EXECUTORS.get(key)
    if ex is None:
        ex = _SHARDED_EXECUTORS[key] = ShardedGraphExecutor(
            get_graph(name, quant), get_dataset(name, quant)[0],
            num_shards, strategy=strategy, graph_quant=quant)
    if storage is None:
        return ex
    return ShardedGraphExecutor(ex.graph, ex.store, num_shards,
                                strategy=strategy, graph_quant=quant,
                                storage=storage)


def get_sharded_storage(name: str, num_shards: int, quant: str = "none",
                        capacity_frac: float = 1.0, policy: str = "lru"):
    """Per-shard StorageEngines (each holding capacity_frac / num_shards
    of the dataset's page space — the aggregate pool budget stays fixed
    as the shard count sweeps) wrapped in the accounting facade."""
    from repro.core.distributed import make_sharded_storage
    from repro.storage import make_storage_engine
    store, _ = get_dataset(name, quant)
    graph = get_graph(name, quant)
    engines = [make_storage_engine(
        store, graph=graph, capacity_frac=capacity_frac / num_shards,
        policy=policy) for _ in range(num_shards)]
    return make_sharded_storage(engines, store.n)


_SHARDED_EXECUTORS: dict = {}


def run_storage_measured(name: str, method: str, sel: float, params):
    """One cold-pool measured run at `params` (capacity = full page
    space): the shared protocol behind table6's measured-page columns and
    fig10's cold-miss penalty.  Returns the SearchResult (`.storage`
    carries the StorageStats)."""
    quant = _method_quant(method)
    store, queries = get_dataset(name, quant)
    bm = get_bitmaps(name, sel, "none", quant)
    eng = get_storage_engine(name, method, capacity_frac=1.0)
    return get_executor(name, method, storage=eng).search(queries, bm,
                                                          params)


def measured_graph_cycles(res, params, q_batch: int, dim: int) -> float:
    """Per-query SYSTEM cycles of a pooled graph run in the engine-true
    currency: quant-aware component costs from the measured counters
    (frontier `engine_scale`) plus the measured pool miss penalty — the
    same costing the planner predicts against (DESIGN.md §9).  Shared by
    bench_graph_quant and table4 so both report in ONE currency."""
    base = cycle_breakdown(
        res.stats, dim, SYSTEM,
        engine_scale(res.strategy, params, q_batch),
        graph_quant=params.graph_quant)["total"]
    return base + measured_miss_penalty(res.storage, q_batch, SYSTEM)


def heap_read_misses(res) -> int:
    """Physical page reads of the row-fetch segments (heap + qheap)."""
    return int(res.storage.misses.get("heap", 0)
               + res.storage.misses.get("qheap", 0))


def get_storage_engine(name: str, method: str = "adaptive", **kw):
    """StorageEngine over the dataset's page space, with the layouts the
    method needs (scann leaves / graph adjacency / heap + the always-laid
    qheap shadow segment)."""
    from repro.storage import make_storage_engine
    quant = _method_quant(method)
    store, _ = get_dataset(name, quant)
    index = get_scann(name) if method in ("scann", "scann_vmapped",
                                          "adaptive") else None
    graph = get_graph(name, quant) if method not in (
        "scann", "scann_vmapped", "bruteforce") else None
    return make_storage_engine(store, index=index, graph=graph, **kw)


def _ladder(method: str, k: int, tm: bool, page_accounting: str):
    """Param ladder per method (paper §5: climb until target recall)."""
    if method in ("scann", "scann_vmapped", "scann_distributed"):
        return [SearchParams(k=k, num_leaves_to_search=nl, reorder_factor=4,
                             scann_page_accounting=page_accounting)
                for nl in LEAVES_LADDER]
    if method in ("bruteforce",):
        return [SearchParams(k=k)]
    quant = _method_quant(method)
    strat = method[:-4] if quant == "sq8" else method
    ladder = []
    for ef in EF_LADDER:
        ef = max(ef, 2 * k)
        ladder.append(SearchParams(
            k=k, ef_search=ef, beam_width=max(512, 4 * ef), strategy=strat,
            max_hops=3000, translation_map=tm, graph_quant=quant,
            scann_page_accounting=page_accounting,
            batch_tuples=max(64, k * 8), max_rounds=16))
    return ladder


def run_method(name: str, method: str, sel: float, corr: str, k: int = 10,
               target_recall: float = 0.95, tm: bool = True,
               page_accounting: str = "batch"):
    """Tuning-ladder run (paper §5: highest QPS at 95% recall) through the
    executor registry.  Returns (recall, stats_row, wall_us_per_query,
    params_used).  `method` is any registered executor ("adaptive"
    included).

    `page_accounting` picks the ScaNN index-page counter semantics:
    "batch" amortizes each opened leaf over the query batch (the batched
    pipeline's real access pattern), "per_query" reproduces the paper's
    per-query accounting (Fig. 10/13)."""
    quant = _method_quant(method)
    store, queries = get_dataset(name, quant)
    bm = get_bitmaps(name, sel, corr, quant)
    _, tid = ground_truth(name, sel, corr, k)
    executor = get_executor(name, method)
    best = None
    if method == "adaptive":
        # the planner picks its own strategy; one balanced config
        ladder = [SearchParams(k=k, ef_search=128, beam_width=512,
                               max_hops=3000, translation_map=tm,
                               scann_page_accounting=page_accounting,
                               batch_tuples=max(64, k * 8), max_rounds=16)]
    else:
        ladder = _ladder(method, k, tm, page_accounting)
    for p in ladder:
        t0 = time.perf_counter()
        res = executor.search(queries, bm, p)
        jax.block_until_ready(res.ids)
        wall = (time.perf_counter() - t0) / queries.shape[0] * 1e6
        rec = mean_recall(res.ids, tid, k)
        best = (rec, stats_table_row(res.stats), wall, p)
        if rec >= target_recall:
            break
    return best


def emit(rows: list[dict], name: str) -> None:
    """Print benchmark rows as `name,us_per_call,derived` CSV lines."""
    for r in rows:
        derived = ";".join(f"{k}={v}" for k, v in r.items()
                           if k not in ("name", "us_per_call"))
        print(f"{r.get('name', name)},{r.get('us_per_call', 0):.1f},"
              f"{derived}")
