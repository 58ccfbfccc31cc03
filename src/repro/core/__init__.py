"""Core filter-agnostic FVS library (the paper's contribution in JAX)."""
from repro.core.types import (METRIC_COS, METRIC_IP, METRIC_L2, AnytimeInfo,
                              SearchParams, SearchResult, SearchStats,
                              VectorStore, bitset_mark, bitset_words,
                              bitset_zeros, heap_pages_per_vector,
                              pack_bitmap, pack_bool_bitmap, probe_bitmap,
                              quant_heap_pages_per_vector, quantize_store,
                              recall_at_k, sq8_quantize, topk_smallest,
                              unpack_bitmap, bitmap_andnot, merge_topk)
from repro.core.workload import (CORRELATIONS, PAPER_SELECTIVITIES,
                                 WorkloadSpec, assign_family_bitmaps,
                                 generate_bitmaps, generate_families,
                                 generate_grid, generate_passing_rows)
from repro.core.bruteforce import filtered_knn, filtered_knn_partial, knn
from repro.core.exclusion import (ExclusionIndex, build_exclusion,
                                  ladder_rung, match_families, select_radii)
from repro.core.hnsw import (GraphPartition, HNSWGraph, PartitionedGraph,
                             build_graph, build_graph_partitioned)
from repro.core.graph_search import search_batch
from repro.core.scann import (ScannIndex, build_scann, leaves_within_budget,
                              scann_search_batch, scann_search_batch_vmapped)
from repro.core.costmodel import (LIBRARY, SYSTEM, CostConstants, IndexShape,
                                  budget_cycle_weights, cache_miss_penalty,
                                  component_cycles, cycle_breakdown,
                                  engine_scale, evaluate_anytime,
                                  fault_penalty, index_segment,
                                  linear_cycles, measured_miss_penalty,
                                  modeled_qps, predict_counters,
                                  predict_cycles, stats_table_row)
from repro.core.executor import (AdaptivePlanner, BruteForceExecutor,
                                 DeltaExecutor, Executor, GraphExecutor,
                                 PartitionedGraphExecutor, ScannExecutor,
                                 SearchPlan, index_shape, make_executor,
                                 EXCL_METHODS, GRAPH_SQ8_METHODS,
                                 PARTITIONED_METHODS, REGISTERED_METHODS)
from repro.core.mutable import (MergedResult, MutableIndex,
                                rebuild_oracle_store)

__all__ = [
    "METRIC_COS", "METRIC_IP", "METRIC_L2", "AnytimeInfo",
    "budget_cycle_weights", "evaluate_anytime", "fault_penalty",
    "filtered_knn_partial", "leaves_within_budget", "linear_cycles",
    "SearchParams", "SearchResult",
    "SearchStats", "VectorStore", "heap_pages_per_vector", "pack_bitmap",
    "pack_bool_bitmap", "probe_bitmap", "quant_heap_pages_per_vector",
    "quantize_store", "recall_at_k", "sq8_quantize", "topk_smallest",
    "unpack_bitmap", "bitset_mark", "bitset_words", "bitset_zeros",
    "CORRELATIONS", "PAPER_SELECTIVITIES", "WorkloadSpec",
    "generate_bitmaps", "generate_grid", "generate_passing_rows",
    "filtered_knn", "knn", "HNSWGraph", "build_graph",
    "search_batch", "ScannIndex", "build_scann", "scann_search_batch",
    "scann_search_batch_vmapped", "LIBRARY", "SYSTEM", "CostConstants",
    "IndexShape", "cache_miss_penalty", "component_cycles",
    "cycle_breakdown", "engine_scale", "index_segment",
    "measured_miss_penalty", "modeled_qps", "predict_counters",
    "predict_cycles", "stats_table_row",
    "AdaptivePlanner", "BruteForceExecutor", "Executor", "GraphExecutor",
    "PartitionedGraphExecutor", "ScannExecutor", "SearchPlan",
    "index_shape", "make_executor", "EXCL_METHODS", "GRAPH_SQ8_METHODS",
    "PARTITIONED_METHODS", "REGISTERED_METHODS",
    "ExclusionIndex", "build_exclusion", "ladder_rung", "match_families",
    "select_radii", "GraphPartition", "PartitionedGraph",
    "build_graph_partitioned", "generate_families", "assign_family_bitmaps",
    "bitmap_andnot", "merge_topk", "DeltaExecutor",
    "MergedResult", "MutableIndex", "rebuild_oracle_store",
]
