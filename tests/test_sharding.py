"""Mesh-sharded graph + storage tiers (DESIGN.md §13).

Lockstep (beam_exchange_interval=1) sharding must be INVISIBLE: the
owner-masked pmin/pmax reductions select the owning shard's bit-exact
values, so ids, dists, and every counter match the single-device engine
for any shard count.  Drift mode (E>1) trades recall for collective
volume.  Multi-device shard_map execution runs in a subprocess with 8
forced host devices (XLA locks the device count at first init)."""
import dataclasses
import json
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (SearchParams, WorkloadSpec, filtered_knn,
                        generate_bitmaps, make_executor, quantize_store,
                        recall_at_k)
from repro.core import costmodel
from repro.core.distributed import (ShardedGraphExecutor,
                                    make_sharded_storage,
                                    shard_graph_tiers)
from repro.core.types import SearchStats, sq8_quantize
from repro.data import DatasetSpec, make_dataset, make_dataset_streamed
from repro.data.datasets import _stream_block, _stream_centers
from repro.launch.mesh import make_mesh, validate_mesh_request
from repro.storage import make_storage_engine

REPO = pathlib.Path(__file__).resolve().parents[1]

STRATEGIES = ("unfiltered", "sweeping", "acorn", "navix", "iterative_scan")


@pytest.fixture(scope="module")
def sharding_setup(small_dataset, small_graph):
    store, queries = small_dataset
    store = quantize_store(store)
    bm = generate_bitmaps(store, queries, WorkloadSpec(0.3, "none"), seed=5)
    return store, queries, small_graph, bm


def _params(strategy, quant="none", E=1):
    return SearchParams(k=10, ef_search=32, beam_width=128,
                        strategy=strategy, max_hops=150, graph_quant=quant,
                        beam_exchange_interval=E,
                        batch_tuples=64, max_rounds=8)


def _stats_dict(stats):
    return {k: np.asarray(v) for k, v in stats.as_dict().items()}


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_lockstep_shard_count_invariance(sharding_setup, strategy):
    """1/2/4/8 shards × none/sq8: bit-identical ids, dists, counters."""
    store, queries, graph, bm = sharding_setup
    for quant in ("none", "sq8"):
        p = _params(strategy, quant)
        method = strategy if quant == "none" else f"{strategy}_sq8"
        base = make_executor(method, store, graph=graph).search(
            queries, bm, p)
        bstats = _stats_dict(base.stats)
        for S in (1, 2, 4, 8):
            ex = ShardedGraphExecutor(graph, store, S, strategy=strategy,
                                      graph_quant=quant)
            res = ex.search(queries, bm, p)
            assert np.array_equal(np.asarray(res.ids),
                                  np.asarray(base.ids)), (strategy, quant, S)
            assert np.array_equal(np.asarray(res.dists),
                                  np.asarray(base.dists)), (strategy,
                                                            quant, S)
            for k, v in _stats_dict(res.stats).items():
                assert np.array_equal(v, bstats[k]), (strategy, quant, S, k)


def test_drift_recall_monotone_in_exchange_interval(sharding_setup):
    """E=1 (lockstep) is exact w.r.t. the base engine; widening E only
    loses recall (within noise slack), never collapses it."""
    store, queries, graph, bm = sharding_setup
    _, tid = filtered_knn(store, queries, bm, 10)

    def rec(ids):
        return float(np.mean(np.asarray(jax.vmap(
            lambda f, t: recall_at_k(f, t, 10))(ids, tid))))

    ex = ShardedGraphExecutor(graph, store, 2, strategy="sweeping")
    recalls = {}
    for E in (1, 2, 4, 8):
        recalls[E] = rec(ex.search(queries, bm, _params("sweeping",
                                                        E=E)).ids)
    base = make_executor("sweeping", store, graph=graph)
    assert recalls[1] == rec(base.search(queries, bm,
                                         _params("sweeping")).ids)
    prev = recalls[1]
    for E in (2, 4, 8):
        assert recalls[E] <= prev + 0.05, recalls   # monotone within slack
        assert recalls[E] >= 0.5, recalls           # still a real search
        prev = recalls[E]


def test_drift_mode_validations(sharding_setup):
    store, queries, graph, bm = sharding_setup
    ex = ShardedGraphExecutor(graph, store, 2, strategy="iterative_scan")
    with pytest.raises(ValueError, match="emission buffer"):
        ex.search(queries, bm, _params("iterative_scan", E=2))
    engines = [make_storage_engine(store, graph=graph, capacity_frac=0.5)
               for _ in range(2)]
    acct = make_sharded_storage(engines, store.n)
    exs = ShardedGraphExecutor(graph, store, 2, storage=acct)
    with pytest.raises(ValueError, match="lockstep"):
        exs.search(queries, bm, _params("sweeping", E=4))
    with pytest.raises(ValueError, match="shards"):
        ShardedGraphExecutor(graph, store, 4, storage=acct)
    with pytest.raises(ValueError, match="sq8"):
        ShardedGraphExecutor(graph, store, 2, f32=False,
                             graph_quant="none")


def test_sharded_storage_aggregation(sharding_setup):
    """Per-shard pools see disjoint row slices; the merged StorageStats
    equals the single-engine accounting in every logical counter."""
    store, queries, graph, bm = sharding_setup
    p = _params("sweeping")
    single = make_storage_engine(store, graph=graph, capacity_frac=1.0)
    base = make_executor("sweeping", store, graph=graph,
                         storage=single).search(queries, bm, p)
    S = 2
    engines = [make_storage_engine(store, graph=graph, capacity_frac=1.0)
               for _ in range(S)]
    acct = make_sharded_storage(engines, store.n)
    ex = ShardedGraphExecutor(graph, store, S, strategy="sweeping",
                              storage=acct)
    res = ex.search(queries, bm, p)
    assert res.storage.logical == base.storage.logical
    assert len(acct.last_per_shard) == S
    for s in acct.last_per_shard:
        assert 0.0 <= s.hit_rate <= 1.0
    # each shard only touches its own rows: per-shard heap logical sums
    # to the single-engine heap logical
    heap = sum(s.logical.get("heap", 0) for s in acct.last_per_shard)
    assert heap == base.storage.logical.get("heap", 0)
    st = acct.state()
    assert st.capacity == sum(e.state().capacity for e in engines)


def test_serving_delegates_match_graph_executor(sharding_setup):
    """init/step/finalize (continuous-batching surface) are bit-equal to
    GraphExecutor's — the server consumes the sharded tier unchanged."""
    store, queries, graph, bm = sharding_setup
    p = _params("sweeping")
    base = make_executor("sweeping", store, graph=graph)
    ex = ShardedGraphExecutor(graph, store, 4, strategy="sweeping")
    st_b = base.init_frontier(queries, bm, p)
    st_s = ex.init_frontier(queries, bm, p)
    for _ in range(3):
        st_b = base.step_frontier(st_b, p, 20)
        st_s = ex.step_frontier(st_s, p, 20)
    db, ib, _ = base.finalize_frontier(st_b, p)[:3]
    ds, is_, _ = ex.finalize_frontier(st_s, p)[:3]
    assert np.array_equal(np.asarray(ib), np.asarray(is_))
    assert np.array_equal(np.asarray(db), np.asarray(ds))
    with pytest.raises(ValueError, match="lockstep"):
        ex.init_frontier(queries, bm, _params("sweeping", E=2))


def test_shard_tiers_partition(sharding_setup):
    """Blocked views cover every row exactly once, −1-pad the tail, and
    keep global ids in the adjacency."""
    store, queries, graph, bm = sharding_setup
    gv, sv = shard_graph_tiers(graph, store, 4)
    S, rps = 4, -(-store.n // 4)
    assert sv.vectors.shape == (S, rps, store.dim)
    flat = np.asarray(sv.vectors).reshape(S * rps, store.dim)[:store.n]
    assert np.array_equal(flat, np.asarray(store.vectors))
    nb = np.asarray(gv.neighbors)
    assert nb.shape[0] == S and nb.max() < store.n
    # local entries: each shard's entry is a row it owns (or −1)
    le = np.asarray(gv.local_entry)
    for s in range(S):
        if le[s] >= 0:
            assert s * rps <= le[s] < (s + 1) * rps


def test_mesh_validation_errors():
    validate_mesh_request((2, 4), ("data", "model"))
    with pytest.raises(ValueError, match="one name per dim"):
        validate_mesh_request((2, 4), ("data",))
    with pytest.raises(ValueError, match="non-positive"):
        validate_mesh_request((0,), ("data",))
    with pytest.raises(ValueError, match="duplicate"):
        validate_mesh_request((2, 2), ("data", "data"))
    with pytest.raises(ValueError, match="did you mean 'shard'"):
        validate_mesh_request((2,), ("shrad",))
    with pytest.raises(ValueError, match="divisible"):
        validate_mesh_request((3,), ("data",), num_devices=8)
    m = make_mesh((1,), ("shard",))
    assert m.axis_names == ("shard",)


def test_streamed_dataset_matches_batch_quantizer():
    """Streamed two-pass SQ8 is bit-equal to quantizing the materialized
    array; block RNG is deterministic and block_rows-stable for a fixed
    value; f32=False carries the same shadow with placeholder f32."""
    spec = DatasetSpec("t-stream", 3_000, 16, "ip", clusters=8)
    s1, q1 = make_dataset_streamed(spec, num_queries=6, seed=3,
                                   block_rows=512)
    s2, q2 = make_dataset_streamed(spec, num_queries=6, seed=3,
                                   block_rows=512)
    assert np.array_equal(np.asarray(s1.vectors), np.asarray(s2.vectors))
    assert np.array_equal(np.asarray(q1), np.asarray(q2))
    q, scale, mean = sq8_quantize(np.asarray(s1.vectors))
    assert np.array_equal(np.asarray(s1.q_vectors), q)
    assert np.array_equal(np.asarray(s1.q_scale), scale)
    assert np.array_equal(np.asarray(s1.q_mean), mean)
    # f32-free twin: same shadow, placeholder (zero-strided) f32 tier
    s3, q3 = make_dataset_streamed(spec, num_queries=6, seed=3,
                                   block_rows=512, f32=False)
    assert np.array_equal(np.asarray(s3.q_vectors), q)
    assert np.array_equal(np.asarray(q3), np.asarray(q1))
    assert np.asarray(s3.vectors).shape == (spec.n, spec.dim)
    assert not np.asarray(s3.vectors).any()
    # per-block streams: block contents don't depend on which other
    # blocks were generated
    centers = _stream_centers(spec, 3)
    blk = _stream_block(spec, centers, 3, 2, 1024, 1536, None)
    assert np.array_equal(blk, np.asarray(s1.vectors)[1024:1536])


def test_blocked_graph_builder_routed_path():
    """Force the routed/blocked code path (exact_threshold below n) and
    check the graph still navigates to high recall."""
    from repro.core.hnsw import build_graph_blocked
    spec = DatasetSpec("t-blocked", 2_500, 24, "l2", clusters=12)
    store, queries = make_dataset(spec, num_queries=6, seed=1)
    queries = jnp.asarray(queries)
    g = build_graph_blocked(store, m=12, ef_construction=32, seed=0,
                            exact_threshold=500)
    nb = np.asarray(g.neighbors)
    assert nb.max() < store.n and nb.min() >= -1
    words = (store.n + 31) // 32
    bm = jnp.ones((queries.shape[0], words), jnp.uint32) * jnp.uint32(
        0xFFFFFFFF)
    _, tid = filtered_knn(store, queries, bm, 10)
    res = make_executor("sweeping", store, graph=g).search(
        queries, bm, _params("sweeping"))
    rec = float(np.mean(np.asarray(jax.vmap(
        lambda f, t: recall_at_k(f, t, 10))(res.ids, tid))))
    assert rec >= 0.8, rec


def test_cost_model_sharded_terms():
    counters = {"distance_comps": 2_000.0, "hops": 400.0}
    p = _params("sweeping")
    assert costmodel.beam_exchange_bytes(counters, p, 1) == 0.0
    lock = costmodel.beam_exchange_bytes(counters, p, 8)
    assert lock == 8.0 * 2_000.0 * 2.0 * 7 / 8
    drift = costmodel.beam_exchange_bytes(
        counters, dataclasses.replace(p, beam_exchange_interval=4), 8)
    assert drift == 8.0 * p.ef_search * 100 * 7
    z = jnp.full((4,), 2_000, jnp.int32)
    stats = SearchStats(z, z, jnp.full((4,), 400, jnp.int32),
                        z // 10, z // 10, z * 0, z * 0)
    s1 = costmodel.sharded_cycle_summary(stats, p, 768, 1)
    s8 = costmodel.sharded_cycle_summary(stats, p, 768, 8)
    assert s8["collective_bytes"] > 0 and s1["collective_bytes"] == 0
    assert s8["modeled_qps"] / s1["modeled_qps"] >= 2.5
    # predict_cycles carries the same sharding terms
    shape = costmodel.IndexShape(n=1_000_000, dim=768, graph_m=16)
    c1 = costmodel.predict_cycles("sweeping", shape, p, 0.2)
    c8 = costmodel.predict_cycles("sweeping", shape, p, 0.2, num_shards=8)
    assert c8 < c1 and c8 > c1 / 8


_SUBPROCESS_SRC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import json
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import (SearchParams, WorkloadSpec, generate_bitmaps,
                            quantize_store)
    from repro.core.distributed import (ShardedGraphExecutor,
                                        sharded_graph_search_fn)
    from repro.core import build_graph
    from repro.data import DatasetSpec, make_dataset

    spec = DatasetSpec("t-shmap", 3000, 32, "l2", clusters=12)
    store, queries = make_dataset(spec, num_queries=6, seed=0)
    store = quantize_store(store)
    queries = jnp.asarray(queries)
    graph = build_graph(store, m=12, ef_construction=32, seed=0)
    bm = generate_bitmaps(store, queries, WorkloadSpec(0.3, "none"), seed=5)
    p = SearchParams(k=10, ef_search=32, beam_width=128,
                     strategy="sweeping", max_hops=150)
    out = {"devices": jax.device_count()}
    for S in (2, 8):
        fn = sharded_graph_search_fn(graph, store, S, p)
        d, ids, stats = fn(queries, bm)
        ref = ShardedGraphExecutor(graph, store, S,
                                   strategy="sweeping").search(queries,
                                                               bm, p)
        out[f"ids_eq_{S}"] = bool(np.array_equal(np.asarray(ids),
                                                 np.asarray(ref.ids)))
        out[f"d_eq_{S}"] = bool(np.array_equal(np.asarray(d),
                                               np.asarray(ref.dists)))
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_shard_map_matches_vmap_8dev():
    """The same shard body under real shard_map devices reproduces the
    single-process vmap executor bit-exactly."""
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS_SRC],
                          capture_output=True, text=True, cwd=REPO,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.splitlines()[-1])
    assert rec["devices"] == 8
    assert rec["ids_eq_2"] and rec["d_eq_2"]
    assert rec["ids_eq_8"] and rec["d_eq_8"]
