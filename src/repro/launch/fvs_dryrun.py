import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Dry-run of the paper's technique AT SCALE: distributed filtered vector
search over the production mesh (EXPERIMENTS.md §Perf, paper-technique cell).

A 10M-row × 768-d store (the paper's cohere10m scale) is sharded across all
mesh devices (leaves + heap rows local, queries replicated); the jitted
search step is lowered + compiled with ShapeDtypeStructs only, and the
three roofline terms extracted exactly like the LM cells.

  PYTHONPATH=src python -m repro.launch.fvs_dryrun [--multi-pod] \
      [--n 10000000] [--dim 768] [--queries 128] [--leaves-searched 256]
"""
import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.scann import ScannIndex
from repro.core.types import SearchParams, VectorStore
from repro.core.distributed import DistributedScannExecutor, ShardedFVS
from repro.launch.dryrun import (HBM_BW, ICI_BW, PEAK_FLOPS,
                                 collective_bytes)
from repro.launch.jaxpr_cost import step_cost
from repro.launch.mesh import make_production_mesh


def abstract_sharded_fvs(mesh, n: int, dim: int, leaf_rows: int,
                         axis: str = "data") -> tuple[ShardedFVS, dict]:
    """Build a ShapeDtypeStruct-only ShardedFVS (no allocation)."""
    num_leaves = -(-n // leaf_rows)
    nd = mesh.shape[axis]
    num_leaves += (-num_leaves) % nd
    cap = leaf_rows + (-leaf_rows) % 8
    words = (n + 31) // 32
    sds = jax.ShapeDtypeStruct
    idx = ScannIndex(
        leaf_tiles=sds((num_leaves, cap, dim), jnp.int8),
        leaf_rowids=sds((num_leaves, cap), jnp.int32),
        leaf_centroids=sds((num_leaves, dim), jnp.float32),
        scale=sds((dim,), jnp.float32), mean=sds((dim,), jnp.float32),
        branch_centroids=sds((1, dim), jnp.float32),
        branch_leaves=sds((1, num_leaves), jnp.int32),
        pca=sds((dim + 1, dim), jnp.float32), metric="l2", levels=1)
    store = VectorStore(vectors=sds((n, dim), jnp.float32),
                        norms_sq=sds((n,), jnp.float32), metric="l2")
    shardings = dict(
        leaf_tiles=NamedSharding(mesh, P(axis, None, None)),
        leaf_rowids=NamedSharding(mesh, P(axis, None)),
        leaf_centroids=NamedSharding(mesh, P(axis, None)),
        rep=NamedSharding(mesh, P()),
        vectors=NamedSharding(mesh, P(axis, None)),
        norms=NamedSharding(mesh, P(axis)),
    )
    return ShardedFVS(index=idx, store=store, mesh=mesh, axis=axis), \
        {"words": words}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--n", type=int, default=10_000_000)
    ap.add_argument("--dim", type=int, default=768)
    ap.add_argument("--queries", type=int, default=128)
    ap.add_argument("--leaf-rows", type=int, default=512)
    ap.add_argument("--leaves-searched", type=int, default=256)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--pallas", action="store_true")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    chips = mesh.devices.size
    sharded, meta = abstract_sharded_fvs(mesh, args.n, args.dim,
                                         args.leaf_rows)
    params = SearchParams(k=args.k,
                          num_leaves_to_search=args.leaves_searched,
                          reorder_factor=4)
    executor = DistributedScannExecutor(sharded, use_pallas=args.pallas,
                                        heap_layout="leaf_ordered")
    t0 = time.time()
    with jax.set_mesh(mesh):
        fn = executor.raw_search_fn(params)
        idx, store = sharded.index, sharded.store
        sargs = (idx.leaf_tiles, idx.leaf_rowids, idx.leaf_centroids,
                 idx.scale, idx.mean, idx.pca, store.vectors,
                 store.norms_sq,
                 jax.ShapeDtypeStruct((args.queries, args.dim), jnp.float32),
                 jax.ShapeDtypeStruct((args.queries, meta["words"]),
                                      jnp.uint32))
        axis = sharded.axis
        in_sh = (NamedSharding(mesh, P(axis, None, None)),
                 NamedSharding(mesh, P(axis, None)),
                 NamedSharding(mesh, P(axis, None)),
                 NamedSharding(mesh, P()), NamedSharding(mesh, P()),
                 NamedSharding(mesh, P()),
                 NamedSharding(mesh, P(axis, None)),
                 NamedSharding(mesh, P(axis)),
                 NamedSharding(mesh, P()), NamedSharding(mesh, P()))
        jc = step_cost(fn, *sargs)
        lowered = jax.jit(fn, in_shardings=in_sh).lower(*sargs)
        compiled = lowered.compile()
        hlo = compiled.as_text()
        coll = collective_bytes(hlo, loop_multiplier=1)
        try:
            ma = compiled.memory_analysis()
            mem = {"argument_gb": ma.argument_size_in_bytes / 1e9,
                   "temp_gb": ma.temp_size_in_bytes / 1e9}
        except Exception:
            mem = {}
    flops_dev = jc.flops / chips
    bytes_dev = jc.bytes / chips
    coll_dev = sum(coll.values())
    rec = {
        "cell": "distributed-filtered-scann-serving",
        "mesh": "2x16x16" if args.multi_pod else "16x16", "chips": chips,
        "store": {"n": args.n, "dim": args.dim,
                  "leaves_searched": args.leaves_searched,
                  "batch_queries": args.queries},
        "compile_s": round(time.time() - t0, 1),
        "roofline": {
            "compute_s": flops_dev / PEAK_FLOPS,
            "memory_s": bytes_dev / HBM_BW,
            "collective_s": coll_dev / ICI_BW,
        },
        "collectives": coll, "memory_analysis": mem,
        "queries_per_s_bound": args.queries / max(
            flops_dev / PEAK_FLOPS, bytes_dev / HBM_BW,
            coll_dev / ICI_BW, 1e-12),
    }
    print(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
