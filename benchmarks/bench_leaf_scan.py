"""Microbenchmark: query-batched vs vmapped single-query fused leaf scan.

Measures the tentpole claim directly: the batched kernel fetches each int8
leaf tile once per *batch* and scores it with one MXU (Q, d) × (d, C)
contraction, while `jax.vmap` of the single-query kernel re-streams every
tile per query.  Emits one JSON line (and writes it to
`BENCH_leaf_scan.json`) so the perf trajectory is tracked run-over-run.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.types import pack_bool_bitmap
from repro.kernels import ops

U, C, D = 12, 128, 128          # leaves × rows/leaf × dims (container scale)
BATCHES = (1, 8, 16, 32)
REPS = 5


def _time(fn, *args) -> float:
    jax.block_until_ready(fn(*args))          # compile + warm cache
    t0 = time.perf_counter()
    for _ in range(REPS):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / REPS * 1e6


def run(use_pallas: bool = True) -> dict:
    rng = np.random.RandomState(0)
    n_rows = U * C
    tiles = jnp.asarray(rng.randint(-127, 128, (U, C, D)).astype(np.int8))
    rowids = jnp.asarray(rng.permutation(n_rows).reshape(U, C).astype(
        np.int32))
    scale = jnp.asarray(np.abs(rng.randn(D)).astype(np.float32) * 0.02)
    mean = jnp.asarray(rng.randn(D).astype(np.float32) * 0.05)
    x = tiles.astype(jnp.float32) * scale + mean
    norms = jnp.sum(x * x, axis=-1)

    vmapped = jax.jit(jax.vmap(lambda q, bm: ops.leaf_scan(
        q, tiles, rowids, scale, mean, bm, "l2", use_pallas)))
    batched = jax.jit(lambda qs, bms: ops.leaf_scan_batched(
        qs, tiles, rowids, scale, mean, bms, norms, "l2", use_pallas))

    out = {"bench": "leaf_scan", "backend": jax.default_backend(),
           "use_pallas": use_pallas, "U": U, "C": C, "D": D, "points": []}
    for q in BATCHES:
        qs = jnp.asarray(rng.randn(q, D).astype(np.float32))
        bms = jnp.stack([pack_bool_bitmap(rng.rand(n_rows) < 0.5)
                         for _ in range(q)])
        t_v = _time(vmapped, qs, bms)
        t_b = _time(batched, qs, bms)
        out["points"].append({"batch": q, "vmapped_us": round(t_v, 1),
                              "batched_us": round(t_b, 1),
                              "speedup": round(t_v / t_b, 2)})
    return out


def main() -> None:
    result = run(use_pallas=True)
    line = json.dumps(result)
    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_leaf_scan.json")
    with open(path, "w") as f:
        f.write(line + "\n")
    print(line)


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
