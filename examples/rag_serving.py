"""Retrieval-augmented serving: the paper's FVS inside a serving stack.

A llama-family LM is paired with a sharded filtered vector store; each
request carries a structured predicate (simulated as a bitmap), retrieval
runs the filtered ScaNN search across the device mesh, and the retrieved
document chunks are spliced into the prompt before generation — the
paper's introduction e-commerce query, end to end.

    PYTHONPATH=src python examples/rag_serving.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.core import SearchParams, WorkloadSpec, generate_bitmaps
from repro.core.distributed import (DistributedScannExecutor,
                                    build_sharded_scann)
from repro.data import DatasetSpec, make_dataset
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.serving import RetrievalAugmentedServer, ServeEngine


def main() -> None:
    cfg = smoke_config("llama3.2-3b")
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)

    # document store: 4096 chunks with embeddings + token payloads
    spec = DatasetSpec("docs", 4096, 64, "l2", clusters=16)
    store, _ = make_dataset(spec, num_queries=1)
    docs = rng.randint(0, cfg.vocab, (4096, 8)).astype(np.int32)
    mesh = make_mesh((jax.device_count(),), ("data",))
    sharded = build_sharded_scann(store, mesh, "data", num_leaves=64,
                                  levels=1)
    server = RetrievalAugmentedServer(
        bundle, params, DistributedScannExecutor(sharded),
        SearchParams(k=4, num_leaves_to_search=32), docs, chunk_len=8)

    # two requests with different predicates (20% vs 5% selectivity)
    prompts = rng.randint(0, cfg.vocab, (2, 16)).astype(np.int32)
    q_embed = jnp.asarray(rng.randn(2, 64).astype(np.float32))
    bm = jnp.concatenate([
        generate_bitmaps(store, q_embed[:1], WorkloadSpec(0.2, "none"), 1),
        generate_bitmaps(store, q_embed[1:], WorkloadSpec(0.05, "none"), 2),
    ])
    res = server.retrieve(prompts, bm)
    print("retrieved ids per request (filtered):", res.ids.tolist())
    print("augmented prompt length:", res.tokens.shape[1])

    engine = ServeEngine(bundle, params, max_seq=res.tokens.shape[1] + 16,
                         batch_size=2)
    out = engine.generate(res.tokens, max_new_tokens=12)
    print("generated token ids:", out.tolist())
    print(f"decode throughput: {engine.stats.decoded_tokens} tokens")


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
