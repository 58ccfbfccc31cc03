"""End-to-end training driver: a ~20M-param llama-family model for a few
hundred steps on CPU, with checkpointing, deterministic data, and resume.

    PYTHONPATH=src python examples/train_lm.py [--steps 200]

(on a real slice: `python -m repro.launch.train --arch llama3.2-3b --full
--mesh single` runs the assigned config on the production mesh.)
"""
import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses
import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.data.pipeline import DataConfig, batch_for_step
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.train.loop import TrainConfig, Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    args = ap.parse_args()

    cfg = dataclasses.replace(smoke_config("llama3.2-3b"),
                              d_model=256, n_layers=6, d_ff=1024,
                              vocab=2048, n_heads=8, n_kv=4, d_head=32)
    bundle = build_model(cfg)
    dcfg = DataConfig(cfg.vocab, seq_len=256, global_batch=8)

    def batch_fn(step):
        return {k: jnp.asarray(v) for k, v in batch_for_step(dcfg,
                                                             step).items()}

    ckpt = tempfile.mkdtemp(prefix="vexa_ckpt_")
    tc = TrainConfig(steps=args.steps, checkpoint_every=args.steps // 4,
                     checkpoint_dir=ckpt, log_every=20)
    opt = AdamWConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps)
    trainer = Trainer(bundle, opt, tc, batch_fn)
    params, opt_state, start = trainer.init_or_restore(jax.random.PRNGKey(0))
    n = sum(int(x.size) for x in jax.tree.leaves(params))
    print(f"params: {n/1e6:.1f}M  steps: {args.steps}  ckpt: {ckpt}")
    t0 = time.time()
    trainer.run(params, opt_state, start)
    dt = time.time() - t0
    for h in trainer.history:
        print(f"  step {h['step']:4d}  loss {h['loss']:.4f}  "
              f"gnorm {h['grad_norm']:.2f}")
    toks = args.steps * dcfg.global_batch * dcfg.seq_len
    print(f"throughput: {toks/dt:,.0f} tok/s  "
          f"(loss {trainer.history[0]['loss']:.3f} -> "
          f"{trainer.history[-1]['loss']:.3f})")


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
