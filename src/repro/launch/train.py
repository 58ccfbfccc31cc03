"""Training launcher: `python -m repro.launch.train --arch <id> [...]`.

Container-scale by default (reduced config, CPU). On a real slice, pass
--full to use the exact assigned config and --mesh to pick the production
mesh; params/optimizer are sharded by the partition rules, the data
pipeline is deterministic-by-step, and checkpoints are preemption-safe —
the same invocation resumes after a failure (optionally on a different
device count: elastic restore reshards).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.data.pipeline import DataConfig, batch_for_step
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.sharding import batch_specs, opt_specs, param_specs, \
    to_named
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.train.loop import TrainConfig, Trainer, init_opt_state
from repro.launch.specs import make_smoke_batch


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--full", action="store_true",
                    help="use the full assigned config (needs a real slice)")
    ap.add_argument("--mesh", default=None,
                    help="'single'|'multi' production mesh, default unsharded")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    cfg = get_config(args.arch) if args.full else smoke_config(args.arch)
    bundle = build_model(cfg)
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(10, args.steps // 20))
    tc = TrainConfig(steps=args.steps, microbatches=args.microbatches,
                     checkpoint_dir=args.checkpoint_dir,
                     checkpoint_every=max(20, args.steps // 5),
                     grad_compression=args.grad_compression)

    if cfg.family == "encoder":
        def batch_fn(step):
            return make_smoke_batch(cfg, args.batch, args.seq, "train",
                                    seed=step)
    else:
        dcfg = DataConfig(cfg.vocab, args.seq, args.batch)

        def batch_fn(step):
            raw = batch_for_step(dcfg, step)
            b = {k: jnp.asarray(v) for k, v in raw.items()}
            if cfg.family == "vlm":
                rngp = np.random.RandomState(step)
                b["patch_embeds"] = jnp.asarray(rngp.randn(
                    args.batch, cfg.num_patches, cfg.d_model
                ).astype(np.float32) * 0.02)
            return b

    ctx = None
    if args.mesh:
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
        ctx = jax.set_mesh(mesh)
        ctx.__enter__()

    trainer = Trainer(bundle, opt_cfg, tc, batch_fn)
    params, opt_state, start = trainer.init_or_restore(jax.random.PRNGKey(0))
    print(f"arch={cfg.name} params={sum(np.prod(p.shape) for p in jax.tree.leaves(params)):,}")
    t0 = time.time()
    params, opt_state = trainer.run(params, opt_state, start)
    dt = time.time() - t0
    for h in trainer.history:
        print(f"step {h['step']:5d} loss {h['loss']:.4f} "
              f"gnorm {h['grad_norm']:.3f} {h['sec']*1e3:.0f}ms")
    toks = args.steps * args.batch * args.seq
    print(f"done: {args.steps} steps, {toks/dt:.0f} tok/s, "
          f"stragglers={len(trainer.stragglers)}")
    if ctx:
        ctx.__exit__(None, None, None)


if __name__ == "__main__":
    main()
