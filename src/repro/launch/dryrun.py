import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and extract roofline terms (assignment MULTI-POD DRY-RUN
and ROOFLINE ANALYSIS blocks).

MUST be run as its own process (`python -m repro.launch.dryrun ...`): the
XLA_FLAGS line above executes before any other import so the 512 placeholder
devices exist before jax initializes.  Never import this module from tests
or benchmarks.

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all            # every live cell, subprocesses
"""
import argparse
import json
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, applicable_shapes, get_config
from repro.configs.registry import ARCH_IDS
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import (batch_specs, cache_specs, opt_specs,
                                   param_specs, to_named)
from repro.launch.jaxpr_cost import step_cost
from repro.launch.specs import input_specs
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.train.loop import TrainConfig, init_opt_state, make_train_step

# v5e hardware constants (ROOFLINE ANALYSIS block)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # B/s / chip
ICI_BW = 50e9                # B/s / link

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1,
                "f8e5m2": 1, "s4": 1, "u4": 1}

_COLL_RE = re.compile(
    r"=\s+(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COMP_RE = re.compile(r"^(?:ENTRY )?%?([\w.\-]+)\s*\(.*\)\s*->")
_WHILE_BODY_RE = re.compile(r"body=%?([\w.\-]+)")


def _shape_bytes(text: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo: str, loop_multiplier: int = 1) -> dict[str, float]:
    """Per-device collective traffic from optimized HLO text, keyed by op.

    Bytes = result-shape bytes of each collective (for `-start` tuples, the
    last tuple element — the destination buffer).  Ops inside `while` bodies
    (the scan-over-layers) are multiplied by `loop_multiplier`, since the
    printed body executes once per layer.  This is exact for all-gather /
    reduce-scatter payloads and within 2× for ring all-reduce (which moves
    ~2·(n−1)/n · bytes); EXPERIMENTS.md states the convention.
    """
    per_comp: dict[str, dict[str, float]] = {}
    while_bodies: set[str] = set()
    comp = "__entry__"
    for line in hlo.splitlines():
        mc = _COMP_RE.match(line.strip())
        if mc and "=" not in line.split("(")[0]:
            comp = mc.group(1)
        for mb in _WHILE_BODY_RE.finditer(line):
            while_bodies.add(mb.group(1))
        m = _COLL_RE.search(line)
        if not m:
            continue
        result, kind = m.group(1), m.group(2)
        if result.startswith("("):
            shapes = _SHAPE_RE.findall(result)
            if shapes:
                dt, dims = shapes[-1]
                result = f"{dt}[{dims}]"
        b = _shape_bytes(result)
        # XLA:CPU promotes bf16 all-reduces to f32 ("*_promo" reducers);
        # on TPU they stay bf16 — count at the unpromoted width.
        if "promo" in line:
            b *= 0.5
        per_comp.setdefault(comp, {}).setdefault(kind, 0.0)
        per_comp[comp][kind] += b
    out: dict[str, float] = {}
    for comp_name, kinds in per_comp.items():
        mult = loop_multiplier if comp_name in while_bodies else 1
        for kind, b in kinds.items():
            out[kind] = out.get(kind, 0.0) + b * mult
    return out


def analytic_model_flops(cfg, shape) -> float:
    """Useful-FLOPs estimate (no remat, no capacity waste): matmul params ×
    6·tokens (train) / 2·tokens (inference) + attention/SSM state terms."""
    tokens = shape.global_batch * (shape.seq_len
                                   if shape.kind != "decode" else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    d, hd = cfg.d_model, cfg.head_dim
    # per-token matmul params, non-embedding (embed lookup is a gather)
    if cfg.family == "ssm":
        per_layer = 4 * d * d + 2 * d * cfg.d_ff + d * LORA_FLOPS_DIM
    else:
        attn_p = d * cfg.n_heads * hd * 2 + d * cfg.n_kv * hd * 2
        if cfg.family == "moe":
            ffn_p = cfg.moe_top_k * 3 * d * cfg.d_ff + d * cfg.n_experts
        else:
            ffn_p = 3 * d * cfg.d_ff
        per_layer = attn_p + ffn_p
        if cfg.family == "hybrid":
            d_in = 2 * d
            mamba_p = d * (2 * d_in + 2 * cfg.ssm_state) + d_in * d
            n_attn = cfg.n_layers // max(cfg.attn_every, 1)
            total_p = cfg.n_layers * mamba_p + n_attn * (
                attn_p + 3 * d * cfg.d_ff)
            per_layer = None
    if cfg.family == "hybrid":
        matmul = total_p
    else:
        matmul = cfg.n_layers * per_layer
    matmul += d * (cfg.num_classes if cfg.family == "encoder" else cfg.vocab)
    flops = mult * matmul * tokens
    # attention context term (scores + pv): fwd = 4·hd·H·ctx per token
    if cfg.family not in ("ssm",):
        ctx_layers = []
        if cfg.family == "hybrid":
            n_attn = cfg.n_layers // max(cfg.attn_every, 1)
            ctx_layers = [("full", n_attn)]
        elif cfg.global_every > 1 and cfg.window > 0:
            g = cfg.n_layers // cfg.global_every
            ctx_layers = [("win", g * (cfg.global_every - 1)), ("full", g)]
        else:
            ctx_layers = [("win" if cfg.window > 0 else "full",
                           cfg.n_layers)]
        t = shape.seq_len
        for kindw, n_l in ctx_layers:
            if shape.kind == "decode":
                ctx = min(cfg.window, t) if (kindw == "win" or (
                    cfg.family == "hybrid" and t > cfg.shared_attn_window)
                ) else t
                if cfg.family == "hybrid":
                    ctx = min(cfg.shared_attn_window, t)
                per_tok = 4 * hd * cfg.n_heads * ctx
            else:
                ctx = min(cfg.window, t) if kindw == "win" else t
                avg = ctx if kindw == "win" else t / 2
                per_tok = 4 * hd * cfg.n_heads * avg
            flops += (3.0 if shape.kind == "train" else 1.0) * \
                n_l * per_tok * tokens
    # SSM state terms
    if cfg.family in ("ssm", "hybrid"):
        if cfg.family == "ssm":
            n_heads = d // 64
            per_tok = 4 * n_heads * 64 * 64          # wkv state update+read
            flops += (3.0 if shape.kind == "train" else 1.0) * \
                cfg.n_layers * per_tok * tokens
        else:
            d_in = 2 * d
            nh = d_in // 64
            per_tok = 4 * nh * 64 * cfg.ssm_state
            flops += (3.0 if shape.kind == "train" else 1.0) * \
                cfg.n_layers * per_tok * tokens
    return float(flops)


LORA_FLOPS_DIM = 2 * 32 * 6   # rwkv ddlerp loras (5 mix + decay)


def _flatten_memory_analysis(ma) -> dict:
    keys = ("generated_code_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "alias_size_in_bytes",
            "temp_size_in_bytes", "host_generated_code_size_in_bytes",
            "host_argument_size_in_bytes", "host_output_size_in_bytes",
            "host_alias_size_in_bytes", "host_temp_size_in_bytes")
    out = {}
    for k in keys:
        if hasattr(ma, k):
            out[k] = int(getattr(ma, k))
    return out


def lower_cell(arch_id: str, shape_name: str, multi_pod: bool = False,
               overrides: dict | None = None) -> dict:
    import dataclasses
    cfg = get_config(arch_id)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    bundle = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()

    with jax.set_mesh(mesh):
        key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
        pshape = jax.eval_shape(bundle.init, key_sds)
        pspec = param_specs(cfg, pshape, mesh)
        pshard = to_named(pspec, mesh)
        batch = input_specs(cfg, shape_name)
        bshard = to_named(batch_specs(cfg, batch, mesh), mesh)

        if shape.kind == "train":
            opt_cfg = AdamWConfig(
                state_dtype="bfloat16" if cfg.param_dtype == "bfloat16"
                else "float32")
            tc = TrainConfig(microbatches=1)
            step = make_train_step(bundle, opt_cfg, tc, donate=False)
            oshape = jax.eval_shape(
                lambda p: init_opt_state(bundle, p, opt_cfg, tc), pshape)
            oshard = to_named(opt_specs(cfg, oshape, pspec, mesh), mesh)
            jfn = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                          donate_argnums=(0, 1))
            jcost = step_cost(step, pshape, oshape, batch)
            lowered = jfn.lower(pshape, oshape, batch)
        elif shape.kind == "prefill":
            fn = lambda p, b: bundle.prefill(p, b)
            jfn = jax.jit(fn, in_shardings=(pshard, bshard))
            jcost = step_cost(fn, pshape, batch)
            lowered = jfn.lower(pshape, batch)
        else:  # decode
            cshape = jax.eval_shape(
                lambda: bundle.init_cache(shape.global_batch, shape.seq_len))
            cshard = to_named(cache_specs(cfg, cshape, mesh), mesh)
            fn = lambda p, c, b: bundle.decode(
                p, c, b, jnp.int32(shape.seq_len - 1))
            jfn = jax.jit(fn, in_shardings=(pshard, cshard, bshard),
                          donate_argnums=(1,))
            jcost = step_cost(fn, pshape, cshape, batch)
            lowered = jfn.lower(pshape, cshape, batch)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        cost = compiled.cost_analysis() or {}
        try:
            mem = _flatten_memory_analysis(compiled.memory_analysis())
        except Exception as e:  # backend-dependent
            mem = {"error": str(e)[:200]}
        hlo = compiled.as_text()
        if cfg.attn_every > 1:
            loop_mult = cfg.n_layers // cfg.attn_every
        elif cfg.global_every > 1 and cfg.window > 0:
            loop_mult = cfg.n_layers // cfg.global_every
        else:
            loop_mult = cfg.n_layers
        coll = collective_bytes(hlo, loop_multiplier=loop_mult)

    # jaxpr-exact totals (scan bodies x length); XLA's numbers kept raw
    flops_dev = jcost.flops / chips
    bytes_dev = jcost.bytes / chips
    xla_flops_dev = float(cost.get("flops", 0.0))
    xla_bytes_dev = float(cost.get("bytes accessed", 0.0))
    coll_dev = sum(coll.values())
    # roofline terms (seconds); cost_analysis is per-device (SPMD module)
    t_comp = flops_dev / PEAK_FLOPS
    t_mem = bytes_dev / HBM_BW
    t_coll = coll_dev / ICI_BW
    terms = {"compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll}
    dom = max(terms, key=terms.get)

    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    model_flops = analytic_model_flops(cfg, shape)
    hlo_flops_total = flops_dev * chips
    return {
        "arch": arch_id, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": flops_dev, "bytes_per_device": bytes_dev,
        "xla_flops_per_device_loop_once": xla_flops_dev,
        "xla_bytes_per_device_loop_once": xla_bytes_dev,
        "collective_bytes_per_device": coll_dev,
        "collectives": coll, "memory_analysis": mem,
        "roofline": {**{k: float(v) for k, v in terms.items()},
                     "dominant": dom},
        "model_flops": float(model_flops),
        "hlo_flops_total": float(hlo_flops_total),
        "useful_flops_ratio": float(model_flops / hlo_flops_total)
        if hlo_flops_total else None,
        "params": int(n_params), "active_params": int(n_active),
    }


def run_all(multi_pod: bool, out_path: str, archs=None, shapes=None) -> int:
    """Drive every live cell in a fresh subprocess (compile isolation)."""
    fails = 0
    results = []
    for arch in (archs or ARCH_IDS):
        cfg = get_config(arch)
        for shape in (shapes or applicable_shapes(cfg)):
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--json"]
            if multi_pod:
                cmd.append("--multi-pod")
            t0 = time.time()
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=3600)
            if proc.returncode != 0:
                fails += 1
                print(f"FAIL {arch} {shape}: {proc.stderr[-500:]}",
                      flush=True)
                results.append({"arch": arch, "shape": shape,
                                "error": proc.stderr[-2000:]})
            else:
                rec = json.loads(proc.stdout.splitlines()[-1])
                results.append(rec)
                r = rec["roofline"]
                print(f"OK   {arch:24s} {shape:12s} dom={r['dominant']:12s}"
                      f" comp={r['compute_s']:.4f}s mem={r['memory_s']:.4f}s"
                      f" coll={r['collective_s']:.4f}s"
                      f" ({time.time()-t0:.0f}s)", flush=True)
            with open(out_path, "w") as f:
                json.dump(results, f, indent=1)
    return fails


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--override", default=None,
                    help="JSON dict of ArchConfig overrides (perf sweeps)")
    args = ap.parse_args()

    if args.all:
        fails = run_all(args.multi_pod, args.out)
        sys.exit(1 if fails else 0)

    overrides = json.loads(args.override) if args.override else None
    rec = lower_cell(args.arch, args.shape, args.multi_pod, overrides)
    if args.json:
        print(json.dumps(rec))
    else:
        print(json.dumps(rec, indent=2))


if __name__ == "__main__":
    main()
