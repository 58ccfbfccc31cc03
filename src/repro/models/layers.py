"""Transformer building blocks — pure-functional, pytree params.

Conventions:
  * params are nested dicts of jnp arrays; layer-stacked params have a
    leading (L, ...) axis consumed by `lax.scan` (O(1) compile in depth).
  * activations: (B, T, D); compute dtype per config (bf16 default), norms
    and softmax accumulate in f32.
  * `shard(x, *axes)` applies a sharding constraint iff a mesh is active —
    model code is mesh-agnostic and runs unsharded in unit tests.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig

BATCH_AXES = ("pod", "data")


def shard(x: jax.Array, *axes):
    """with_sharding_constraint that no-ops without an active mesh, drops
    axis names the mesh doesn't have, and drops axes that don't divide the
    dim (avoids GSPMD forced-remat on e.g. 8 kv heads over a 16-way axis)."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or m.empty:
        return x
    sizes = dict(zip(m.axis_names, m.axis_sizes))

    def keep(dim: int, a):
        names = a if isinstance(a, (tuple, list)) else (a,)
        kept, prod = [], 1
        for n in names:
            if n is None or n not in sizes:
                continue
            if dim % (prod * sizes[n]) == 0:
                kept.append(n)
                prod *= sizes[n]
        if not kept:
            return None
        return tuple(kept) if len(kept) > 1 else kept[0]

    spec = P(*[keep(d, a) for d, a in zip(x.shape, axes)])
    return jax.lax.with_sharding_constraint(x, spec)


def act_spec(cfg: ArchConfig, kind: str):
    """Activation sharding templates per scheme (DESIGN.md §6).

    tp — Megatron-style: heads/ffn-hidden/vocab over `model`.
    sp — sequence-parallel: seq over `model`, weights FSDP over `data`;
         K/V gathered for attention (the §Perf beyond-baseline scheme).
    """
    if cfg.sharding_scheme == "sp":
        return {
            "resid": (BATCH_AXES, "model", None),
            "heads": (BATCH_AXES, "model", None, None),
            "kv": (BATCH_AXES, None, None, None),
            "ffn": (BATCH_AXES, "model", None),
            "logits": (BATCH_AXES, "model", None),
        }[kind]
    return {
        "resid": (BATCH_AXES, None, None),
        "heads": (BATCH_AXES, None, "model", None),
        "kv": (BATCH_AXES, None, "model", None),
        "ffn": (BATCH_AXES, None, "model"),
        "logits": (BATCH_AXES, None, "model"),
    }[kind]


def shard_act(x: jax.Array, cfg: ArchConfig, kind: str):
    return shard(x, *act_spec(cfg, kind))


def cdtype(cfg: ArchConfig):
    return jnp.dtype(cfg.compute_dtype)


def pdtype(cfg: ArchConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# Norms / embeddings / rope
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(ms + eps)) * w.astype(jnp.float32)
            ).astype(x.dtype)


def init_dense(key, d_in: int, d_out: int, dtype, scale: float = 1.0):
    std = scale / np.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * std
            ).astype(dtype)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., T, H, hd), positions: (..., T)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., T, hd/2)
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA / MQA, full or sliding-window, flash-style blocked)
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ArchConfig) -> dict:
    ks = jax.random.split(key, 5)
    hd, dt = cfg.head_dim, pdtype(cfg)
    return {
        "wq": init_dense(ks[0], cfg.d_model, cfg.n_heads * hd, dt),
        "wk": init_dense(ks[1], cfg.d_model, cfg.n_kv * hd, dt),
        "wv": init_dense(ks[2], cfg.d_model, cfg.n_kv * hd, dt),
        "wo": init_dense(ks[3], cfg.n_heads * hd, cfg.d_model, dt),
        "norm": jnp.ones((cfg.d_model,), jnp.float32),
    }


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int = 0,
                    q_offset=0, kv_len: Optional[jax.Array] = None,
                    block: int = 512) -> jax.Array:
    """Blocked (flash-style) attention in pure JAX.

    q: (B, Tq, H, hd); k, v: (B, Tk, KV, hd). GQA via head grouping.
    window > 0 limits attention to the last `window` key positions
    (sliding-window causal).  kv_len masks a padded cache (decode).
    Memory: O(Tq × block) — required for the 32k/500k shapes.
    """
    b, tq, h, hd = q.shape
    tk, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = 1.0 / np.sqrt(hd)
    qh = (q * scale).reshape(b, tq, kv, g, hd)
    block = min(block, tk)
    nblk = -(-tk // block)
    pad = nblk * block - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nblk, block, kv, hd)
    vb = v.reshape(b, nblk, block, kv, hd)
    qpos = (jnp.arange(tq) + q_offset)[None, :]          # (1, Tq)

    def body(carry, inp):
        m, l, acc = carry
        kblk, vblk, i = inp
        kpos = i * block + jnp.arange(block)[None, :]    # (1, block)
        s = jnp.einsum("btkgh,bskh->bkgts", qh, kblk,
                       preferred_element_type=jnp.float32)
        mask = jnp.ones((tq, block), bool)
        if causal:
            mask &= kpos <= qpos[0][:, None]
        if window > 0:
            mask &= (qpos[0][:, None] - kpos) < window
        mask &= kpos < (tk if kv_len is None else kv_len)
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        # guard fully-masked rows (m_new = -inf): exp(-inf - -inf) -> nan
        m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isinf(m_new)[..., None], 0.0, p)
        corr = jnp.exp(jnp.where(jnp.isinf(m), 0.0, m) - m_safe)
        corr = jnp.where(jnp.isinf(m), 0.0, corr)
        l_new = l * corr + p.sum(-1)
        pv = jnp.einsum("bkgts,bskh->btkgh", p.astype(vblk.dtype), vblk,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kv, g, tq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kv, g, tq), jnp.float32)
    a0 = jnp.zeros((b, tq, kv, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4),
         jnp.arange(nblk)))
    lt = l.transpose(0, 3, 1, 2)[..., None]
    out = acc / jnp.maximum(lt, 1e-20)
    return out.reshape(b, tq, h, hd).astype(q.dtype)


def windowed_attention(q, k, v, window: int, block: int = 512):
    """Local (sliding-window causal) attention computing only the blocks a
    query block can see — O(T × window) FLOPs instead of O(T²).

    Used for gemma3's 5-of-6 local layers (beyond-paper perf feature; the
    baseline path can also run these through `flash_attention` with a mask).
    """
    b, t, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    block = min(block, t)
    w_blocks = -(-window // block) + 1
    nblk = -(-t // block)
    padq = nblk * block - t
    if padq:
        q = jnp.pad(q, ((0, 0), (0, padq), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, padq), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, padq), (0, 0), (0, 0)))
    tp = nblk * block
    scale = 1.0 / np.sqrt(hd)
    qb = (q * scale).reshape(b, nblk, block, kvh, g, hd)
    # For query block i, gather key blocks [i-w_blocks+1 .. i]
    kpad = jnp.pad(k, ((0, 0), ((w_blocks - 1) * block, 0), (0, 0), (0, 0)))
    vpad = jnp.pad(v, ((0, 0), ((w_blocks - 1) * block, 0), (0, 0), (0, 0)))

    def per_block(qi, i):
        ks = jax.lax.dynamic_slice_in_dim(kpad, i * block, w_blocks * block, 1)
        vs = jax.lax.dynamic_slice_in_dim(vpad, i * block, w_blocks * block, 1)
        s = jnp.einsum("btkgh,bskh->bkgts", qi, ks,
                       preferred_element_type=jnp.float32)
        qpos = i * block + jnp.arange(block)
        kpos = (i - w_blocks + 1) * block + jnp.arange(w_blocks * block)
        mask = (kpos[None, :] <= qpos[:, None]) & \
               (qpos[:, None] - kpos[None, :] < window) & (kpos[None, :] >= 0)
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m = s.max(-1, keepdims=True)
        msafe = jnp.where(jnp.isinf(m), 0.0, m)
        p = jnp.exp(s - msafe)
        p = jnp.where(jnp.isinf(m), 0.0, p)
        o = jnp.einsum("bkgts,bskh->btkgh", p.astype(vs.dtype), vs,
                       preferred_element_type=jnp.float32)
        return o / jnp.maximum(p.sum(-1), 1e-20).transpose(
            0, 3, 1, 2)[..., None]

    out = jax.lax.map(lambda args: per_block(*args),
                      (qb.transpose(1, 0, 2, 3, 4, 5), jnp.arange(nblk)))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, tp, h, hd)
    return out[:, :t].astype(q.dtype)


def attention_block(params: dict, x: jax.Array, cfg: ArchConfig,
                    is_global: bool = True, positions=None,
                    cache: Optional[dict] = None, pos=None,
                    use_windowed_kernel: bool = False,
                    allow_pallas: bool = False):
    """Pre-norm attention. If `cache` is given, runs as one decode step
    (x: (B, 1, D)) reading/writing the cache at `pos`.  Returns (out, cache).
    """
    b, t, _ = x.shape
    hd = cfg.head_dim
    h = rmsnorm(x, params["norm"])
    q = (h @ params["wq"].astype(h.dtype)).reshape(b, t, cfg.n_heads, hd)
    k = (h @ params["wk"].astype(h.dtype)).reshape(b, t, cfg.n_kv, hd)
    v = (h @ params["wv"].astype(h.dtype)).reshape(b, t, cfg.n_kv, hd)
    q = shard_act(q, cfg, "heads")
    k = shard_act(k, cfg, "kv")
    v = shard_act(v, cfg, "kv")
    window = 0 if is_global else cfg.window
    if cache is None:
        if positions is None:
            positions = jnp.arange(t)[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        if cfg.pallas_flash and window == 0 and allow_pallas:
            from repro.kernels.ops import flash_attention_fused
            o = flash_attention_fused(q, k, v, causal=cfg.causal)
        elif not cfg.causal:
            o = flash_attention(q, k, v, causal=False, window=0)
        elif window and use_windowed_kernel:
            o = windowed_attention(q, k, v, window)
        else:
            o = flash_attention(q, k, v, causal=True, window=window)
        new_cache = None
    else:
        # single-token decode: append to cache, attend over it
        q = apply_rope(q, pos[None, None] if pos.ndim == 0 else pos,
                       cfg.rope_theta)
        k = apply_rope(k, pos[None, None] if pos.ndim == 0 else pos,
                       cfg.rope_theta)
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, pos, axis=1) \
            if cache["k"].shape[1] != 0 else cache["k"]
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, pos, axis=1) \
            if cache["v"].shape[1] != 0 else cache["v"]
        o = flash_attention(q, ck, cv, causal=False, kv_len=pos + 1,
                            block=2048)
        new_cache = {"k": ck, "v": cv}
    o = o.reshape(b, t, cfg.n_heads * hd)
    out = o @ params["wo"].astype(o.dtype)
    return shard_act(out, cfg, "resid"), new_cache


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ArchConfig, d_ff: Optional[int] = None) -> dict:
    ks = jax.random.split(key, 3)
    dff = d_ff or cfg.d_ff
    dt = pdtype(cfg)
    return {
        "wg": init_dense(ks[0], cfg.d_model, dff, dt),
        "wu": init_dense(ks[1], cfg.d_model, dff, dt),
        "wd": init_dense(ks[2], dff, cfg.d_model, dt),
        "norm": jnp.ones((cfg.d_model,), jnp.float32),
    }


def mlp_block(params: dict, x: jax.Array,
              cfg: ArchConfig | None = None) -> jax.Array:
    h = rmsnorm(x, params["norm"])
    g = jax.nn.silu(h @ params["wg"].astype(h.dtype))
    u = h @ params["wu"].astype(h.dtype)
    g = shard_act(g, cfg, "ffn") if cfg is not None else \
        shard(g, BATCH_AXES, None, "model")
    out = (g * u) @ params["wd"].astype(h.dtype)
    return shard_act(out, cfg, "resid") if cfg is not None else \
        shard(out, BATCH_AXES, None, None)


def init_moe(key, cfg: ArchConfig) -> dict:
    ks = jax.random.split(key, 4)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    dt = pdtype(cfg)
    std = 1.0 / np.sqrt(d)
    return {
        "router": init_dense(ks[0], d, e, jnp.float32),
        "wg": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * std
               ).astype(dt),
        "wu": (jax.random.normal(ks[2], (e, d, f), jnp.float32) * std
               ).astype(dt),
        "wd": (jax.random.normal(ks[3], (e, f, d), jnp.float32)
               / np.sqrt(f)).astype(dt),
        "norm": jnp.ones((d,), jnp.float32),
    }


def moe_block(params: dict, x: jax.Array, cfg: ArchConfig,
              groups: int = 32) -> jax.Array:
    """Token-choice top-k routing with grouped capacity-factor dispatch.

    Tokens are split into G groups along the batch dim (G shards over the
    data axes), and routing/rank assignment is computed PER GROUP — so the
    sort, capacity bookkeeping, and dispatch scatter are all local to a
    data shard, and the only cross-device traffic is the EP combine
    (gather from model-sharded expert buffers ≙ the all-to-all).  Expert
    compute is a batched (G, E, C_g, d) × (E, d, f) einsum with E sharded
    over `model` (EP) and G over the data axes.
    """
    b, t, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    g_ = np.gcd(b, groups)
    h = rmsnorm(x, params["norm"])
    xt = h.reshape(g_, (b // g_) * t, d)                    # (G, n_g, d)
    xt = shard(xt, BATCH_AXES, None, None)
    n_g = xt.shape[1]
    logits = jnp.einsum("gnd,de->gne", xt.astype(jnp.float32),
                        params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate, sel = jax.lax.top_k(probs, k)                     # (G, n_g, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    cap = int(np.ceil(n_g * k / e * cfg.capacity_factor))
    cap = max(8, -(-cap // 8) * 8)

    def route_one(sel_g, x_g):
        """Per-group local dispatch (vmapped over G)."""
        e_flat = sel_g.reshape(-1)                          # (n_g·k,)
        order = jnp.argsort(e_flat)
        sorted_e = e_flat[order]
        counts = jnp.bincount(e_flat, length=e)
        seg_start = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                     jnp.cumsum(counts)[:-1]])
        rank = jnp.arange(n_g * k) - seg_start[sorted_e]
        keep = rank < cap
        tok = order // k
        buf = jnp.zeros((e, cap, d), x_g.dtype)
        buf = buf.at[sorted_e, jnp.minimum(rank, cap - 1)].add(
            jnp.where(keep[:, None], x_g[tok], 0))
        return buf, (sorted_e, rank, keep, tok, order)

    buf, route = jax.vmap(route_one)(sel, xt)               # (G, E, C, d)
    buf = shard(buf, BATCH_AXES, "model", None, None)

    gg = jnp.einsum("gecd,edf->gecf", buf, params["wg"].astype(buf.dtype))
    uu = jnp.einsum("gecd,edf->gecf", buf, params["wu"].astype(buf.dtype))
    oo = jnp.einsum("gecf,efd->gecd", jax.nn.silu(gg) * uu,
                    params["wd"].astype(buf.dtype))
    oo = shard(oo, BATCH_AXES, "model", None, None)

    def combine_one(o_g, x_g, gate_g, r):
        sorted_e, rank, keep, tok, order = r
        vals = o_g[sorted_e, jnp.minimum(rank, cap - 1)]    # (n_g·k, d)
        w = gate_g.reshape(-1)[order]
        return jnp.zeros((n_g, d), x_g.dtype).at[tok].add(
            jnp.where(keep[:, None], vals * w[:, None].astype(vals.dtype),
                      0))

    if cfg.moe_local_combine and _model_axis_size() > 1:
        out = _ep_local_combine(oo, xt, gate, route, cap, n_g, d)
    else:
        out = jax.vmap(combine_one)(oo, xt, gate, route)
    out = out.reshape(b, t, d)
    return shard(out, BATCH_AXES, None, None)


def _model_axis_size() -> int:
    m = jax.sharding.get_abstract_mesh()
    if m is None or m.empty or "model" not in m.axis_names:
        return 1
    return dict(zip(m.axis_names, m.axis_sizes))["model"]


def _ep_local_combine(oo, xt, gate, route, cap: int, n_g: int, d: int):
    """EP combine with per-shard partial reduction (§Perf cell A it4).

    GSPMD's default plan all-reduces the per-(token, choice) expert outputs
    — (n_g·k, d) bytes.  Summing each shard's k-subset LOCALLY first and
    psumming the (n_g, d) partials moves k× fewer bytes across the `model`
    axis.  Implemented as a manual shard_map over `model` (data/pod stay
    auto-sharded).
    """
    mesh = jax.sharding.get_abstract_mesh()

    def local(oo_l, w_, se, rk, kp, tk):
        # oo_l: (G, E/shard, C, d) — this shard's experts only
        ax = jax.lax.axis_index("model")
        e_loc = oo_l.shape[1]
        in_shard = (se - ax * e_loc >= 0) & (se - ax * e_loc < e_loc) & kp

        def one(o_g, w_g, se_g, rk_g, ok_g, tk_g):
            vals = o_g[jnp.clip(se_g - ax * e_loc, 0, e_loc - 1),
                       jnp.minimum(rk_g, cap - 1)]           # (n_g·k, d)
            return jnp.zeros((n_g, d), vals.dtype).at[tk_g].add(
                jnp.where(ok_g[:, None],
                          vals * w_g[:, None].astype(vals.dtype), 0))

        out = jax.vmap(one)(oo_l, w_, se, rk, in_shard, tk)
        return jax.lax.psum(out, "model")

    sorted_e, rank, keep, tok, order = route
    # gate weight aligned with the sorted (token, choice) order
    w_sorted = jnp.take_along_axis(gate.reshape(gate.shape[0], -1), order,
                                   axis=1)
    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    g_spec = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(g_spec, "model", None, None), P(g_spec), P(g_spec),
                  P(g_spec), P(g_spec), P(g_spec)),
        out_specs=P(g_spec), check_vma=False)
    return fn(oo, w_sorted, sorted_e, rank, keep, tok)


# ---------------------------------------------------------------------------
# LM head / embeddings
# ---------------------------------------------------------------------------

def init_embeddings(key, cfg: ArchConfig) -> dict:
    k1, k2 = jax.random.split(key)
    dt = pdtype(cfg)
    p = {"tok": (jax.random.normal(k1, (cfg.vocab, cfg.d_model), jnp.float32)
                 * 0.02).astype(dt),
         "final_norm": jnp.ones((cfg.d_model,), jnp.float32)}
    if not cfg.tie_embeddings:
        p["unembed"] = init_dense(k2, cfg.d_model, cfg.vocab, dt, scale=0.5)
    return p


def embed_tokens(params: dict, tokens: jax.Array, cfg: ArchConfig):
    x = params["tok"].astype(cdtype(cfg))[tokens]
    return shard_act(x, cfg, "resid")


def lm_logits(params: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    h = rmsnorm(x, params["final_norm"])
    w = (params["tok"].T if cfg.tie_embeddings else params["unembed"])
    logits = h @ w.astype(h.dtype)
    return shard_act(logits, cfg, "logits")


def softmax_xent(logits: jax.Array, targets: jax.Array,
                 mask: jax.Array) -> jax.Array:
    """Cross-entropy that stays partitionable when the vocab dim is sharded:
    the label log-prob is an einsum against a (fused) one-hot instead of a
    take_along_axis gather — GSPMD turns the V-reduction into a local
    partial sum + psum rather than all-gathering the (B, T, V) logits
    (EXPERIMENTS.md §Perf iteration 1)."""
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=logits.dtype)
    ll = jnp.einsum("...v,...v->...", shifted, onehot) + m[..., 0]
    loss = (lse - ll) * mask
    return loss.sum() / jnp.maximum(mask.sum(), 1.0)
