"""Llama-style decoder layers (h2o-danube3): pre-RMSNorm grouped-query
attention with RoPE and a causal window, then a SwiGLU MLP."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.flops import mean_context
from bench.reference import F32, rmsnorm

SCOPES = ("attention",)


def rope(x, theta):
    """Rotate-half rotary embedding of (S, heads, dh) at positions 0..S-1."""
    s, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(cfg, dot, p, x, layer):
    """Pre-norm GQA attention with RoPE and a causal window, then SwiGLU;
    no auxiliary loss."""
    eps = cfg["rms_norm_eps"]
    s, d = x.shape
    h = rmsnorm(x, p["norm1"]["scale"], eps)
    a = p["attn"]
    q = rope(dot("sd,dhk->shk", h, a["wq"]), cfg["rope_theta"])
    k = rope(dot("sd,dhk->shk", h, a["wk"]), cfg["rope_theta"])
    v = dot("sd,dhk->shk", h, a["wv"])
    kv, dh = k.shape[1], k.shape[2]
    g = q.shape[1] // kv
    i = jnp.arange(s)
    back = i[:, None] - i[None, :]
    window = cfg.get("sliding_window") or s
    mask = (back >= 0) & (back < window)

    def group(args):
        qq, kk, vv = args                       # (S, g, dh), (S, dh) x2
        sc = dot("sgd,td->gst", qq, kk) / math.sqrt(dh)
        pr = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return dot("gst,td->sgd", pr, vv)

    o = jax.lax.map(jax.checkpoint(group),
                    (q.reshape(s, kv, g, dh).transpose(1, 0, 2, 3),
                     k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2, 3).reshape(s, kv * g * dh)
    x = x + dot("sk,kd->sd", o, a["wo"])
    h = rmsnorm(x, p["norm2"]["scale"], eps)
    m = p["mlp"]
    up = jax.nn.silu(dot("sd,df->sf", h, m["w1"])) * dot("sd,df->sf", h,
                                                         m["w3"])
    return x + dot("sf,fd->sd", up, m["w2"]), None


def layer_kind(cfg, layer):
    return "attn"


def final_norm(cfg, p, x):
    return rmsnorm(x, p["scale"], cfg["rms_norm_eps"])


def matmul_params(cfg, layer):
    d = cfg["hidden_size"]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    ff = cfg["intermediate_size"]
    gated = 3 if cfg.get("hidden_act") == "silu" else 2
    return d * h * dh * 2 + d * kv * dh * 2 + gated * d * ff


def mixer_flops(cfg, seq_len, layer):
    """q.k and p.v over the context that binds (the window, where it is
    shorter than the position)."""
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    return 2 * 2 * h * dh * mean_context(seq_len, cfg.get("sliding_window"))


def spec_check(cfg, spec):
    return ({"n_heads": cfg["num_attention_heads"],
             "n_kv": cfg["num_key_value_heads"], "d_head": cfg["head_dim"]},
            {"n_heads": spec.n_heads, "n_kv": spec.n_kv,
             "d_head": spec.d_head})
