"""RWKV-6 Finch layers: time-mix with data-dependent token shift and
decay over a matrix-valued WKV state per head, then channel-mix with
squared ReLU, each pre-LayerNorm."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import F32, HIGHEST, layernorm

SCOPES = ("wkv",)
WKV_CHUNK = 64


def wkv(r, k, v, logw, u):
    """RWKV-6 WKV, exact, over (S, H, dh) inputs with log-decays logw.

      S_t = diag(w_t) S_{t-1} + k_t v_t^T
      y_t = r_t . (S_{t-1} + diag(u) k_t v_t^T)

    Chunks of up to WKV_CHUNK positions; every decay factor is exp of a sum of
    log-decays that is <= 0, so nothing overflows.
    """
    s, h, dh = r.shape
    c = math.gcd(WKV_CHUNK, s)
    rs = lambda a: a.reshape(s // c, c, h, dh)
    cum = jnp.cumsum(rs(logw), axis=1)              # sum over tau <= t
    excl = cum - rs(logw)                           # sum over tau < t
    t = jnp.arange(c)
    before = (t[:, None] > t[None, :])[:, :, None, None]
    ein = lambda eq, *a: jnp.einsum(eq, *a, precision=HIGHEST)

    def chunk(state, inp):
        rb, kb, vb, cb, eb = inp                    # (C, H, dh)
        y = ein("thi,hij->thj", rb * jnp.exp(eb), state)
        dec = jnp.exp(jnp.where(before, eb[:, None] - cb[None, :], -jnp.inf))
        att = ein("thi,shi,tshi->hts", rb, kb, dec)
        y = y + ein("hts,shj->thj", att, vb)
        y = y + ein("thi,thi->th", rb, u * kb)[..., None] * vb
        last = cb[-1]
        state = jnp.exp(last)[..., None] * state + ein(
            "shi,shj->hij", kb * jnp.exp(last[None] - cb), vb)
        return state, y

    _, ys = jax.lax.scan(jax.checkpoint(chunk), jnp.zeros((h, dh, dh), F32),
                         (rs(r), rs(k), rs(v), cum, excl))
    return ys.reshape(s, h, dh)


def block(cfg, dot, p, x, layer):
    """RWKV-6 time-mix then channel-mix, each pre-LayerNorm; no auxiliary
    loss."""
    eps, gn_eps = cfg["layer_norm_epsilon"], cfg["group_norm_epsilon"]
    s, d = x.shape
    dh = cfg["head_size"]
    nh = d // dh
    shift = lambda a: jnp.concatenate([jnp.zeros((1, d), F32), a[:-1]])
    t = p["tmix"]
    h = layernorm(x, p["norm1"]["scale"], p["norm1"]["bias"], eps)
    dx = shift(h) - h
    low = jnp.tanh(dot("sd,dr->sr", h + dx * t["maa_x"], t["tmix_w1"]))
    mids = dot("sfr,frd->sfd", low.reshape(s, 5, -1), t["tmix_w2"])
    xw, xk, xv, xr, xg = (h + dx * (t[f"maa_{n}"] + mids[:, i])
                          for i, n in enumerate("wkvrg"))
    r = dot("sd,de->se", xr, t["wr"]).reshape(s, nh, dh)
    k = dot("sd,de->se", xk, t["wk"]).reshape(s, nh, dh)
    v = dot("sd,de->se", xv, t["wv"]).reshape(s, nh, dh)
    g = jax.nn.silu(dot("sd,de->se", xg, t["wg"]))
    dec = t["w0"] + dot("sr,rd->sd", jnp.tanh(dot("sd,dr->sr", xw,
                                                  t["decay_w1"])),
                        t["decay_w2"])
    y = wkv(r, k, v, -jnp.exp(dec).reshape(s, nh, dh), t["u"].reshape(nh, dh))
    mu = jnp.mean(y, -1, keepdims=True)
    var = jnp.mean((y - mu) ** 2, -1, keepdims=True)
    y = ((y - mu) * jax.lax.rsqrt(var + gn_eps)).reshape(s, d)
    y = y * t["gn_scale"] + t["gn_bias"]
    x = x + dot("sd,de->se", y * g, t["wo"])
    c = p["cmix"]
    h = layernorm(x, p["norm2"]["scale"], p["norm2"]["bias"], eps)
    dx = shift(h) - h
    kk = jnp.square(jax.nn.relu(dot("sd,df->sf", h + dx * c["maa_k"],
                                    c["wk"])))
    gate = jax.nn.sigmoid(dot("sd,de->se", h + dx * c["maa_r"],
                              c["wr_gate"]))
    return x + gate * dot("sf,fd->sd", kk, c["wv"]), None


def layer_kind(cfg, layer):
    return "rwkv"


def final_norm(cfg, p, x):
    return layernorm(x, p["scale"], p["bias"], cfg["layer_norm_epsilon"])


def matmul_params(cfg, layer):
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    lora_mix, lora_decay = (cfg["time_mix_extra_dim"],
                            cfg["time_decay_extra_dim"])
    time_mix = 5 * d * d + d * 5 * lora_mix + 5 * lora_mix * d \
        + 2 * d * lora_decay
    channel_mix = 2 * d * ff + d * d
    return time_mix + channel_mix


def mixer_flops(cfg, seq_len, layer):
    """The WKV state's update and readout."""
    dh = cfg["head_size"]
    return 4 * (cfg["hidden_size"] // dh) * dh * dh


def spec_check(cfg, spec):
    return {"d_head": cfg["head_size"]}, {"d_head": spec.rwkv.head_dim}
