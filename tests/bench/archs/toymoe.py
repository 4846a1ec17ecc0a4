"""A toy architecture for the benchmark's tests: dense layers first
(``first_k_dense_replace``), then layers whose MLP output is scaled by a
softmax router's mix of per-expert gains, each returning the router's
balance loss as its auxiliary loss."""
import jax
import jax.numpy as jnp

from bench.reference import rmsnorm

SCOPES = ()


def layer_kind(cfg, layer):
    return "dense" if layer < cfg["first_k_dense_replace"] else "routed"


def block(cfg, dot, p, x, layer):
    h = rmsnorm(x, p["norm1"]["scale"], cfg["rms_norm_eps"])
    y = dot("sf,fd->sd", jax.nn.silu(dot("sd,df->sf", h, p["mlp"]["w1"])),
            p["mlp"]["w2"])
    if layer_kind(cfg, layer) == "dense":
        return x + y, None
    probs = jax.nn.softmax(dot("sd,de->se", h, p["router"]), axis=-1)
    load = jnp.mean(probs, axis=0)
    aux = probs.shape[-1] * jnp.sum(load * load)
    return x + y * (probs @ p["gain"])[:, None], aux


def final_norm(cfg, p, x):
    return rmsnorm(x, p["scale"], cfg["rms_norm_eps"])


def matmul_params(cfg, layer):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    routed = layer_kind(cfg, layer) == "routed"
    return 2 * d * f + (d * cfg["n_routed_experts"] if routed else 0)


def mixer_flops(cfg, seq_len, layer):
    return 0


def spec_check(cfg, spec):
    return {}, {}
