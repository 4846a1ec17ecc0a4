"""Model FLOPs of one trained token, from a configuration file's widths.

Forward and backward of every matrix product the model needs (3 x the
forward's 2 x multiply-adds), causal attention over the context that binds
(the window, where it is shorter than the position), and the WKV
recurrence's state arithmetic.  Recomputation is not counted, nor are the
embedding lookup, norms, activations and the optimizer.
"""
from __future__ import annotations


def mean_context(seq_len: int, window: int | None) -> float:
    """Mean number of keys a query attends to under a causal mask."""
    w = window if window and window > 0 else seq_len
    # positions t = 0..S-1 see min(t + 1, w) keys
    full = min(w, seq_len)
    total = full * (full + 1) / 2 + (seq_len - full) * w
    return total / seq_len


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer that take part in a matrix product."""
    d = cfg["hidden_size"]
    if cfg["mixer"] == "attn":
        h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        ff = cfg["intermediate_size"]
        gated = 3 if cfg.get("hidden_act") == "silu" else 2
        return d * h * dh * 2 + d * kv * dh * 2 + gated * d * ff
    if cfg["mixer"] == "rwkv":
        ff = cfg["intermediate_size"]
        lora_mix, lora_decay = cfg["time_mix_extra_dim"], cfg["time_decay_extra_dim"]
        time_mix = 5 * d * d + d * 5 * lora_mix + 5 * lora_mix * d \
            + 2 * d * lora_decay
        channel_mix = 2 * d * ff + d * d
        return time_mix + channel_mix
    raise ValueError(f"unknown mixer {cfg['mixer']!r}")


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    total = 2.0 * (layers * layer_matmul_params(cfg) + d * cfg["vocab_size"])
    if cfg["mixer"] == "attn":
        h, dh = cfg["num_attention_heads"], cfg["head_dim"]
        ctx = mean_context(seq_len, cfg.get("sliding_window"))
        total += layers * 2 * 2 * h * dh * ctx       # q.k and p.v
    elif cfg["mixer"] == "rwkv":
        dh = cfg["head_size"]
        heads = d // dh
        total += layers * 4 * heads * dh * dh        # state update, readout
    return total


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 3.0 * forward_flops_per_token(cfg, seq_len)
