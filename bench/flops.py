"""Model FLOPs of one trained token, from a configuration file's widths.

Forward and backward of every matrix product the model needs (3 x the
forward's 2 x multiply-adds), over the weights a token actually
multiplies by in each layer, and the head's; plus each layer's sequence
mixer beyond its weights (causal attention over the context that binds,
the WKV recurrence's state arithmetic).  The per-layer terms come from
the configuration's architecture module (bench/archs).  Recomputation is
not counted, nor are the embedding lookup, norms, activations and the
optimizer.
"""
from __future__ import annotations

from bench import archs


def mean_context(seq_len: int, window: int | None) -> float:
    """Mean number of keys a query attends to under a causal mask."""
    w = window if window and window > 0 else seq_len
    # positions t = 0..S-1 see min(t + 1, w) keys
    full = min(w, seq_len)
    total = full * (full + 1) / 2 + (seq_len - full) * w
    return total / seq_len


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    arch = archs.load(cfg)
    total = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    for layer in range(cfg["num_hidden_layers"]):
        total += 2.0 * arch.matmul_params(cfg, layer) \
            + arch.mixer_flops(cfg, seq_len, layer)
    return total


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 3.0 * forward_flops_per_token(cfg, seq_len)
