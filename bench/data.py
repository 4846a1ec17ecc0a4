"""The benchmark's token stream, drawn from ``--seed`` and the step index.

The arithmetic is that of the program's synthetic stream: a zipf-like
marginal (a uniform draw raised to a power, scaled to the vocabulary) with
end-of-document tokens sprinkled at a rate of one per ``mean_doc_len``.
Every (seed, step) pair gives its own rows, so no two steps of a run, and
no two rows of a step, repeat.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

U64 = (1 << 64) - 1


def round_batch(seed: int, step: int, *, microbatches: int, rows: int,
                seq_len: int, vocab: int, marginal_power: float,
                mean_doc_len: int, eos_id: int) -> Dict[str, np.ndarray]:
    """(R, rows, S) int32 tokens and their next-token labels."""
    rng = np.random.default_rng((seed & U64, step))
    shape = (microbatches, rows, seq_len + 1)
    u = rng.random(shape)
    toks = np.minimum((u ** marginal_power * vocab).astype(np.int64),
                      vocab - 1)
    doc = rng.random(shape) < (1.0 / mean_doc_len)
    toks = np.where(doc, eos_id, toks).astype(np.int32)
    return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}


class TrafficSource:
    """``round_batch(step, R, rows)`` for the program's ShardedLoader."""

    def __init__(self, seed: int, traffic: dict, vocab: int):
        self.seed = seed
        self.seq_len = traffic["seq_len"]
        self.vocab = vocab
        self.tokens = traffic["tokens"]

    def round_batch(self, step: int, r_microbatches: int, bmb: int):
        return round_batch(self.seed, step, microbatches=r_microbatches,
                           rows=bmb, seq_len=self.seq_len, vocab=self.vocab,
                           **self.tokens)
