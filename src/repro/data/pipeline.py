"""Deterministic synthetic LM data pipeline.

Produces microbatched rounds shaped for the pipeline train step:
tokens/labels (R, Bmb, S).  Fully deterministic in (seed, step) so a
restarted run consumes identical data — required for checkpoint/restart
tests and for PipeDream's deterministic round-robin replica routing.

On a real multi-host pod each host materializes only its shard via
``jax.make_array_from_callback``; on the single-process CPU host the same
code path produces the global array.  A background prefetch thread keeps
``prefetch`` rounds in flight (the input stage's "reads from disk" in
paper Figure 9).
"""
from __future__ import annotations

import queue
import threading
from typing import Dict, Iterator, Optional

import jax
import numpy as np

from repro import obs


class SyntheticLM:
    """Zipf-ish token stream with EOS-delimited documents."""

    def __init__(self, vocab: int, seq_len: int, *, seed: int = 0,
                 eos_id: int = 0, mean_doc_len: int = 512):
        self.vocab = vocab
        self.seq_len = seq_len
        self.seed = seed
        self.eos_id = eos_id
        self.mean_doc_len = mean_doc_len

    def round_batch(self, step: int, r_microbatches: int, bmb: int
                    ) -> Dict[str, np.ndarray]:
        """(R, Bmb, S) tokens + next-token labels for one round."""
        rng = np.random.default_rng((self.seed, step))
        shape = (r_microbatches, bmb, self.seq_len + 1)
        # zipf-like marginal over the vocab, cheap to sample
        u = rng.random(shape)
        toks = np.minimum((u ** 2.5 * self.vocab).astype(np.int64),
                          self.vocab - 1)
        # sprinkle document boundaries
        doc = rng.random(shape) < (1.0 / self.mean_doc_len)
        toks = np.where(doc, self.eos_id, toks).astype(np.int32)
        return {"tokens": toks[..., :-1],
                "labels": toks[..., 1:].astype(np.int32)}


class ShardedLoader:
    """Places per-round host arrays onto the mesh with the bundle's specs."""

    def __init__(self, source: SyntheticLM, batch_specs: Dict,
                 *, extra_fn=None):
        self.source = source
        self.batch_specs = batch_specs
        self.extra_fn = extra_fn or (lambda step, shapes: {})

    def get(self, step: int):
        t = self.batch_specs["tokens"]
        r, bmb, s = t.shape
        with obs.span(obs.LOADER_DRAW):
            host = dict(self.source.round_batch(step, r, bmb))
            for k, spec in self.batch_specs.items():
                if k not in host:
                    host[k] = self.extra_fn(step, {k: spec})[k]
        out = {}
        with obs.span(obs.LOADER_PLACE):
            for k, spec in self.batch_specs.items():
                def cb(index, _data=host[k]):
                    return _data[index]

                out[k] = jax.make_array_from_callback(spec.shape,
                                                      spec.sharding, cb)
        return out


class Prefetcher:
    """Background-thread prefetch of upcoming rounds."""

    def __init__(self, loader: ShardedLoader, start_step: int = 0,
                 prefetch: int = 2):
        self.loader = loader
        self.q: "queue.Queue" = queue.Queue(maxsize=prefetch)
        self._step = start_step
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        step = self._step
        while not self._stop.is_set():
            try:
                self.q.put(self.loader.get(step), timeout=0.5)
                step += 1
            except queue.Full:
                continue

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        return self.q.get()

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)


def vlm_patch_stub(d_model: int, seed: int = 0):
    """Frontend stub: deterministic fake patch embeddings for VLM configs."""

    def fn(step: int, shapes: Dict):
        out = {}
        for k, spec in shapes.items():
            rng = np.random.default_rng((seed, step, hash(k) % (2 ** 31)))
            out[k] = rng.standard_normal(spec.shape).astype(np.float32) * 0.02
        return out

    return fn
