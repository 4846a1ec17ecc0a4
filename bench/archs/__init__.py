"""The benchmark's architecture-specific code, one module per sequence
mixer: ``bench/archs/<mixer>.py``, found by a configuration's "mixer".

A module exports

* ``block(cfg, dot, p, x, layer) -> (x, aux)``: one layer of the plain
  reference on one row, x (S, d) float32, ``dot(eq, a, b)`` its matrix
  product, ``layer`` the layer's index; ``aux`` is a float32 scalar (an
  auxiliary loss, such as a router's balance loss) or None;
* ``layer_kind(cfg, layer) -> str``: layers of one kind compute alike,
  so the reference compiles one program per kind;
* ``final_norm(cfg, p, x)``: the norm before the head;
* ``matmul_params(cfg, layer)``: the weights a token multiplies by in
  that layer (for experts, the routed and shared ones a token uses);
* ``mixer_flops(cfg, seq_len, layer)``: forward FLOPs a token of the
  sequence mixer beyond its weights' (attention's scores, a scan's state);
* ``spec_check(cfg, spec) -> (want, got)``: the mixer's widths as the
  configuration states them and as the program built them;
* ``SCOPES``: the program's device scopes around the mixer's core.
"""
from __future__ import annotations

import importlib.util
import os

from bench import CellFailed

DIR = os.path.dirname(os.path.abspath(__file__))


def load(cfg: dict):
    """The module of the configuration's mixer."""
    path = os.path.join(DIR, cfg["mixer"] + ".py")
    if not os.path.isfile(path):
        raise CellFailed(f"no architecture module {path} for mixer "
                         f"{cfg['mixer']!r}")
    spec = importlib.util.spec_from_file_location(
        f"bench_arch_{cfg['mixer']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
