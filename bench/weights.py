"""Seeded random weights, made on the device by the benchmark itself.

Every floating leaf of the parameter tree is drawn from its own key,
``fold_in(key, crc32(path))``, as N(mean, std) by the first rule of the
configuration's ``init`` list whose pattern matches the leaf's path
("stages/layer_0/attn/wq"), and cast to the leaf's dtype.  Leaves that
``keep`` names are structure (windows, rope thetas) and stay as the
caller made them.  The same function, given the same key and shapes,
gives the reference the program's initial weights bit for bit without
taking them from the program.
"""
from __future__ import annotations

import re
import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any whole seed, 64 bits of it."""
    seed &= (1 << 64) - 1
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _rule(cfg: dict, name: str):
    for r in cfg["init"]:
        if re.search(r["match"], name):
            return r
    return None


def generate(cfg: dict, key, like):
    """A tree shaped like ``like`` (arrays or ShapeDtypeStructs).

    Raises when a floating leaf has no rule and is not kept, so a new
    parameter of the program is never left at the program's own values
    unnoticed."""
    keep = [re.compile(p) for p in cfg.get("keep", ())]

    def make(path, leaf):
        name = leaf_name(path)
        if any(p.search(name) for p in keep):
            return leaf
        rule = _rule(cfg, name)
        if rule is None:
            raise KeyError(f"no init rule for parameter {name} "
                           f"{tuple(leaf.shape)}")
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        x = rule["mean"] + rule["std"] * jax.random.normal(
            k, leaf.shape, jnp.float32) if rule["std"] else \
            jnp.full(leaf.shape, rule["mean"], jnp.float32)
        return x.astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(make, like)
