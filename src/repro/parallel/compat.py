"""Thin wrappers over the installed jax (0.9) APIs the codebase shares.

  shard_map            ``jax.shard_map`` (callers pass ``check_vma``).
  cost_analysis        ``compiled.cost_analysis()`` as a plain dict.
  tpu_compiler_params  ``pltpu.CompilerParams`` for the Pallas kernels.
"""
from __future__ import annotations

from jax import shard_map


def cost_analysis(compiled) -> dict:
    """compiled.cost_analysis() as a dict (empty when unavailable)."""
    return dict(compiled.cost_analysis() or {})


def tpu_compiler_params(**kw):
    """pltpu.CompilerParams for a pallas_call."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kw)


__all__ = ["shard_map", "cost_analysis", "tpu_compiler_params"]
