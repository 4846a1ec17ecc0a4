"""Pallas TPU kernels for the compute hot-spots.

  flash_attention.py   causal/sliding-window/GQA flash attention, with
                       its own backward (training attention on TPU)
  wkv6.py              RWKV6 chunked WKV scan (matrix-valued state)
  ops.py               jit'd wrappers + use_flash()/use_pallas() dispatch
  ref.py               naive pure-jnp oracles (tests assert against these)
"""
from repro.kernels import ops, ref  # noqa: F401
