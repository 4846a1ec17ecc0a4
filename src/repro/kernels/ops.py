"""jit'd wrappers + dispatch for the Pallas kernels.

On a TPU the kernels run compiled.  Anywhere else they can only run in
Pallas interpret mode (the kernel body executes in Python — exact
semantics, no Mosaic), and that is the caller's explicit choice:
``set_interpret(True)`` (the CPU test suite does this).  A kernel
dispatched off a TPU without that choice raises instead of silently
running the interpreter.  ``set_interpret(False)`` forces the Mosaic
lowering, which is how kernels are compiled for a described (not
attached) TPU.

Dispatch from models/nn.py:

  * training attention — ``use_flash()``: the flash kernel (forward and
    backward) wherever a kernel can run, that is on a TPU or in the
    caller's interpret mode; the jnp twin elsewhere.  Tile sizes follow
    the call's shape (``flash_attention.choose_blocks``).
  * WKV, mamba and paged decode — ``use_pallas()``: the XLA-lowerable jnp
    twins by default; REPRO_USE_PALLAS=1 routes them through the
    kernels.

``enable(on)`` overrides both: ``enable(False)`` forces every twin,
``enable(True)`` every kernel.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import choose_blocks, fit_blocks
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.mamba_scan import mamba_scan as _mamba
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.wkv6 import wkv6 as _wkv6

_FORCE: Optional[bool] = None


def enable(on: Optional[bool] = True):
    """Force every kernel on (True) or every twin (False); None restores
    the dispatch rules."""
    global _FORCE
    _FORCE = on


def use_pallas() -> bool:
    """WKV, mamba and paged decode take their kernels."""
    if _FORCE is not None:
        return _FORCE
    return os.environ.get("REPRO_USE_PALLAS", "0") == "1"


def use_flash() -> bool:
    """Training attention takes the flash kernel."""
    if _FORCE is not None:
        return _FORCE
    return bool(_INTERPRET) or jax.default_backend() == "tpu"


_INTERPRET: Optional[bool] = None


def set_interpret(on: Optional[bool]) -> Optional[bool]:
    """Choose Pallas interpret mode (None: unset).  Returns the old choice."""
    global _INTERPRET
    old, _INTERPRET = _INTERPRET, on
    return old


def interpret_mode() -> bool:
    """The caller's interpret choice; compiled on a TPU when unset."""
    if _INTERPRET is not None:
        return _INTERPRET
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"Pallas kernel dispatched on backend {backend!r}: the kernels "
            "compile only for a TPU.  Call repro.kernels.ops."
            "set_interpret(True) to run them in interpret mode.")
    return False


def flash_attention(q, k, v, *, causal: bool = True, window=-1,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None):
    """Shape-padding wrapper: pads Sq/Sk up to block multiples and crops.

    Tiles come from the shape (``choose_blocks``) unless ``block_q`` and
    ``block_k`` name them.  Padding keys sit *after* the real ones, so
    causal masking keeps them unattended for any real query; padding
    queries are cropped, so their gradient is 0.
    """
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    if block_q is None:
        block_q, block_k = choose_blocks(sq, sk)
    else:
        block_q, block_k = fit_blocks(block_q, block_k, sq, sk)
    pq = (-sq) % block_q
    pk = (-sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v
    out = _flash(qp, kp, vp, causal=causal, window=window, block_q=block_q,
                 block_k=block_k, interpret=interpret_mode())
    return out[:, :sq]


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    window=-1, k_scale=None, v_scale=None):
    """Decode (q (B, H, Dh)) or speculative verify (q (B, Q, H, Dh))
    attention over a paged KV pool (no padding needed: page and table
    extents are already block-exact by construction).  ``k_scale`` /
    ``v_scale`` (P, KV) activate the int8-pool dequantizing page walk."""
    return _paged(q, k_pages, v_pages, block_tables, lengths,
                  window=window, k_scale=k_scale, v_scale=v_scale,
                  interpret=interpret_mode())


def mamba_scan(u, dt, A, B, C, D, *, chunk: int = 128,
               ci_block: int = 512):
    """Pads S to the chunk multiple (dt=0 padding is state-neutral)."""
    b, s, ci = u.shape
    chunk = min(chunk, -(-s // 8) * 8)
    ci_block = min(ci_block, ci)
    while ci % ci_block:
        ci_block //= 2
    pad = (-s) % chunk
    if pad:
        zp = ((0, 0), (0, pad), (0, 0))
        u, dt, B, C = (jnp.pad(a, zp) for a in (u, dt, B, C))
    y, h_last = _mamba(u, dt, A, B, C, D, chunk=chunk, ci_block=ci_block,
                       interpret=interpret_mode())
    return y[:, :s], h_last


def wkv6(r, k, v, w, u, *, chunk: int = 128
         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pads S up to the chunk multiple (w=1 padding is decay-neutral)."""
    b, s, h, dh = r.shape
    chunk = min(chunk, max(s, 8))
    pad = (-s) % chunk
    if pad:
        zp = ((0, 0), (0, pad), (0, 0), (0, 0))
        r = jnp.pad(r, zp)
        k = jnp.pad(k, zp)
        v = jnp.pad(v, zp)
        w = jnp.pad(w, zp, constant_values=1.0)
    y, s_last = _wkv6(r, k, v, w, u, chunk=chunk,
                      interpret=interpret_mode())
    return y[:, :s], s_last
