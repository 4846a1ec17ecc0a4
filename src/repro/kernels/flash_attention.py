"""Flash attention Pallas TPU kernel (causal + sliding-window + GQA).

TPU adaptation of the memory hierarchy insight: stream KV through VMEM in
``block_k`` tiles while the (block_q, d_head) query tile and the running
(m, l, acc) softmax state stay resident in VMEM; the (block_q, block_k)
score tile hits the MXU as one matmul.  Block defaults are 128-aligned to
the MXU systolic array; the k-block grid axis is the innermost (sequential
on TPU) so VMEM scratch carries the running state across k steps.

Heads move ahead of the sequence axis outside the kernel, so every tile
is a (rows, d_head) slab whose last two dims meet the TPU's (8, 128)
tiling rule (d_head is the array's full last dim, so d_head = 120 works).

Grid: (batch, q_heads, Sq/block_q, Sk/block_k).
BlockSpecs (VMEM tiles) on the (B, H, S, Dh) layout, batch and head dims
squeezed (None):
  q   (block_q, d_head)   index (b, h, iq)    — reused across all ik
  k,v (block_k, d_head)   index (b, h // group_q, ik)   — GQA: query
                          heads map onto their shared KV head
  out (block_q, d_head)   written once at ik == nk-1

Scratch: m, l (block_q, 1) f32; acc (block_q, d_head) f32.

Fully-masked (q, k) block pairs are skipped with pl.when — on hardware
this prunes ~half the causal grid and all-but-window/block_k of the SWA
grid (the compute-roofline win the paper's profile-then-partition flow
would observe as a shorter stage time).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.parallel.compat import tpu_compiler_params

NEG_INF = -1e30


def _flash_kernel(w_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, scale: float, causal: bool,
                  block_q: int, block_k: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    window = w_ref[0]            # SMEM scalar; <= 0 means global

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q0 = iq * block_q
    k0 = ik * block_k
    # Block-level visibility: skip fully-masked tiles.
    visible = jnp.bool_(True)
    if causal:
        visible &= k0 <= q0 + block_q - 1           # below-diagonal overlap
    visible &= (window <= 0) | (k0 + block_k - 1 > q0 - window)

    @pl.when(visible)
    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (bq, bk)
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones(s.shape, jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        mask &= (window <= 0) | ((qpos - kpos) < jnp.maximum(window, 1))
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window=-1,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, Sq, H, Dh); k, v: (B, Sk, KV, Dh), H % KV == 0.

    ``window`` may be a Python int or a traced scalar (<= 0 means global)
    — it rides in SMEM, matching the stage design where per-layer window
    size is data, not program structure.  Returns (B, Sq, H, Dh) in
    q.dtype.  Sq % block_q == Sk % block_k == 0 (pad outside if needed);
    softmax statistics in f32.
    """
    b, sq, h, dh = q.shape
    sk, kv = k.shape[1], k.shape[2]
    assert h % kv == 0 and sq % block_q == 0 and sk % block_k == 0, (
        q.shape, k.shape, block_q, block_k)
    group = h // kv
    nq, nk = sq // block_q, sk // block_k
    scale = 1.0 / np.sqrt(dh)
    warr = jnp.asarray(window, jnp.int32).reshape(1)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, nk=nk)

    # heads ahead of the sequence axis: (B, S, H, Dh) -> (B, H, S, Dh)
    qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    q_spec = pl.BlockSpec((None, None, block_q, dh),
                          lambda b_, h_, iq, ik: (b_, h_, iq, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, dh),
                           lambda b_, h_, iq, ik: (b_, h_ // group, ik, 0))
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(warr, qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
