"""RWKV6 (Finch) chunked WKV Pallas TPU kernel.

The WKV recurrence with data-dependent per-channel decay

    S_t = diag(w_t) S_{t-1} + k_t v_t^T
    y_t = r_t · (S_{t-1} + diag(u) k_t v_t^T)

is sequential in t, which wastes the MXU if evaluated stepwise.  TPU
adaptation (same insight as the CUDA chunked kernels, re-blocked for
VMEM/MXU): split the sequence into C-length chunks; inside a chunk the
contribution of earlier in-chunk tokens is an attention-like (C × C)
matmul with decay weights, and the carry-in state contributes through a
(C × Dh) @ (Dh × Dh) matmul — both MXU-shaped.  The (Dh × Dh) f32 state
lives, transposed, in VMEM scratch across the (sequential) chunk grid
axis; the in-chunk prefix sum of log-decays is a triangular matmul.

Grid: (B·H, S/C) — chunk axis innermost/sequential.
BlockSpecs: r/k/v/w tiles (1, C, Dh) in VMEM; y tile (1, C, Dh); the
final state (1, Dh, Dh) is written at the last chunk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.parallel.compat import tpu_compiler_params


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, sout_ref,
                 state_scr, *, chunk: int, nc: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[0].astype(jnp.float32)                 # (C, Dh)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)               # (1, Dh)

    logw = jnp.log(jnp.clip(w, 1e-8, 1.0))
    # inclusive prefix sum over the chunk as a lower-triangular matmul
    # (Mosaic has no cumsum); HIGHEST keeps the f32 log-decays exact
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    cum = jax.lax.dot((ti >= si).astype(jnp.float32), logw,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)   # (C, Dh)
    decay_to_t = jnp.exp(cum - logw)                 # prod over [0, t-1]

    # the carried state is kept transposed, S^T (Dh_v, Dh_k), so the
    # per-k-channel decay scales its lanes
    state_t = state_scr[...]
    # inter-chunk: y_t += (r_t ⊙ decay_to_t) @ S_in
    rd = r * decay_to_t
    y = jax.lax.dot_general(rd, state_t, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # intra-chunk: strictly-lower-triangular attention-like term
    att = jax.lax.dot_general(rd, k * jnp.exp(-cum),
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (C, C)
    att = jnp.where(ti > si, att, 0.0)
    y += jax.lax.dot(att, v, preferred_element_type=jnp.float32)
    # bonus diagonal term: y_t += (r_t · (u ⊙ k_t)) v_t
    y += jnp.sum(r * u * k, axis=-1, keepdims=True) * v
    y_ref[0] = y.astype(y_ref.dtype)

    # state update: S_out = diag(prod w) S_in + Σ_s (prod_{τ>s} w_τ ⊙ k_s) v_s^T
    last = cum[chunk - 1:chunk]                      # (1, Dh)
    kdec = k * jnp.exp(last - cum)                   # (C, Dh)
    state_scr[...] = jnp.exp(last) * state_t + jax.lax.dot_general(
        v, kdec, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ic == nc - 1)
    def _emit_state():
        sout_ref[0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, *, chunk: int = 128, interpret: bool = False):
    """r, k, v, w: (B, S, H, Dh); u: (H, Dh).  S % chunk == 0.

    Returns (y (B,S,H,Dh) in r.dtype, s_last (B,H,Dh,Dh) f32).
    """
    b, s, h, dh = r.shape
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    def flat(x):  # (B,S,H,Dh) -> (B*H, S, Dh)
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, dh)

    rf, kf, vf, wf = flat(r), flat(k), flat(v), flat(w)
    uf = jnp.broadcast_to(u[None], (b, h, dh)).reshape(b * h, 1, dh)

    kernel = functools.partial(_wkv6_kernel, chunk=chunk, nc=nc)
    y, s_last = pl.pallas_call(
        kernel,
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dh), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, dh), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, dh), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, dh), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((None, 1, dh), lambda bh, ic: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dh), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, dh, dh), lambda bh, ic: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, dh), r.dtype),
            jax.ShapeDtypeStruct((b * h, dh, dh), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rf, kf, vf, wf, uf)

    y = y.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
    s_last = s_last.reshape(b, h, dh, dh).transpose(0, 1, 3, 2)
    return y, s_last
