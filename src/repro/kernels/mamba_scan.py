"""Mamba selective-scan Pallas TPU kernel.

The diagonal SSM recurrence

    h_t = exp(dt_t ⊙ A) ⊙ h_{t-1} + (dt_t u_t) ⊗ B_t
    y_t = h_t · C_t + D ⊙ u_t

expands to a (Ci × N) state per token; the XLA twin (models/nn.py::
selective_scan) must materialize (chunk, Ci, N) decay tensors at fusion
boundaries — the dominant HBM-byte signature of the jamba dry-run.  The
kernel keeps the (ci_block × N) state AND the expansion in VMEM: HBM
traffic collapses to streaming u/dt (Ci-major) and B/C (N-major) in, y
out — the roofline-ideal O(S·Ci) bytes.

Grid: (B, Ci/ci_block, S/chunk) — chunk axis innermost/sequential.  The
state is kept transposed, h^T (N, ci_block) f32, so channels fill the
lanes and a token's dt/u rows broadcast over the N sublanes; B and C
arrive transposed, (N, chunk), and a token's column is picked by a
masked lane sum (Mosaic has no dynamic lane slice).  Within a chunk a
fori_loop steps eight tokens at a time entirely in VMEM/VREGs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.parallel.compat import tpu_compiler_params


def _mamba_kernel(u_ref, dt_ref, bt_ref, ct_ref, at_ref, d_ref, y_ref,
                  hout_ref, h_scr, u_scr, dt_scr, y_scr, *, chunk: int,
                  nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    at = at_ref[...].astype(jnp.float32)              # (N, ci_b)
    dvec = d_ref[...].astype(jnp.float32)             # (1, ci_b)
    u_scr[...] = u_ref[...].astype(jnp.float32)       # (chunk, ci_b)
    dt_scr[...] = dt_ref[...].astype(jnp.float32)
    bt = bt_ref[...].astype(jnp.float32)              # (N, chunk)
    ct = ct_ref[...].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (8, at.shape[1]), 0)

    def column(mat, t):
        """Column t of an (N, chunk) tile as (N, 1), by a masked lane sum."""
        return jnp.sum(jnp.where(lane == t, mat, 0.0), axis=1, keepdims=True)

    def group(g, h):
        # eight tokens per step: aligned sublane loads/stores, the rows
        # inside the group taken at static offsets
        r0 = pl.multiple_of(g * 8, 8)
        u8 = u_scr[pl.ds(r0, 8), :]
        dt8 = dt_scr[pl.ds(r0, 8), :]
        y8 = jnp.zeros(u8.shape, jnp.float32)
        for i in range(8):
            dt_t, u_t = dt8[i:i + 1], u8[i:i + 1]                # (1, ci_b)
            h = (jnp.exp(dt_t * at) * h
                 + (dt_t * u_t) * column(bt, r0 + i))            # (N, ci_b)
            y_t = (jnp.sum(h * column(ct, r0 + i), axis=0, keepdims=True)
                   + dvec * u_t)
            y8 = jnp.where(rows == i, y_t, y8)
        y_scr[pl.ds(r0, 8), :] = y8
        return h

    h_last = jax.lax.fori_loop(0, chunk // 8, group, h_scr[...])
    h_scr[...] = h_last
    y_ref[...] = y_scr[...].astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _emit():
        hout_ref[...] = h_last


@functools.partial(jax.jit, static_argnames=("chunk", "ci_block",
                                             "interpret"))
def mamba_scan(u, dt, A, B, C, D, *, chunk: int = 128,
               ci_block: int = 512, interpret: bool = False):
    """u, dt: (B, S, Ci); A: (Ci, N); B, C: (B, S, N); D: (Ci,).

    Returns (y (B,S,Ci) in u.dtype — D⊙u included, h_last (B,Ci,N) f32).
    S % chunk == 0, chunk % 8 == 0 and Ci % ci_block == 0 (pad outside).
    """
    b, s, ci = u.shape
    n = A.shape[-1]
    ci_block = min(ci_block, ci)
    assert s % chunk == 0 and chunk % 8 == 0 and ci % ci_block == 0, (
        s, chunk, ci, ci_block)
    nc = s // chunk
    nci = ci // ci_block

    kernel = functools.partial(_mamba_kernel, chunk=chunk, nc=nc)
    seq_tile = pl.BlockSpec((None, chunk, ci_block),
                            lambda b_, ici, ic: (b_, ic, ici))
    state_tile = pl.BlockSpec((None, n, chunk),
                              lambda b_, ici, ic: (b_, 0, ic))
    y, h_last = pl.pallas_call(
        kernel,
        grid=(b, nci, nc),
        in_specs=[
            seq_tile,                                          # u
            seq_tile,                                          # dt
            state_tile,                                        # B^T
            state_tile,                                        # C^T
            pl.BlockSpec((n, ci_block),
                         lambda b_, ici, ic: (0, ici)),        # A^T
            pl.BlockSpec((1, ci_block),
                         lambda b_, ici, ic: (0, ici)),        # D
        ],
        out_specs=[
            seq_tile,                                          # y
            pl.BlockSpec((None, n, ci_block),
                         lambda b_, ici, ic: (b_, 0, ici)),    # h^T
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, ci), u.dtype),
            jax.ShapeDtypeStruct((b, n, ci), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, ci_block), jnp.float32),
                        pltpu.VMEM((chunk, ci_block), jnp.float32),
                        pltpu.VMEM((chunk, ci_block), jnp.float32),
                        pltpu.VMEM((chunk, ci_block), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(u, dt, B.transpose(0, 2, 1), C.transpose(0, 2, 1), A.T,
      D.reshape(1, ci))
    return y, h_last.transpose(0, 2, 1)
