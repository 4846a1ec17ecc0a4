"""Flash attention Pallas TPU kernels (causal + sliding-window + GQA),
forward and backward.

TPU adaptation of the memory hierarchy insight: stream KV through VMEM in
``block_k`` tiles while the (block_q, d_head) query tile and the running
(m, l, acc) softmax state stay resident in VMEM; the (block_q, block_k)
score tile hits the MXU as one matmul and never reaches HBM.

Heads move ahead of the sequence axis outside the kernels, so every tile
is a (rows, d_head) slab whose last two dims meet the TPU's (8, 128)
tiling rule (d_head is the array's full last dim, so d_head = 120 works).
Operands enter the MXU in their own dtype (bf16 in training) with f32
accumulation; softmax statistics, dK/dV/dQ accumulators and
D = rowsum(dO * O) are f32, and P and dS are cast to the input dtype only
as matmul operands.

Three kernels, each named in the compiled program and the profile:

  flash_fwd      grid (batch, q_head, q_block, k_block); the k blocks are
                 the sequential (``arbitrary``) axis.  Under
                 differentiation it also writes the row log-sum-exp,
                 lane-broadcast to (S, 128) f32, the backward's residual.
  flash_bwd_dkv  grid (batch, kv_head, k_block, group * q_block): the
                 group's query heads and their q blocks are the sequential
                 axis, so GQA sums dK and dV in f32 VMEM scratch and no
                 per-query-head dK/dV ever exists.
  flash_bwd_dq   grid (batch, q_head, q_block, k_block), as the forward.

Both backward kernels recompute the score tile in VMEM from the saved
log-sum-exp (FlashAttention-2's backward).  ``flash_attention`` is a
``jax.custom_vjp`` over the three.

The window (<= 0: global) is data, not program structure: it is
scalar-prefetched into SMEM, where both the in-kernel visibility test and
the BlockSpec index maps read it.  A tile no query of which may see any
of its keys is skipped with pl.when, and its index map repeats the
previous visible block, so Pallas fetches nothing for it either: this
prunes about half the causal grid and all but window/block of the
sliding-window grid.

The kernels assume that query row i sits at position i and key row j at
position j (self-attention over a whole sequence, as in training).
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
LANES = 128        # per-row statistics are stored lane-broadcast


def choose_blocks(sq: int, sk: int):
    """(block_q, block_k) of all three kernels for a call's shape, from a
    sweep of each kernel on a TPU v5e at danube3-4b's shape (S 4096, Dh
    120; PERF.md §6): 1024-row tiles of queries and keys were the
    fastest forward, and the backward kernels were within 4% of their best
    there.  Tiles this size stay within the default scoped VMEM up to a
    d_head of 256."""
    return fit_blocks(1024, 1024, sq, sk)


def fit_blocks(block_q: int, block_k: int, sq: int, sk: int):
    """No tile longer than its sequence (rounded up to 8 rows)."""
    return min(block_q, -(-sq // 8) * 8), min(block_k, -(-sk // 8) * 8)


# --------------------------------------------------------------------------
# Visibility: which tiles hold a (query, key) pair that may attend
# --------------------------------------------------------------------------

def _visible(q0, k0, bq: int, bk: int, window, causal: bool):
    """Whether any query of rows q0.. may see any key of rows k0.."""
    vis = (window <= 0) | (k0 + bk - 1 > q0 - window)
    if causal:
        vis &= k0 <= q0 + bq - 1
    return vis


def _edge(q0, k0, bq: int, bk: int, window, causal: bool):
    """Whether the tile also holds a pair that may not attend: only such
    tiles pay for the mask."""
    edge = (window > 0) & (q0 + bq - 1 - k0 >= window)
    if causal:
        edge |= k0 + bk - 1 > q0
    return edge


def _mask(q0, k0, shape, window, causal: bool, keys_on_rows=False):
    """(queries, keys) mask of a tile, or (keys, queries) with
    ``keys_on_rows``."""
    qa, ka = (1, 0) if keys_on_rows else (0, 1)
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, qa)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, ka)
    mask = (window <= 0) | ((qpos - kpos) < jnp.maximum(window, 1))
    if causal:
        mask &= qpos >= kpos
    return mask


def _each_visible_tile(q0, k0, bq: int, bk: int, window, causal: bool,
                       body):
    """body(masked) on a visible tile: masked only where it is an edge."""
    visible = _visible(q0, k0, bq, bk, window, causal)
    edge = _edge(q0, k0, bq, bk, window, causal)
    pl.when(visible & edge)(lambda: body(True))
    pl.when(visible & jnp.logical_not(edge))(lambda: body(False))


def _k_block(iq, ik, bq: int, bk: int, nk: int, window, causal: bool):
    """The k block to fetch at (iq, ik): ik itself where the tile is
    visible, else the nearest visible one, which the step before fetched
    already."""
    q0 = iq * bq
    lo = jnp.where(window > 0, jnp.maximum(q0 - window + 1, 0) // bk, 0)
    hi = jnp.minimum((q0 + bq - 1) // bk, nk - 1) if causal else nk - 1
    return jnp.minimum(jnp.maximum(ik, lo), hi)


def _q_block(ik, iq, bq: int, bk: int, nq: int, window, causal: bool):
    """The q block to fetch at (ik, iq), as ``_k_block`` with the roles
    swapped."""
    k0 = ik * bk
    lo = k0 // bq if causal else 0
    hi = jnp.where(window > 0,
                   jnp.minimum((k0 + bk - 2 + window) // bq, nq - 1), nq - 1)
    return jnp.minimum(jnp.maximum(iq, lo), hi)


def _dot_nt(a, b):
    """a @ b.T with f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot(a, b):
    """a @ b with f32 accumulation."""
    return jax.lax.dot(a, b, preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# Kernels
# --------------------------------------------------------------------------

def _fwd_kernel(w_ref, q_ref, k_ref, v_ref, o_ref, *rest,
                scale: float, causal: bool, block_q: int, block_k: int,
                nk: int, with_lse: bool):
    lse_ref, (m_scr, l_scr, acc_scr) = ((rest[0], rest[1:]) if with_lse
                                        else (None, rest))
    iq, ik = pl.program_id(2), pl.program_id(3)
    window = w_ref[0]

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q0, k0 = iq * block_q, ik * block_k

    def body(masked: bool):
        v = v_ref[...]
        s = _dot_nt(q_ref[...], k_ref[...]) * scale            # (bq, bk)
        if masked:
            mask = _mask(q0, k0, s.shape, window, causal)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + _dot(p.astype(v.dtype), v)
        m_scr[...] = m_new

    _each_visible_tile(q0, k0, block_q, block_k, window, causal, body)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
        if with_lse:
            lse_ref[...] = jnp.broadcast_to(m_scr[...] + jnp.log(l),
                                            lse_ref.shape)


def _dkv_kernel(w_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                causal: bool, block_q: int, block_k: int, nq: int, nj: int):
    """Transposed tiles, keys on rows: P^T = exp(K Q^T - lse) and
    dS^T = P^T * (V dO^T - D), with lse and D as (1, bq) rows, so dV and
    dK are plain matmuls.  dK's scale is applied once, at the end."""
    ik, j = pl.program_id(2), pl.program_id(3)
    window = w_ref[0]

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q0, k0 = (j % nq) * block_q, ik * block_k

    def body(masked: bool):
        q, do = q_ref[...], do_ref[...]
        p = jnp.exp(_dot_nt(k_ref[...], q) * scale - lse_ref[...])  # (bk, bq)
        if masked:
            p = jnp.where(_mask(q0, k0, p.shape, window, causal,
                                keys_on_rows=True), p, 0.0)
        dv_scr[...] += _dot(p.astype(do.dtype), do)
        ds = p * (_dot_nt(v_ref[...], do) - d_ref[...])
        dk_scr[...] += _dot(ds.astype(q.dtype), q)

    _each_visible_tile(q0, k0, block_q, block_k, window, causal, body)

    @pl.when(j == nj - 1)
    def _finalize():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(w_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
               dq_ref, dq_scr, *, scale: float, causal: bool,
               block_q: int, block_k: int, nk: int):
    """Queries on rows, lse and D as lane-broadcast columns; dQ's scale
    is applied once, at the end."""
    iq, ik = pl.program_id(2), pl.program_id(3)
    window = w_ref[0]

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q0, k0 = iq * block_q, ik * block_k

    def body(masked: bool):
        k = k_ref[...]
        p = jnp.exp(_dot_nt(q_ref[...], k) * scale - lse_ref[:, :1])
        if masked:
            p = jnp.where(_mask(q0, k0, p.shape, window, causal), p, 0.0)
        ds = p * (_dot_nt(do_ref[...], v_ref[...]) - d_ref[:, :1])
        dq_scr[...] += _dot(ds.astype(k.dtype), k)

    _each_visible_tile(q0, k0, block_q, block_k, window, causal, body)

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


# --------------------------------------------------------------------------
# pallas_calls on the (B, H, S, Dh) layout
# --------------------------------------------------------------------------

def _params(*semantics):
    return tpu_compiler_params(dimension_semantics=semantics)


def _forward(q, k, v, w, causal, bq: int, bk: int, interpret, with_lse):
    b, h, sq, dh = q.shape
    kv, sk = k.shape[1], k.shape[2]
    group = h // kv
    nq, nk = sq // bq, sk // bk

    def kv_map(b_, h_, iq, ik, w_ref):
        return (b_, h_ // group,
                _k_block(iq, ik, bq, bk, nk, w_ref[0], causal), 0)

    q_spec = pl.BlockSpec((None, None, bq, dh),
                          lambda b_, h_, iq, ik, w_ref: (b_, h_, iq, 0))
    kv_spec = pl.BlockSpec((None, None, bk, dh), kv_map)
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec(
            (None, None, bq, LANES),
            lambda b_, h_, iq, ik, w_ref: (b_, h_, iq, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, h, sq, LANES),
                                              jnp.float32))
    kernel = functools.partial(
        _fwd_kernel, scale=1.0 / np.sqrt(dh), causal=causal, block_q=bq,
        block_k=bk, nk=nk, with_lse=with_lse)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, dh), jnp.float32)]),
        out_shape=out_shape,
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
        name="flash_fwd",
    )(w, q, k, v)


def _dkv(q, k, v, w, do, lse, d, causal, bq: int, bk: int, interpret):
    """dK, dV: one (kv head, k block) a cell; its group's query heads and
    their q blocks run in sequence, j = head_in_group * nq + iq.  lse and
    D are (B, H, 1, S) rows."""
    b, h, sq, dh = q.shape
    kv, sk = k.shape[1], k.shape[2]
    group = h // kv
    nq, nk = sq // bq, sk // bk

    def row_map(b_, g, ik, j, w_ref):
        return (b_, g * group + j // nq,
                _q_block(ik, j % nq, bq, bk, nq, w_ref[0], causal), 0)

    def key_map(b_, g, ik, j, w_ref):
        return (b_, g, ik, 0)

    def stat_map(b_, g, ik, j, w_ref):
        b_, h_, iq, _ = row_map(b_, g, ik, j, w_ref)
        return (b_, h_, 0, iq)

    q_spec = pl.BlockSpec((None, None, bq, dh), row_map)
    stat_spec = pl.BlockSpec((None, None, 1, bq), stat_map)
    k_spec = pl.BlockSpec((None, None, bk, dh), key_map)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=1.0 / np.sqrt(dh),
                          causal=causal, block_q=bq, block_k=bk, nq=nq,
                          nj=group * nq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kv, nk, group * nq),
            in_specs=[q_spec, k_spec, k_spec, q_spec, stat_spec, stat_spec],
            out_specs=[k_spec, k_spec],
            scratch_shapes=[pltpu.VMEM((bk, dh), jnp.float32),
                            pltpu.VMEM((bk, dh), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(w, q, k, v, do, lse, d)


def _dq(q, k, v, w, do, lse, d, causal, bq: int, bk: int, interpret):
    """dQ: one (query head, q block) a cell, the k blocks in sequence.
    lse and D are (B, H, S, LANES) lane-broadcast columns."""
    b, h, sq, dh = q.shape
    kv, sk = k.shape[1], k.shape[2]
    group = h // kv
    nq, nk = sq // bq, sk // bk

    def q_map(b_, h_, iq, ik, w_ref):
        return (b_, h_, iq, 0)

    def kv_map(b_, h_, iq, ik, w_ref):
        return (b_, h_ // group,
                _k_block(iq, ik, bq, bk, nk, w_ref[0], causal), 0)

    q_spec = pl.BlockSpec((None, None, bq, dh), q_map)
    stat_spec = pl.BlockSpec((None, None, bq, LANES), q_map)
    kv_spec = pl.BlockSpec((None, None, bk, dh), kv_map)
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=1.0 / np.sqrt(dh),
                          causal=causal, block_q=bq, block_k=bk, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec,
                      stat_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
        name="flash_bwd_dq",
    )(w, q, k, v, do, lse, d)


def _backward(q, k, v, w, out, lse, do, causal, bq: int, bk: int,
              interpret):
    d = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                axis=-1)                                   # D = rowsum(dO*O)
    dk, dv = _dkv(q, k, v, w, do, lse[..., 0][:, :, None], d[:, :, None],
                  causal, bq, bk, interpret)
    dq = _dq(q, k, v, w, do, lse, jnp.broadcast_to(d[..., None], lse.shape),
             causal, bq, bk, interpret)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _attend(q, k, v, w, causal, bq, bk, interpret):
    return _forward(q, k, v, w, causal, bq, bk, interpret, False)[0]


def _attend_fwd(q, k, v, w, causal, bq, bk, interpret):
    out, lse = _forward(q, k, v, w, causal, bq, bk, interpret, True)
    return out, (q, k, v, w, out, lse)


def _attend_bwd(causal, bq, bk, interpret, res, do):
    q, k, v, w, out, lse = res
    dq, dk, dv = _backward(q, k, v, w, out, lse, do, causal, bq, bk,
                           interpret)
    return dq, dk, dv, None


_attend.defvjp(_attend_fwd, _attend_bwd)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, block_q: int, block_k: int,
                    causal: bool = True, window=-1, interpret: bool = False):
    """q: (B, Sq, H, Dh); k, v: (B, Sk, KV, Dh), H % KV == 0.

    ``window`` may be a Python int or a traced scalar (<= 0 means global)
    — it rides in SMEM, matching the stage design where per-layer window
    size is data, not program structure.  Returns (B, Sq, H, Dh) in
    q.dtype and is differentiable in q, k and v.  All three kernels tile
    by (block_q, block_k); Sq % block_q == Sk % block_k == 0 (pad outside
    if needed).
    """
    b, sq, h, dh = q.shape
    sk, kv = k.shape[1], k.shape[2]
    assert h % kv == 0 and sq % block_q == 0 and sk % block_k == 0, (
        q.shape, k.shape, block_q, block_k)
    w = jnp.asarray(window, jnp.int32).reshape(1)
    # heads ahead of the sequence axis: (B, S, H, Dh) -> (B, H, S, Dh)
    qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    return _attend(qt, kt, vt, w, causal, block_q, block_k,
                   interpret).transpose(0, 2, 1, 3)
