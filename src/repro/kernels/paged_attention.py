"""Paged-attention decode Pallas TPU kernel (page-table gather + GQA).

Decode-side attention over a block-paged KV cache: instead of one dense
``(B, cache_len, KV, Dh)`` slab per sequence, keys/values live in a
global page pool ``(P, page, KV, Dh)`` and each sequence owns an ordered
list of page ids (its *page table* row).  The kernel walks the table one
page per sequential grid step: the scalar-prefetched table entry feeds
the k/v BlockSpec index maps, so the gather IS the DMA schedule — each
(page, Dh) tile streams through VMEM exactly like a ``block_k`` tile of
the flash kernel (kernels/flash_attention.py), with the same running
(m, l, acc) softmax scratch discipline.

Grid: (batch, n_pages); the page axis is innermost ("arbitrary" =
sequential on TPU) so the VMEM scratch carries the running state across
pages.  One grid step loads a page for all KV heads — the (page, KV, Dh)
tile keeps the pool's own layout and meets the TPU tiling rule for any
KV count — and walks the heads statically.  GQA is handled by processing
one KV head's whole query-head group (G = H // KV) at a time — the
(G, page) score tile hits the MXU as one matmul.

Speculative verify generalizes the query tile from one position to
``Q = spec_k + 1``: the tile becomes the row-flattened (Q·G, page)
score matrix — row r is query position ``lengths - Q + r // G`` — and
causal masking happens *inside* the tile (``kpos <= qpos`` per row), so
drafts never attend to the suffix they precede.  Q = 1 is plain decode
and reproduces the original kernel bit-for-bit.

Scalar-prefetch operands (SMEM, available before the body runs):
  block_tables (B, n_pages) int32   page ids, -1 = not allocated
  lengths      (B,)         int32   valid keys per sequence
  window       (1,)         int32   sliding window (<= 0: global)

``pl.when`` skips pages past the sequence's valid length (and pages
wholly outside the window for every query row), so a short sequence in a
long-capacity batch costs only its own pages — the roofline win paging
buys at the kernel level on top of the HBM-capacity win.
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


def _paged_kernel(tab_ref, len_ref, w_ref, q_ref, k_ref, v_ref, *rest,
                  page: int, n_pages: int, q_len: int, group: int,
                  n_kv: int, scale: float, quantized: bool):
    if quantized:
        # int8 pools ride with per-(page, kv-head) f32 scales; the scale
        # tile is gathered by the same table entry as its page.
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    i = pl.program_id(1)
    length = len_ref[b]              # valid keys for this sequence
    window = w_ref[0]                # <= 0 means global
    # the q_len queries sit at positions length - q_len .. length - 1;
    # score-tile row r belongs to query position length - q_len + r//group
    min_qpos = length - q_len

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Page-level visibility: skip unallocated pages, pages past the valid
    # length, and pages wholly older than the window for even the OLDEST
    # query (younger queries see strictly less of the past).
    live = (tab_ref[b, i] >= 0) & (i * page < length)
    live &= (window <= 0) | (min_qpos - (i * page + page - 1)
                             < jnp.maximum(window, 1))

    @pl.when(live)
    def _compute():
        # one page tile holds every KV head; walk the heads statically
        for h in range(n_kv):
            q = q_ref[h]                                  # (Q·G, Dh)
            k = k_ref[:, h, :]                            # (page, Dh)
            v = v_ref[:, h, :]
            if quantized:
                # dequantize the page tile in VMEM: int8 payload times
                # the page's per-kv-head scale, f32 end to end
                q = q.astype(jnp.float32)
                k = k.astype(jnp.float32) * ks_ref[:, h:h + 1]
                v = v.astype(jnp.float32) * vs_ref[:, h:h + 1]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (Q·G, page)
            r = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            qpos = min_qpos + r // group                  # per-row query pos
            kpos = i * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = kpos <= qpos
            mask &= (window <= 0) | ((qpos - kpos) < jnp.maximum(window, 1))
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            p = jnp.where(mask, p, 0.0)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(i == n_pages - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    window=-1, k_scale=None, v_scale=None,
                    interpret: bool = False):
    """q: (B, H, Dh) decode or (B, Q, H, Dh) verify; pools (P, page, KV, Dh).

    ``block_tables``: (B, n_pages) int32 page ids into the pool, -1 for
    unallocated entries; ``lengths``: (B,) int32 valid keys per sequence
    — the Q queries sit at positions ``lengths - Q .. lengths - 1``
    (Q = 1 for plain decode, spec_k + 1 for speculative verify; causal
    masking between the queries happens inside the tile).  ``window``
    may be a Python int or traced scalar (<= 0: global).  Returns the
    query shape back ((B, H, Dh) or (B, Q, H, Dh)) in q.dtype; softmax
    statistics in f32.  H % KV == 0.

    int8 pools: pass ``k_scale`` / ``v_scale`` (P, KV) f32 per-page
    per-kv-head scales; the kernel dequantizes each page tile in VMEM
    and computes scores/weighted values in f32.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, q_len, h, dh = q.shape
    n_pool, page, kv, dh_k = k_pages.shape
    assert dh == dh_k and h % kv == 0, (q.shape, k_pages.shape)
    quantized = k_scale is not None
    assert (v_scale is not None) == quantized
    n_pages = block_tables.shape[1]
    group = h // kv
    scale = 1.0 / np.sqrt(dh)
    # row-flatten (Q, G) so one (Q·G, page) tile scores all queries of a
    # KV head per grid step
    qg = (q.reshape(b, q_len, kv, group, dh)
          .transpose(0, 2, 1, 3, 4)
          .reshape(b, kv, q_len * group, dh))

    kernel = functools.partial(_paged_kernel, page=page, n_pages=n_pages,
                               q_len=q_len, group=group, n_kv=kv,
                               scale=scale, quantized=quantized)
    # A page tile carries all KV heads: its last two dims are the pool's
    # full (KV, Dh), which meets the TPU tiling rule for any head count
    # without relayouting the pool.
    page_spec = pl.BlockSpec((None, page, kv, dh),
                             lambda b_, i, tab, lens, w:
                             (jnp.maximum(tab[b_, i], 0), 0, 0, 0))
    q_spec = pl.BlockSpec((None, kv, q_len * group, dh),
                          lambda b_, i, tab, lens, w: (b_, 0, 0, 0))
    in_specs = [q_spec, page_spec, page_spec]
    operands = [qg, k_pages, v_pages]
    if quantized:
        # scale tiles (1, KV) gather with the same table entry as their
        # page
        scale_spec = pl.BlockSpec((None, 1, kv),
                                  lambda b_, i, tab, lens, w:
                                  (jnp.maximum(tab[b_, i], 0), 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [jnp.asarray(k_scale, jnp.float32)[:, None],
                     jnp.asarray(v_scale, jnp.float32)[:, None]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_pages),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((kv, q_len * group, 1), jnp.float32),
            pltpu.VMEM((kv, q_len * group, 1), jnp.float32),
            pltpu.VMEM((kv, q_len * group, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, q_len * group, dh), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(lengths, jnp.int32),
      jnp.asarray(window, jnp.int32).reshape(1),
      *operands)
    out = (out.reshape(b, kv, q_len, group, dh)
           .transpose(0, 2, 1, 3, 4)
           .reshape(b, q_len, h, dh))
    return out[:, 0] if squeeze else out
