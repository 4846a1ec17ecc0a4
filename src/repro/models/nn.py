"""Layer zoo, written shard_map-native.

Every function takes the *local* parameter shard (what one device sees
inside the pipeline shard_map) plus an optional ``tp_axis`` naming the
tensor-parallel mesh axis; collectives no-op when ``tp_axis is None`` so the
same code runs single-device in smoke tests and in the reference pipeline.

Per-layer scalars that vary across stages (attention window, rope theta)
arrive as traced scalars so all stages execute one SPMD program.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import ops as kernel_ops
from repro.parallel.mesh import maybe_axis_index, maybe_psum
from repro.quant import maybe_dequant, quantize_kv_page_batched

# Sequence-length product above which attention switches to flash
# attention (the Pallas kernel where one can run, else the blockwise jnp
# twin) to keep activation memory O(S * block).  4M ⇒ every ≥2k×2k
# attention goes blockwise (train_4k's 4k×4k included — the naive path
# would materialize (mb, h, 4k, 4k) f32 score tensors).
_FLASH_THRESHOLD = 4 * 1024 * 1024
_FLASH_BLOCK = 1024


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rmsnorm(x, scale, eps: float = 1e-6):
    # NOTE (§Perf iteration Q5, refuted): a bf16 normalize-multiply
    # (x * rsqrt(var).astype(x.dtype)) measured WORSE (qwen3 M
    # 18.9 → 32.2 s) — the f32 chain below fuses into its consumer,
    # the split form does not.  Keep the fused f32 form.
    h = x.astype(jnp.float32)
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    return (h * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def layernorm(x, scale, bias, eps: float = 1e-5):
    h = x.astype(jnp.float32)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean((h - mu) ** 2, axis=-1, keepdims=True)
    out = (h - mu) * jax.lax.rsqrt(var + eps)
    return out.astype(x.dtype) * scale + bias


def apply_norm(p, x, kind: str):
    if kind == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"])


def groupnorm_heads(x, scale, bias, eps: float = 1e-5):
    """GroupNorm over the head dim of (B, S, H, Dh) -> normalized per head."""
    h = x.astype(jnp.float32)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean((h - mu) ** 2, axis=-1, keepdims=True)
    out = (h - mu) * jax.lax.rsqrt(var + eps)
    b, s, nh, dh = x.shape
    out = out.reshape(b, s, nh * dh).astype(x.dtype)
    return out * scale + bias


# --------------------------------------------------------------------------
# Rotary embeddings (standard neox rotate-half; chatglm "2d" = half-rotary)
# --------------------------------------------------------------------------

def rope_frequencies(d_rot: int, theta):
    exponent = jnp.arange(0, d_rot, 2, dtype=jnp.float32) / d_rot
    return 1.0 / (theta ** exponent)  # (d_rot/2,)


def apply_rope(q, k, positions, theta, *, rope_2d: bool = False):
    """q: (B,S,H,Dh), k: (B,S,KV,Dh), positions: (B,S) int32, theta traced."""
    dh = q.shape[-1]
    d_rot = dh // 2 if rope_2d else dh
    inv = rope_frequencies(d_rot, theta)
    ang = positions.astype(jnp.float32)[..., None] * inv  # (B,S,d_rot/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]

    def rot(x):
        rx, keep = x[..., :d_rot], x[..., d_rot:]
        x1, x2 = rx[..., : d_rot // 2], rx[..., d_rot // 2:]
        r1 = x1 * cos - x2 * sin
        r2 = x2 * cos + x1 * sin
        out = jnp.concatenate([r1, r2], axis=-1).astype(x.dtype)
        return jnp.concatenate([out, keep], axis=-1) if rope_2d else out

    return rot(q), rot(k)


# --------------------------------------------------------------------------
# Attention (GQA + qk-norm + sliding window + KV cache + cross-attention)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnStatic:
    """Static (compile-time) attention configuration for one device."""

    n_heads_local: int
    n_kv_local: int            # local kv heads after sharding (>=1)
    d_head: int
    kv_sharded: bool           # False -> kv weights replicated; slice by rank
    kv_groups_per_device: int  # only used when not kv_sharded
    qk_norm: bool
    rope_2d: bool
    causal: bool = True


def _project_kv(p, x, st: AttnStatic, tp_axis):
    """Project K/V, handling replicated-kv slicing when kv < tp."""
    wk = maybe_dequant(p["wk"], x.dtype)
    wv = maybe_dequant(p["wv"], x.dtype)
    if not st.kv_sharded:
        rank = maybe_axis_index(tp_axis)
        grp = rank // st.kv_groups_per_device if st.kv_groups_per_device else 0
        wk = jax.lax.dynamic_slice_in_dim(wk, grp * st.n_kv_local, st.n_kv_local, 1)
        wv = jax.lax.dynamic_slice_in_dim(wv, grp * st.n_kv_local, st.n_kv_local, 1)
    k = jnp.einsum("bsd,dkh->bskh", x, wk)
    v = jnp.einsum("bsd,dkh->bskh", x, wv)
    return k, v


_INVALID_POS = -(10 ** 9)  # sentinel for padded / not-yet-written KV slots


def _attn_mask(q_pos, k_pos, window, causal: bool):
    """(Q, K) bool mask from traced positions + traced window (<=0: global)."""
    dq = q_pos[:, None] - k_pos[None, :]
    m = jnp.ones(dq.shape, bool) if not causal else (dq >= 0)
    m = m & ((window <= 0) | (dq < jnp.maximum(window, 1)))
    m = m & (k_pos > _INVALID_POS // 2)[None, :]
    return m


@obs.scoped(obs.ATTENTION)
def _sdpa_naive(q, k, v, mask):
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    s = jnp.where(mask[:, None] if mask.ndim == 3 else mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


@obs.scoped(obs.ATTENTION)
def _sdpa_flash_jnp(q, k, v, q_pos, k_pos, window, causal, block: int = _FLASH_BLOCK):
    """Blockwise (flash) attention in pure jnp: O(S*block) memory.

    Scans over KV blocks carrying running (max, sum, acc) — the TPU Pallas
    kernel in repro.kernels.flash_attention is the hardware version of this
    loop; this is its XLA-lowerable twin, which training graphs run where
    no kernel can (``kernel_ops.use_flash()``).
    """
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    nblk = -(-sk // block)
    pad = nblk * block - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=_INVALID_POS)
    scale = 1.0 / np.sqrt(dh)
    kb = k.reshape(b, nblk, block, -1, dh)
    vb = v.reshape(b, nblk, block, -1, dh)
    kpb = k_pos.reshape(nblk, block)

    def step(carry, inp):
        m_run, l_run, acc = carry
        kblk, vblk, kp = inp
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kblk).astype(jnp.float32) * scale
        mask = _attn_mask(q_pos, kp, window, causal)
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_run * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vblk.dtype), vblk).astype(jnp.float32)
        return (m_new, l_new, acc), None

    init = (
        jnp.full((b, h, sq), -jnp.inf, jnp.float32),
        jnp.zeros((b, h, sq), jnp.float32),
        jnp.zeros((b, h, sq, dh), jnp.float32),
    )
    # checkpoint the block step: backward recomputes the (sq, block)
    # score/probability tile from (q, k-block) instead of storing an
    # O(S²) f32 residual — the jnp twin of what the Pallas kernel's
    # VMEM-resident tile achieves structurally.
    (m_run, l_run, acc), _ = jax.lax.scan(
        jax.checkpoint(step), init,
        (kb.swapaxes(0, 1), vb.swapaxes(0, 1), kpb))
    out = acc / jnp.maximum(l_run, 1e-30)[..., None]
    return out.swapaxes(1, 2).astype(q.dtype)  # (B, S, H, Dh)


@obs.scoped(obs.ATTENTION)
def _sdpa_decode_seq_sharded(q, k, v, q_pos, k_pos, window, seq_axis):
    """Decode attention over a sequence-sharded KV cache (SP decode).

    Each device holds a KV shard; partial softmax statistics combine with
    pmax/psum over ``seq_axis``.  q: (B, 1, H, Dh); k/v: local shards.
    """
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    mask = _attn_mask(q_pos, k_pos, window, causal=True)
    s = jnp.where(mask[None, None], s, -1e30)
    m_loc = jnp.max(s, axis=-1)
    m_glob = jax.lax.pmax(m_loc, seq_axis)
    p = jnp.exp(s - m_glob[..., None])
    l_loc = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v
                     ).astype(jnp.float32)
    l_glob = jax.lax.psum(l_loc, seq_axis)
    acc = jax.lax.psum(acc, seq_axis)
    out = acc / jnp.maximum(l_glob, 1e-30)[..., None]
    return out.swapaxes(1, 2).astype(q.dtype)


def attention(
    p,
    x,
    st: AttnStatic,
    *,
    positions,                 # (B, S) int32 query positions
    window,                    # traced scalar; <=0 means global
    theta,                     # traced rope theta
    tp_axis: Optional[str],
    kv_cache: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    cache_pos=None,            # scalar write offset into the cache
    cross_x=None,              # encoder output for cross attention
    seq_axis: Optional[str] = None,  # cache sharded over this axis (SP)
    paged_kv=None,        # (pools, table_row, write_gate, tokenwise);
                          # pools = (k, v) or int8 (k, v, k_scale, v_scale)
):
    """Returns (out, new_kv_cache). x: (B, S, d_local-replicated)."""
    b, s, _ = x.shape
    wo = maybe_dequant(p["wo"], x.dtype)
    q = jnp.einsum("bsd,dhk->bshk", x, maybe_dequant(p["wq"], x.dtype))
    kv_src = cross_x if cross_x is not None else x
    k, v = _project_kv(p, kv_src, st, tp_axis)

    if st.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])

    if cross_x is None:
        k_positions_new = positions[0] if positions.ndim == 2 else positions
        q, k = apply_rope(q, k, positions, theta, rope_2d=st.rope_2d)
    else:
        k_positions_new = jnp.arange(kv_src.shape[1])

    new_cache = None
    if kv_cache is not None and seq_axis is not None:
        # SP decode: cache sharded over seq_axis; writes land on the owner
        # shard via scatter-drop, reads combine partial softmax stats.
        assert s == 1, "sequence-sharded cache supports decode (S=1) only"
        ck, cv = kv_cache                       # (B, L_local, KV, Dh)
        l_local = ck.shape[1]
        off = jax.lax.axis_index(seq_axis) * l_local
        idx = cache_pos - off                   # out-of-range writes drop
        ck = ck.at[:, idx].set(k[:, 0].astype(ck.dtype), mode="drop")
        cv = cv.at[:, idx].set(v[:, 0].astype(cv.dtype), mode="drop")
        new_cache = (ck, cv)
        k_pos = off + jnp.arange(l_local)
        k_pos = jnp.where(k_pos < cache_pos + 1, k_pos, _INVALID_POS)
        groups = st.n_heads_local // ck.shape[2]
        kk = jnp.repeat(ck, groups, axis=2)
        vv = jnp.repeat(cv, groups, axis=2)
        q_pos = positions[0] if positions.ndim == 2 else positions
        out = _sdpa_decode_seq_sharded(q, kk, vv, q_pos, k_pos, window,
                                       seq_axis)
        out = out.reshape(b, s, st.n_heads_local * st.d_head)
        out = jnp.einsum("bsk,kd->bsd", out, wo)
        return maybe_psum(out, tp_axis), new_cache

    if paged_kv is not None:
        # Block-paged KV cache (serving decode/prefill). The pools are
        # global across slots — (n_pool, B, page, KV, Dh) — and ``row``
        # is this slot's page table (-1 = unallocated). Writes are gated
        # by ``gate`` (slot validity) AND page liveness; reads gather the
        # table into a dense (B, n_pages*page, KV, Dh) view whose extent
        # and k_pos mask match the dense ring path exactly, so fp32
        # outputs are bit-identical to the dense cache (masked entries
        # contribute exact zeros to the softmax).
        assert kv_cache is None and cross_x is None and seq_axis is None
        pools, row, gate = paged_kv[0], paged_kv[1], paged_kv[2]
        # token-wise writes: decode always; s > 1 only when the caller
        # says so (speculative verify) — prefill keeps the aligned slab.
        tokenwise = (s == 1) or (len(paged_kv) > 3 and bool(paged_kv[3]))
        kq = len(pools) == 4      # int8 pools carry per-page scale planes
        if kq:
            k_pool, v_pool, ks_pool, vs_pool = pools
        else:
            (k_pool, v_pool), ks_pool, vs_pool = pools, None, None
        n_pool, _, ps, n_kv, dh = k_pool.shape
        npg = row.shape[0]
        L = npg * ps
        q_pos = positions[0] if positions.ndim == 2 else positions

        def _write_page(pool, new, pi, width):
            # new: (B, width, KV, Dh) slab chunk for table entry ``pi``.
            pid = jax.lax.dynamic_index_in_dim(row, pi, keepdims=False)
            ok = gate & (pid >= 0)
            pid_safe = jnp.clip(pid, 0, n_pool - 1)
            cur = jax.lax.dynamic_slice(
                pool, (pid_safe, 0, 0, 0, 0), (1, b, ps, n_kv, dh))
            upd = cur.at[0, :, :width].set(new.astype(pool.dtype))
            upd = jnp.where(ok, upd, cur)
            return jax.lax.dynamic_update_slice(
                pool, upd, (pid_safe, 0, 0, 0, 0))

        def _write_page_q(pool, spool, new, pi, width):
            # int8 prefill write: quantize a freshly built zero-padded
            # page (one scale per kv head per page).  Zeroing the tail
            # past ``width`` is safe — decode appends token-wise later,
            # requantizing the whole page.
            pid = jax.lax.dynamic_index_in_dim(row, pi, keepdims=False)
            ok = gate & (pid >= 0)
            pid_safe = jnp.clip(pid, 0, n_pool - 1)
            page = jnp.zeros((b, ps, n_kv, dh), jnp.float32)
            page = page.at[:, :width].set(new.astype(jnp.float32))
            qpage, scale = quantize_kv_page_batched(page)
            cur = jax.lax.dynamic_slice(
                pool, (pid_safe, 0, 0, 0, 0), (1, b, ps, n_kv, dh))
            cur_s = jax.lax.dynamic_slice(
                spool, (pid_safe, 0, 0), (1, b, n_kv))
            pool = jax.lax.dynamic_update_slice(
                pool, jnp.where(ok, qpage[None], cur),
                (pid_safe, 0, 0, 0, 0))
            spool = jax.lax.dynamic_update_slice(
                spool, jnp.where(ok, scale[None], cur_s),
                (pid_safe, 0, 0))
            return pool, spool

        if tokenwise:
            # decode / verify: key t lands at offset (cache_pos + t) % ps
            # inside the slot's page (cache_pos + t) // ps.  Token-wise
            # (static unroll over s, a compile-time constant: 1 for
            # decode, spec_k + 1 for verify) because a verify round
            # starts at an arbitrary mid-page position — the aligned
            # slab write below would clobber the page's earlier tokens.

            def _write_tok(pool, new, t):
                posn = cache_pos + t
                pi = posn // ps
                off = posn % ps
                pid = jax.lax.dynamic_index_in_dim(row, pi, keepdims=False)
                ok = gate & (pid >= 0)
                pid_safe = jnp.clip(pid, 0, n_pool - 1)
                cur = jax.lax.dynamic_slice(
                    pool, (pid_safe, 0, off, 0, 0), (1, b, 1, n_kv, dh))
                upd = jnp.where(ok, new[None, :, None].astype(pool.dtype),
                                cur)
                return jax.lax.dynamic_update_slice(
                    pool, upd, (pid_safe, 0, off, 0, 0))

            def _write_tok_q(pool, spool, new, t):
                posn = cache_pos + t
                pi = posn // ps
                off = posn % ps
                pid = jax.lax.dynamic_index_in_dim(row, pi, keepdims=False)
                ok = gate & (pid >= 0)
                pid_safe = jnp.clip(pid, 0, n_pool - 1)
                cur = jax.lax.dynamic_slice(
                    pool, (pid_safe, 0, 0, 0, 0), (1, b, ps, n_kv, dh))
                cur_s = jax.lax.dynamic_slice(
                    spool, (pid_safe, 0, 0), (1, b, n_kv))
                # dequantize the whole page, insert the token, requantize:
                # one scale per page stays valid under arbitrary new-token
                # magnitudes (requantization drift is bounded by the page
                # length and gated by the serving tolerance tests).
                page = (cur[0].astype(jnp.float32)
                        * cur_s[0][:, None, :, None])
                page = jax.lax.dynamic_update_slice(
                    page, new[:, None].astype(jnp.float32), (0, off, 0, 0))
                qpage, scale = quantize_kv_page_batched(page)
                pool = jax.lax.dynamic_update_slice(
                    pool, jnp.where(ok, qpage[None], cur),
                    (pid_safe, 0, 0, 0, 0))
                spool = jax.lax.dynamic_update_slice(
                    spool, jnp.where(ok, scale[None], cur_s),
                    (pid_safe, 0, 0))
                return pool, spool

            for t in range(s):
                if kq:
                    k_pool, ks_pool = _write_tok_q(k_pool, ks_pool,
                                                   k[:, t], t)
                    v_pool, vs_pool = _write_tok_q(v_pool, vs_pool,
                                                   v[:, t], t)
                else:
                    k_pool = _write_tok(k_pool, k[:, t], t)
                    v_pool = _write_tok(v_pool, v[:, t], t)
            if st.causal and kernel_ops.use_pallas():
                # Pallas paged kernel: flatten (page, lane) so every lane
                # gets its own table row (all lanes of a slot share page
                # ids and the slot's length); the s queries sit at
                # positions cache_pos .. cache_pos + s - 1.
                lane = jnp.arange(b, dtype=jnp.int32)
                tabs = jnp.where(row[None, :] >= 0,
                                 row[None, :] * b + lane[:, None], -1)
                lens_v = jnp.full((b,), cache_pos + s, jnp.int32)
                kp = k_pool.swapaxes(0, 1).reshape(n_pool * b, ps, n_kv, dh)
                vp = v_pool.swapaxes(0, 1).reshape(n_pool * b, ps, n_kv, dh)
                if kq:
                    ks = ks_pool.swapaxes(0, 1).reshape(n_pool * b, n_kv)
                    vs = vs_pool.swapaxes(0, 1).reshape(n_pool * b, n_kv)
                else:
                    ks = vs = None
                out = kernel_ops.paged_attention(q, kp, vp, tabs, lens_v,
                                                 window=window,
                                                 k_scale=ks, v_scale=vs)
                out = out.reshape(b, s, st.n_heads_local * st.d_head)
                out = jnp.einsum("bsk,kd->bsd", out, wo)
                new_cache = ((k_pool, v_pool, ks_pool, vs_pool) if kq
                             else (k_pool, v_pool))
                return maybe_psum(out, tp_axis), new_cache
        else:
            # prefill: write the fresh slab page-by-page (static unroll —
            # n_pages_slab is a compile-time constant). Unallocated pages
            # of ragged slots skip via the per-page gate.
            for ii in range(-(-s // ps)):
                lo = ii * ps
                width = min(ps, s - lo)
                pi = cache_pos // ps + ii
                if kq:
                    k_pool, ks_pool = _write_page_q(
                        k_pool, ks_pool, k[:, lo:lo + width], pi, width)
                    v_pool, vs_pool = _write_page_q(
                        v_pool, vs_pool, v[:, lo:lo + width], pi, width)
                else:
                    k_pool = _write_page(k_pool, k[:, lo:lo + width],
                                         pi, width)
                    v_pool = _write_page(v_pool, v[:, lo:lo + width],
                                         pi, width)

        # XLA twin read: gather the table into a dense slab and fall
        # through to the shared masked-softmax tail.
        safe = jnp.clip(row, 0, n_pool - 1)
        kk = jnp.take(k_pool, safe, axis=0)      # (npg, B, ps, KV, Dh)
        vv = jnp.take(v_pool, safe, axis=0)
        if kq:
            sk = jnp.take(ks_pool, safe, axis=0)   # (npg, B, KV)
            sv = jnp.take(vs_pool, safe, axis=0)
            kk = (kk.astype(jnp.float32)
                  * sk[:, :, None, :, None]).astype(q.dtype)
            vv = (vv.astype(jnp.float32)
                  * sv[:, :, None, :, None]).astype(q.dtype)
        k = kk.transpose(1, 0, 2, 3, 4).reshape(b, L, n_kv, dh)
        v = vv.transpose(1, 0, 2, 3, 4).reshape(b, L, n_kv, dh)
        j_idx = jnp.arange(L)
        alive = jnp.repeat(row >= 0, ps)
        k_pos = jnp.where((j_idx < cache_pos + s) & alive, j_idx,
                          _INVALID_POS)
        new_cache = ((k_pool, v_pool, ks_pool, vs_pool) if kq
                     else (k_pool, v_pool))
    elif kv_cache is not None:
        ck, cv = kv_cache  # (B, L, KV, Dh)
        L = ck.shape[1]
        if s == 1:
            # decode: ring-buffer write. For full caches (L > pos always)
            # this reduces to an append; for windowed caches (L == window)
            # old positions are overwritten — sliding-window semantics.
            idx = cache_pos % L
            ck = jax.lax.dynamic_update_index_in_dim(
                ck, k[:, 0].astype(ck.dtype), idx, 1)
            cv = jax.lax.dynamic_update_index_in_dim(
                cv, v[:, 0].astype(cv.dtype), idx, 1)
            j = jnp.arange(L)
            # most recent position congruent to slot j that is <= cache_pos
            k_pos = cache_pos - ((cache_pos - j) % L)
            k_pos = jnp.where(k_pos >= 0, k_pos, _INVALID_POS)
        else:
            # prefill: contiguous slab write (cache must be full-length)
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, k.astype(ck.dtype), cache_pos, 1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, v.astype(cv.dtype), cache_pos, 1)
            k_pos = jnp.arange(L)
            k_pos = jnp.where(k_pos < cache_pos + s, k_pos, _INVALID_POS)
        new_cache = (ck, cv)
        k, v = ck, cv
    else:
        k_pos = k_positions_new

    causal = st.causal and cross_x is None
    long = s * k.shape[1] > _FLASH_THRESHOLD
    if (long and causal and kv_cache is None and paged_kv is None
            and kernel_ops.use_flash()):
        # causal self-attention over a whole sequence (training: query
        # and key i sit at position i): the Pallas flash kernel and its
        # own backward, GQA in the BlockSpec index maps, window in SMEM
        with jax.named_scope(obs.ATTENTION):
            out = kernel_ops.flash_attention(q, k, v, causal=True,
                                             window=window)
    else:
        # GQA: broadcast kv heads to query heads
        groups = st.n_heads_local // k.shape[2]
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
        q_pos = positions[0] if positions.ndim == 2 else positions
        if long:
            out = _sdpa_flash_jnp(q, k, v, q_pos, k_pos, window, causal)
        else:
            mask = _attn_mask(q_pos, k_pos, window, causal)
            out = _sdpa_naive(q, k, v, mask[None, None])

    out = out.reshape(b, s, st.n_heads_local * st.d_head)
    out = jnp.einsum("bsk,kd->bsd", out, wo)
    return maybe_psum(out, tp_axis), new_cache


# --------------------------------------------------------------------------
# Dense FFN (SwiGLU / GELU), tensor-parallel column->row split
# --------------------------------------------------------------------------

def mlp(p, x, act: str, tp_axis: Optional[str]):
    w1 = maybe_dequant(p["w1"], x.dtype)
    w2 = maybe_dequant(p["w2"], x.dtype)
    if act == "silu":
        h = jax.nn.silu(x @ w1) * (x @ maybe_dequant(p["w3"], x.dtype))
    else:
        h = jax.nn.gelu(x @ w1)
    return maybe_psum(h @ w2, tp_axis)


# --------------------------------------------------------------------------
# Mixture of Experts (GShard-style capacity dispatch, experts over tensor)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MoEStatic:
    n_experts: int
    n_local: int               # experts on this device
    top_k: int
    capacity: int              # per-expert token slots
    n_shared: int


def moe_dispatch_indices(gate_idx, n_experts: int, capacity: int):
    """Sort-based dispatch: (N*K,) expert ids -> slot assignment.

    Returns (slot_id, keep) where slot_id = expert*capacity + position and
    keep masks tokens dropped past capacity.  Pure jnp; XLA lowers the sort.
    """
    nk = gate_idx.shape[0]
    order = jnp.argsort(gate_idx, stable=True)
    sorted_e = gate_idx[order]
    counts = jnp.bincount(gate_idx, length=n_experts)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(nk) - starts[sorted_e]
    keep_sorted = pos_in_e < capacity
    slot_sorted = sorted_e * capacity + jnp.minimum(pos_in_e, capacity - 1)
    inv = jnp.argsort(order, stable=True)
    return slot_sorted[inv], keep_sorted[inv]


def moe(p, x, ms: MoEStatic, act: str, tp_axis: Optional[str]):
    """x: (B, S, d) replicated over tensor; experts sharded over tensor.

    Compute per device = n_local * capacity * expert FLOPs (true top-k cost,
    not dense-dispatch).  Returns (out, aux_loss).
    """
    b, s, d = x.shape
    n = b * s
    xf = x.reshape(n, d)

    logits = (xf @ p["router"]).astype(jnp.float32)          # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, ms.top_k)            # (N, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balancing auxiliary loss
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(top_i[:, 0], ms.n_experts, dtype=jnp.float32), axis=0)
    aux = ms.n_experts * jnp.sum(me * ce)

    flat_e = top_i.reshape(-1)
    slot, keep = moe_dispatch_indices(flat_e, ms.n_experts, ms.capacity)
    token_of = jnp.repeat(jnp.arange(n), ms.top_k)

    buf = jnp.zeros((ms.n_experts * ms.capacity, d), x.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], xf[token_of], 0))
    buf = buf.reshape(ms.n_experts, ms.capacity, d)

    # Each device computes only its expert shard.
    rank = maybe_axis_index(tp_axis)
    local = jax.lax.dynamic_slice_in_dim(buf, rank * ms.n_local, ms.n_local, 0)
    mw1 = maybe_dequant(p["w1"], x.dtype)
    if act == "silu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", local, mw1)) * \
            jnp.einsum("ecd,edf->ecf", local,
                       maybe_dequant(p["w3"], x.dtype))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", local, mw1))
    y_local = jnp.einsum("ecf,efd->ecd", h,
                         maybe_dequant(p["w2"], x.dtype))

    # EP combine: all-gather the per-device expert outputs over the
    # tensor axis (rank order == expert order).  Half the wire bytes of
    # the zero-padded full-buffer all-reduce this replaces, and no
    # wasted adds of zero slots (§Perf iteration D1).
    if tp_axis is None:
        y = y_local.reshape(ms.n_experts * ms.capacity, d)
    else:
        y = jax.lax.all_gather(y_local, tp_axis, axis=0, tiled=True)
        y = y.reshape(ms.n_experts * ms.capacity, d)

    gathered = y[slot] * jnp.where(keep, top_p.reshape(-1), 0.0)[:, None].astype(x.dtype)
    out = jnp.zeros((n, d), x.dtype).at[token_of].add(gathered)
    out = out.reshape(b, s, d)

    if ms.n_shared:
        out = out + mlp(p["shared"], x, act, tp_axis)
    return out, aux


# --------------------------------------------------------------------------
# Mamba (selective state space; jamba's mixer), channel-sharded TP
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MambaStatic:
    d_inner_local: int
    d_state: int
    d_conv: int
    dt_rank: int
    chunk: int = 256


def _causal_conv1d(x, w):
    """Depthwise causal conv via shifts; x: (B,S,C), w: (C,K)."""
    k = w.shape[-1]
    out = x * w[:, -1]
    for i in range(1, k):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, : x.shape[1]]
        out = out + shifted * w[:, -1 - i]
    return out


def selective_scan(u, dt, A, B, C, D, *, chunk: int, h0=None):
    """Chunked selective scan. u,dt: (B,S,Ci); A: (Ci,N); B,C: (B,S,N).

    Diagonal linear recurrence h_t = exp(dt_t A) h_{t-1} + dt_t B_t u_t,
    y_t = (h_t . C_t) + D u_t.  Within-chunk via associative scan, chunks
    sequential (carrying h) — O(S/chunk) sequential steps, O(chunk) memory.
    Returns (y, h_last) so decode can carry state.
    """
    b, s, ci = u.shape
    n = A.shape[-1]
    nchunk = -(-s // chunk)
    pad = nchunk * chunk - s
    if pad:
        u = jnp.pad(u, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    # (B, nchunk, chunk, ·) views — the (B,S,Ci,N) decay/input expansions
    # are built PER CHUNK inside the (rematerialized) scan body, never at
    # full sequence length (§Perf iteration J2); the Pallas kernel
    # (kernels/mamba_scan.py) keeps even the per-chunk expansion in VMEM.
    uc = u.reshape(b, nchunk, chunk, ci).swapaxes(0, 1)
    dtc = dt.reshape(b, nchunk, chunk, ci).swapaxes(0, 1)
    Bc = B.reshape(b, nchunk, chunk, n).swapaxes(0, 1)
    Cc = C.reshape(b, nchunk, chunk, n).swapaxes(0, 1)

    def assoc(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a2 * a1, a2 * b1 + b2

    def chunk_step(h, inp):
        u_, dt_, B_, cc = inp                                # (B,chunk,·)
        da = jnp.exp(dt_[..., None] * A)                     # (B,chunk,Ci,N)
        dbu = (dt_ * u_)[..., None] * B_[:, :, None, :]
        acc_a, acc_b = jax.lax.associative_scan(assoc, (da, dbu), axis=1)
        h_t = acc_a * h[:, None] + acc_b                     # (B,chunk,Ci,N)
        y = jnp.einsum("btcn,btn->btc", h_t, cc)
        return h_t[:, -1], y

    if h0 is None:
        h0 = jnp.zeros((b, ci, n), jnp.float32)
    h_last, ys = jax.lax.scan(jax.checkpoint(chunk_step), h0,
                              (uc, dtc, Bc, Cc))
    y = ys.swapaxes(0, 1).reshape(b, nchunk * chunk, ci)[:, :s]
    return y + u[:, :s] * D, h_last


def mamba_block(p, x, ms: MambaStatic, tp_axis: Optional[str], state=None):
    """x: (B,S,d). state: (conv_tail (B,K-1,Ci), h (B,Ci,N)) for decode."""
    xi = x @ p["in_x"]                                       # (B,S,Ci)
    z = x @ p["in_z"]
    if state is not None:
        conv_tail, h0 = state
        xi_cat = jnp.concatenate([conv_tail, xi], axis=1)
        new_tail = xi_cat[:, -(ms.d_conv - 1):]
        conv_in = xi_cat
        xc = _causal_conv1d(conv_in, p["conv_w"])[:, -(xi.shape[1]):]
    else:
        h0 = None
        new_tail = None
        xc = _causal_conv1d(xi, p["conv_w"])
    xc = jax.nn.silu(xc)
    # x_proj rows are channel-sharded: partial products reduce over tp so
    # dt/B/C match the unsharded reference exactly.
    proj = maybe_psum(xc @ p["x_proj"], tp_axis)             # (B,S,dt_rank+2N)
    dt_in, Bm, Cm = jnp.split(
        proj, [ms.dt_rank, ms.dt_rank + ms.d_state], axis=-1)
    dt = jax.nn.softplus(dt_in @ p["dt_proj"] + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    if h0 is None and state is None and kernel_ops.use_pallas():
        # Pallas TPU selective-scan kernel (kernels/mamba_scan.py).
        y, h_last = kernel_ops.mamba_scan(
            xc.astype(jnp.float32), dt.astype(jnp.float32), A,
            Bm.astype(jnp.float32), Cm.astype(jnp.float32), p["D"],
            chunk=ms.chunk)
    else:
        y, h_last = selective_scan(
            xc.astype(jnp.float32), dt.astype(jnp.float32), A,
            Bm.astype(jnp.float32), Cm.astype(jnp.float32), p["D"],
            chunk=ms.chunk, h0=h0)
    y = (y.astype(x.dtype) * jax.nn.silu(z)) @ p["out_proj"]
    out = maybe_psum(y, tp_axis)
    new_state = (new_tail, h_last) if state is not None else None
    return out, new_state


# --------------------------------------------------------------------------
# RWKV6 (Finch): time-mix with data-dependent decay + channel-mix
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RWKVStatic:
    n_heads_local: int
    d_head: int
    chunk: int = 128


def _token_shift(x, prev=None):
    """x_{t-1} per position; ``prev`` carries the last token for decode."""
    if prev is None:
        return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, : x.shape[1]]
    return jnp.concatenate([prev[:, None], x], axis=1)[:, : x.shape[1]]


@obs.scoped(obs.WKV)
def wkv6_chunked(r, k, v, w, u, *, chunk: int, s0=None):
    """RWKV6 WKV with matrix-valued state and per-channel decay.

    r,k,v: (B,S,H,Dh); w: (B,S,H,Dh) decay in (0,1); u: (H,Dh) bonus.
      S_t = diag(w_t) S_{t-1} + k_t v_t^T
      y_t = r_t . (S_{t-1} + diag(u) k_t v_t^T)
    Chunked: intra-chunk O(chunk^2) attention-like term + inter-chunk state.
    This is the jnp oracle twin of kernels/wkv6.py.  Returns (y, s_last).
    """
    b, s, h, dh = r.shape
    nchunk = -(-s // chunk)
    pad = nchunk * chunk - s
    if pad:
        zpad = ((0, 0), (0, pad), (0, 0), (0, 0))
        r, k, v = jnp.pad(r, zpad), jnp.pad(k, zpad), jnp.pad(v, zpad)
        w = jnp.pad(w, zpad, constant_values=1.0)

    def rs(x):
        return x.reshape(b, nchunk, chunk, h, dh).swapaxes(0, 1)

    rc, kc, vc, wc = rs(r), rs(k), rs(v), rs(w)
    logw = jnp.log(jnp.clip(wc.astype(jnp.float32), 1e-8, 1.0))
    cum = jnp.cumsum(logw, axis=2)                            # (n,B,C,H,Dh)

    def chunk_step(state, inp):
        rb, kb, vb, cumb, logwb = inp                         # (B,C,H,Dh)
        # inter-chunk: y += (r_t * prod_{<=t-1} w) @ S
        decay_to_t = jnp.exp(cumb - logwb)                    # prod over [0, t-1]
        y_inter = jnp.einsum("bchd,bhde->bche",
                             (rb.astype(jnp.float32) * decay_to_t), state)
        # intra-chunk: s<t term with decay prod_{s<tau<t} ... = exp(cum_{t-1}-cum_s)
        att = jnp.einsum("bchd,bghd->bhcg",
                         rb.astype(jnp.float32) * decay_to_t,
                         kb.astype(jnp.float32) * jnp.exp(-cumb))
        tri = jnp.tril(jnp.ones((rb.shape[1], rb.shape[1]), bool), -1)
        att = jnp.where(tri[None, None], att, 0.0)
        y_intra = jnp.einsum("bhcg,bghd->bchd", att, vb.astype(jnp.float32))
        # bonus diagonal term
        y_diag = jnp.einsum("bchd,bchd,bche->bche",
                            rb.astype(jnp.float32), u[None, None] *
                            kb.astype(jnp.float32), vb.astype(jnp.float32))
        y = y_inter + y_intra + y_diag
        # state update: S' = diag(prod w) S + sum_s (prod_{tau>s} w) k_s v_s
        total = jnp.exp(cumb[:, -1])                          # (B,H,Dh)
        kdec = kb.astype(jnp.float32) * jnp.exp(cumb[:, -1][:, None] - cumb)
        state = total[..., None] * state + jnp.einsum(
            "bchd,bche->bhde", kdec, vb.astype(jnp.float32))
        return state, y

    if s0 is None:
        s0 = jnp.zeros((b, h, dh, dh), jnp.float32)
    s_last, ys = jax.lax.scan(chunk_step, s0, (rc, kc, vc, cum, logw))
    y = ys.swapaxes(0, 1).reshape(b, nchunk * chunk, h, dh)[:, :s]
    return y.astype(r.dtype), s_last


def rwkv_time_mix(p, x, rst: RWKVStatic, tp_axis: Optional[str], state=None):
    """RWKV6 time-mix. state = (x_prev (B,d), wkv_state) for decode."""
    prev_tok = state[0] if state is not None else None
    s0 = state[1] if state is not None else None
    xs = _token_shift(x, prev_tok)
    dx = xs - x

    xxx = x + dx * p["maa_x"]
    low = jnp.tanh(xxx @ p["tmix_w1"])                        # (B,S,5*r)
    low = low.reshape(*low.shape[:-1], 5, -1)
    mids = jnp.einsum("bsfr,frd->bsfd", low, p["tmix_w2"])    # (B,S,5,d)
    mw, mk, mv, mr, mg = [mids[:, :, i] for i in range(5)]
    xw = x + dx * (p["maa_w"] + mw)
    xk = x + dx * (p["maa_k"] + mk)
    xv = x + dx * (p["maa_v"] + mv)
    xr = x + dx * (p["maa_r"] + mr)
    xg = x + dx * (p["maa_g"] + mg)

    b, s, _ = x.shape
    h, dh = rst.n_heads_local, rst.d_head
    r = (xr @ p["wr"]).reshape(b, s, h, dh)
    k = (xk @ p["wk"]).reshape(b, s, h, dh)
    v = (xv @ p["wv"]).reshape(b, s, h, dh)
    g = jax.nn.silu(xg @ p["wg"])
    dec = p["w0"] + jnp.tanh(xw @ p["decay_w1"]) @ p["decay_w2"]
    w = jnp.exp(-jnp.exp(dec.astype(jnp.float32))).reshape(b, s, h, dh)

    if s0 is None and state is None and kernel_ops.use_pallas():
        # Pallas TPU chunked WKV kernel (kernels/wkv6.py), train mode.
        with jax.named_scope(obs.WKV):
            y, s_last = kernel_ops.wkv6(r, k, v, w.astype(r.dtype),
                                        p["u"].reshape(h, dh),
                                        chunk=rst.chunk)
    else:
        y, s_last = wkv6_chunked(r, k, v, w.astype(r.dtype),
                                 p["u"].reshape(h, dh), chunk=rst.chunk,
                                 s0=s0)
    y = groupnorm_heads(y, p["gn_scale"], p["gn_bias"])
    out = (y * g) @ p["wo"]
    out = maybe_psum(out, tp_axis)
    new_state = (x[:, -1], s_last) if state is not None else None
    return out, new_state


def rwkv_channel_mix(p, x, tp_axis: Optional[str], state=None):
    prev_tok = state if state is not None else None
    xs = _token_shift(x, prev_tok)
    dx = xs - x
    xk = x + dx * p["maa_k"]
    xr = x + dx * p["maa_r"]
    k = jnp.square(jax.nn.relu(xk @ p["wk"]))
    out = jax.nn.sigmoid(xr @ p["wr_gate"]) * maybe_psum(k @ p["wv"], tp_axis)
    new_state = x[:, -1] if state is not None else None
    return out, new_state
