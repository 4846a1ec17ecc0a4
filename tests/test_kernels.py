"""Pallas kernels vs pure-jnp oracles (interpret mode, CPU).

Sweeps shapes, dtypes, GQA ratios, window sizes, block sizes, for the
flash kernel's gradients too; plus the model-level dispatch equivalence
(kernels on/off must not change the transformer output, nor, through
the flash kernel's backward, its gradients).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.key(0)


def _qkv(b, sq, sk, h, kv, dh, dt, seed=0):
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 3)
    return (jax.random.normal(ks[0], (b, sq, h, dh), dt),
            jax.random.normal(ks[1], (b, sk, kv, dh), dt),
            jax.random.normal(ks[2], (b, sk, kv, dh), dt))


FLASH_CASES = [
    # b, sq, sk, h, kv, dh, causal, window, dtype, bq, bk
    (2, 256, 256, 4, 2, 64, True, -1, jnp.float32, 128, 128),
    (1, 128, 128, 4, 4, 64, True, 32, jnp.float32, 64, 64),
    (2, 100, 100, 2, 1, 32, True, -1, jnp.bfloat16, 64, 64),
    (1, 256, 256, 8, 2, 128, False, -1, jnp.float32, 128, 128),
    (1, 64, 192, 2, 2, 16, True, 48, jnp.float32, 32, 64),
    (1, 192, 192, 2, 2, 64, True, 200, jnp.float32, 64, 64),  # w > bk span
    (2, 64, 64, 4, 1, 8, True, 1, jnp.float32, 32, 32),       # self only
]


@pytest.mark.parametrize(
    "b,sq,sk,h,kv,dh,causal,window,dt,bq,bk", FLASH_CASES)
def test_flash_attention_matches_oracle(b, sq, sk, h, kv, dh, causal,
                                        window, dt, bq, bk):
    q, k, v = _qkv(b, sq, sk, h, kv, dh, dt, seed=sq * h + dh)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=bq, block_k=bk)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    atol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=atol, rtol=1e-2)


@pytest.mark.parametrize(
    "b,sq,sk,h,kv,dh,causal,window,dt,bq,bk",
    FLASH_CASES + [(1, 256, 256, 8, 2, 120, True, -1, jnp.bfloat16, 128,
                    128)])                             # danube's d_head
def test_flash_attention_gradients_match_oracle(b, sq, sk, h, kv, dh, causal,
                                                window, dt, bq, bk):
    """dQ, dK, dV of the kernel's own backward against autodiff of the
    naive oracle."""
    q, k, v = _qkv(b, sq, sk, h, kv, dh, dt, seed=sq * h + dh)
    do = jax.random.normal(jax.random.key(dh), q.shape, dt)

    def kernel(q, k, v):
        return ops.flash_attention(q, k, v, causal=causal, window=window,
                                   block_q=bq, block_k=bk)

    def oracle(q, k, v):
        return ref.attention_ref(q, k, v, causal=causal, window=window)

    got = jax.vjp(kernel, q, k, v)[1](do)
    want = jax.vjp(oracle, q, k, v)[1](do)
    tol = 2e-2 if dt == jnp.bfloat16 else 1e-5
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        np.testing.assert_allclose(g, w, rtol=0, err_msg=name,
                                   atol=tol * max(1.0, np.abs(w).max()))


def test_flash_traced_window():
    q, k, v = _qkv(2, 128, 128, 4, 2, 32, jnp.float32, seed=7)
    for w in (-1, 16, 64):
        got = ops.flash_attention(q, k, v, window=jnp.int32(w),
                                  block_q=64, block_k=64)
        want = ref.attention_ref(q, k, v, window=w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-3)


def test_flash_matches_blockwise_jnp_twin():
    """The XLA twin used inside training graphs agrees with the kernel."""
    from repro.models.nn import _sdpa_flash_jnp
    q, k, v = _qkv(1, 256, 256, 4, 4, 64, jnp.float32, seed=11)
    got = ops.flash_attention(q, k, v, causal=True, window=-1)
    pos = jnp.arange(256)
    twin = _sdpa_flash_jnp(q, k, v, pos, pos, jnp.int32(-1), True, block=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(twin),
                               atol=2e-5, rtol=1e-3)


WKV_CASES = [
    # b, s, h, dh, chunk, dtype
    (2, 64, 2, 16, 16, jnp.float32),
    (1, 128, 4, 32, 32, jnp.float32),
    (2, 100, 2, 8, 32, jnp.float32),      # ragged tail padding
    (1, 64, 2, 64, 16, jnp.bfloat16),
    (1, 32, 1, 4, 32, jnp.float32),       # single chunk
]


@pytest.mark.parametrize("b,s,h,dh,chunk,dt", WKV_CASES)
def test_wkv6_matches_stepwise_oracle(b, s, h, dh, chunk, dt):
    ks = jax.random.split(jax.random.fold_in(KEY, s * h + dh), 5)
    r = jax.random.normal(ks[0], (b, s, h, dh), dt)
    k = jax.random.normal(ks[1], (b, s, h, dh), dt) * 0.5
    v = jax.random.normal(ks[2], (b, s, h, dh), dt)
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, h, dh))) * 0.5 + 0.49
    u = jax.random.normal(ks[4], (h, dh)) * 0.1
    y, s_last = ops.wkv6(r, k, v, w.astype(dt), u, chunk=chunk)
    yr, sr = ref.wkv6_ref(r, k, v, w.astype(dt), u)
    atol = 5e-2 if dt == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=atol, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(s_last), np.asarray(sr),
                               atol=atol, rtol=1e-2)


def test_wkv6_matches_chunked_jnp_twin():
    from repro.models.nn import wkv6_chunked
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (1, 96, 2, 16))
    k = jax.random.normal(ks[1], (1, 96, 2, 16)) * 0.5
    v = jax.random.normal(ks[2], (1, 96, 2, 16))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (1, 96, 2, 16))) * 0.5 + 0.49
    u = jax.random.normal(ks[4], (2, 16)) * 0.1
    y, s_last = ops.wkv6(r, k, v, w, u, chunk=32)
    yt, st = wkv6_chunked(r, k, v, w, u, chunk=24)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yt),
                               atol=1e-3, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(s_last), np.asarray(st),
                               atol=1e-3, rtol=1e-2)


MAMBA_CASES = [
    # b, s, ci, n, chunk, ci_block
    (2, 64, 32, 8, 16, 16),
    (1, 128, 64, 16, 32, 32),
    (2, 100, 48, 4, 32, 16),        # ragged tail + ci_block fallback
    (1, 48, 512, 16, 16, 256),
]


@pytest.mark.parametrize("b,s,ci,n,chunk,cib", MAMBA_CASES)
def test_mamba_scan_matches_stepwise_oracle(b, s, ci, n, chunk, cib):
    ks = jax.random.split(jax.random.fold_in(KEY, s * ci + n), 6)
    u = jax.random.normal(ks[0], (b, s, ci))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, ci))) * 0.3
    A = -jnp.exp(jax.random.normal(ks[2], (ci, n)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    D = jax.random.normal(ks[5], (ci,))
    y, h = ops.mamba_scan(u, dt, A, B, C, D, chunk=chunk, ci_block=cib)
    yr, hr = ref.mamba_scan_ref(u, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               atol=2e-4, rtol=1e-3)


def test_mamba_scan_matches_chunked_jnp_twin():
    from repro.models.nn import selective_scan
    ks = jax.random.split(KEY, 6)
    u = jax.random.normal(ks[0], (1, 96, 64))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 96, 64))) * 0.3
    A = -jnp.exp(jax.random.normal(ks[2], (64, 8)) * 0.3)
    B = jax.random.normal(ks[3], (1, 96, 8))
    C = jax.random.normal(ks[4], (1, 96, 8))
    D = jax.random.normal(ks[5], (64,))
    y, h = ops.mamba_scan(u, dt, A, B, C, D, chunk=32, ci_block=64)
    yt, ht = selective_scan(u, dt, A, B, C, D, chunk=24)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yt),
                               atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(h), np.asarray(ht),
                               atol=2e-4, rtol=1e-3)


PAGED_CASES = [
    # b, h, kv, dh, page, n_pages, window
    (2, 4, 2, 64, 16, 8, -1),
    (3, 4, 4, 32, 16, 4, -1),        # MHA (group size 1)
    (2, 8, 2, 64, 64, 4, -1),        # big pages, 4:1 GQA
    (2, 4, 1, 32, 16, 8, -1),        # MQA
    (2, 4, 2, 64, 16, 8, 20),        # windowed: dead-page skipping
    (1, 2, 2, 16, 64, 2, 48),        # window inside one page
]


def _paged_case(b, h, kv, dh, page, n_pages, seed):
    """Random pool + permuted tables + ragged per-row lengths."""
    rng = np.random.default_rng(seed)
    n_pool = b * n_pages + 3                     # spare pages stay garbage
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 3)
    q = jax.random.normal(ks[0], (b, h, dh), jnp.float32)
    k_pages = jax.random.normal(ks[1], (n_pool, page, kv, dh), jnp.float32)
    v_pages = jax.random.normal(ks[2], (n_pool, page, kv, dh), jnp.float32)
    lengths = rng.integers(1, n_pages * page + 1, b).astype(np.int32)
    perm = rng.permutation(n_pool)
    tables = np.full((b, n_pages), -1, np.int32)
    used = 0
    for r in range(b):
        need = -(-int(lengths[r]) // page)
        tables[r, :need] = perm[used:used + need]
        used += need
    return q, k_pages, v_pages, jnp.asarray(tables), jnp.asarray(lengths)


@pytest.mark.parametrize("b,h,kv,dh,page,n_pages,window", PAGED_CASES)
def test_paged_attention_matches_ref(b, h, kv, dh, page, n_pages, window):
    q, kp, vp, tables, lengths = _paged_case(
        b, h, kv, dh, page, n_pages, seed=b * h + page + n_pages)
    got = ops.paged_attention(q, kp, vp, tables, lengths, window=window)
    want = ref.paged_attention_ref(q, kp, vp, tables, lengths,
                                   window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-3)


def test_paged_ref_matches_dense_gather():
    """The paged oracle equals dense attention over the gathered slab."""
    b, h, kv, dh, page, n_pages = 2, 4, 2, 32, 16, 4
    q, kp, vp, tables, lengths = _paged_case(b, h, kv, dh, page, n_pages,
                                             seed=5)
    got = ref.paged_attention_ref(q, kp, vp, tables, lengths)
    outs = []
    for r in range(b):
        ln = int(lengths[r])
        pages = [int(p) for p in tables[r] if p >= 0]
        kd = jnp.concatenate([kp[p] for p in pages], axis=0)[:ln]
        vd = jnp.concatenate([vp[p] for p in pages], axis=0)[:ln]
        # one query at position ln-1 attending over ln dense keys
        o = ref.attention_ref(q[r][None, None], kd[None], vd[None],
                              causal=False)
        outs.append(o[0, 0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.stack(outs)),
                               atol=2e-5, rtol=1e-3)


def test_paged_attention_garbage_pages_ignored():
    """NaN in unreferenced / beyond-length pool pages must not leak."""
    b, h, kv, dh, page, n_pages = 2, 4, 2, 32, 16, 4
    q, kp, vp, tables, lengths = _paged_case(b, h, kv, dh, page, n_pages,
                                             seed=9)
    used = set(int(p) for p in np.asarray(tables).ravel() if p >= 0)
    spare = [p for p in range(kp.shape[0]) if p not in used]
    kp = kp.at[jnp.asarray(spare)].set(jnp.nan)
    vp = vp.at[jnp.asarray(spare)].set(jnp.nan)
    got = ops.paged_attention(q, kp, vp, tables, lengths)
    want = ref.paged_attention_ref(q, kp, vp, tables, lengths)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-3)


@pytest.mark.parametrize("arch", ["dense", "rwkv", "hybrid"])
def test_model_dispatch_equivalence(arch):
    """Kernels on/off must not change transformer outputs."""
    import sys as _sys
    import os as _os
    _sys.path.insert(0, _os.path.dirname(__file__))
    from spmd_pipeline_check import build_tiny_spec
    from repro.models.init import init_params
    from repro.models.stage import full_transformer, make_statics
    from repro.parallel.mesh import ParallelismPlan

    spec = build_tiny_spec(arch)
    plan = ParallelismPlan(pp=1, tp=1, microbatches=1, remat=False)
    params, _ = init_params(spec, plan, jax.random.key(3), jnp.float32)
    st = make_statics(spec, plan, tokens_per_mb=64)
    x = jax.random.normal(jax.random.key(4), (2, 16, spec.d_model))
    pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
    try:
        ops.enable(False)
        y0, _ = full_transformer(params, x, st, positions=pos)
        ops.enable(True)
        y1, _ = full_transformer(params, x, st, positions=pos)
    finally:
        ops.enable(None)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               atol=2e-4, rtol=1e-3)


def test_model_dispatch_equivalence_gradients():
    """Past the flash threshold the training path's attention is the
    kernel (interpret mode here): jax.grad through full_transformer agrees
    with the jnp twin's, sliding-window layers included."""
    import sys as _sys
    import os as _os
    _sys.path.insert(0, _os.path.dirname(__file__))
    from spmd_pipeline_check import build_tiny_spec
    from repro.models import nn
    from repro.models.init import init_params
    from repro.models.stage import full_transformer, make_statics
    from repro.parallel.mesh import ParallelismPlan

    s = 2304
    assert s * s > nn._FLASH_THRESHOLD
    spec = build_tiny_spec("dense")
    plan = ParallelismPlan(pp=1, tp=1, microbatches=1, remat=False)
    params, _ = init_params(spec, plan, jax.random.key(3), jnp.float32)
    st = make_statics(spec, plan, tokens_per_mb=s)
    x = jax.random.normal(jax.random.key(4), (1, s, spec.d_model))
    dy = jax.random.normal(jax.random.key(5), (1, s, spec.d_model))
    pos = jnp.arange(s)[None]

    # differentiate the float leaves (the layer windows are int32)
    leaves, treedef = jax.tree.flatten(params)
    trained = [jnp.issubdtype(a.dtype, jnp.floating) for a in leaves]

    def loss(weights, x):
        it = iter(weights)
        full = [next(it) if t else a for a, t in zip(leaves, trained)]
        y, _ = full_transformer(jax.tree.unflatten(treedef, full), x, st,
                                positions=pos)
        return jnp.sum(y * dy)

    def grad():    # traced anew, under the dispatch rule of the moment
        return jax.jit(jax.grad(loss, argnums=(0, 1)))(weights, x)

    weights = [a for a, t in zip(leaves, trained) if t]
    try:
        assert ops.use_flash()
        g_kernel = grad()
        ops.enable(False)
        g_twin = grad()
    finally:
        ops.enable(None)
    names = [jax.tree_util.keystr(p) for (p, _), t in zip(
        jax.tree_util.tree_leaves_with_path(params), trained) if t] + ["x"]
    flat_k, flat_t = jax.tree.leaves(g_kernel), jax.tree.leaves(g_twin)
    assert len(flat_k) == len(flat_t) == len(names)
    for name, a, b in zip(names, flat_k, flat_t):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(
            a, b, rtol=0, atol=1e-4 * max(1.0, np.abs(b).max()),
            err_msg=name)
