"""Compile the Pallas kernels for a described TPU v5e at real widths.

Interpret mode (the rest of the suite) cannot see Mosaic's tiling rules;
these compiles can.  No chip is needed: the TPU compiler is handed a
described v5e:2x2 topology and compiles for its first device.  Nothing
runs, so these tests say nothing about results or times — the
interpret-mode oracle tests in test_kernels.py cover the numbers.

The topology is described inside a fixture (never at import), so the
file collects the same tests on every pytest-xdist worker.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_tpu(one_chip):
    """jit-compile ``fn`` for one described v5e chip with the Mosaic
    lowering forced and the persistent compile cache off (an entry
    written for a described chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    interp_was = ops.set_interpret(False)

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile()

    yield compile_
    ops.set_interpret(interp_was)
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


FLASH_WIDTHS = pytest.mark.parametrize("h,kv,dh,window", [
    (40, 8, 128, -1),      # qwen3-14b
    (32, 8, 120, 4096),    # h2o-danube3-4b (Dh not a multiple of 128)
    (8, 4, 256, 1024),     # gemma3-4b's local layers (Dh 256)
], ids=["qwen3-14b", "danube3-4b", "gemma3-4b"])


@FLASH_WIDTHS
def test_flash_attention_compiles_for_v5e(compile_tpu, h, kv, dh, window):
    s = 4096
    fn = functools.partial(ops.flash_attention, causal=True, window=window)
    c = compile_tpu(fn, ((1, s, h, dh), BF16), ((1, s, kv, dh), BF16),
                    ((1, s, kv, dh), BF16))
    assert "tpu_custom_call" in c.as_text()


@FLASH_WIDTHS
def test_flash_attention_backward_compiles_for_v5e(compile_tpu, h, kv, dh,
                                                   window):
    """The training path's vjp: the forward that saves the log-sum-exp and
    both backward kernels, each under its own name."""
    s = 4096

    def fwd_bwd(q, k, v, do):
        out, vjp = jax.vjp(functools.partial(
            ops.flash_attention, causal=True, window=window), q, k, v)
        return out, vjp(do)

    c = compile_tpu(fwd_bwd, ((1, s, h, dh), BF16), ((1, s, kv, dh), BF16),
                    ((1, s, kv, dh), BF16), ((1, s, h, dh), BF16))
    calls = [line for line in c.as_text().splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    for name in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert sum(f"%{name}" in line for line in calls) == 1, name


@pytest.mark.parametrize("q_len,kv_dtype", [
    (1, BF16), (4, BF16), (1, jnp.int8)], ids=["decode", "verify", "int8"])
def test_paged_attention_compiles_for_v5e(compile_tpu, q_len, kv_dtype):
    b, h, kv, dh, page, n_pages = 8, 40, 8, 128, 16, 64   # qwen3-14b
    pool = b * n_pages
    shapes = [((b, q_len, h, dh), BF16),
              ((pool, page, kv, dh), kv_dtype),
              ((pool, page, kv, dh), kv_dtype),
              ((b, n_pages), I32), ((b,), I32)]
    if kv_dtype == jnp.int8:
        shapes += [((pool, kv), F32), ((pool, kv), F32)]

        def fn(q, kp, vp, tab, lens, ks, vs):
            return ops.paged_attention(q, kp, vp, tab, lens,
                                       k_scale=ks, v_scale=vs)
    else:
        fn = ops.paged_attention
    c = compile_tpu(fn, *shapes)
    assert "tpu_custom_call" in c.as_text()


def test_wkv6_compiles_for_v5e(compile_tpu):
    b, s, h, dh = 1, 4096, 32, 64                          # rwkv6-1.6b
    c = compile_tpu(ops.wkv6, *[((b, s, h, dh), BF16)] * 4, ((h, dh), F32))
    assert "tpu_custom_call" in c.as_text()


def test_mamba_scan_compiles_for_v5e(compile_tpu):
    b, s, ci, n = 1, 4096, 8192, 16                        # jamba-v0.1
    c = compile_tpu(ops.mamba_scan, ((b, s, ci), BF16), ((b, s, ci), BF16),
                    ((ci, n), F32), ((b, s, n), BF16), ((b, s, n), BF16),
                    ((ci,), F32))
    assert "tpu_custom_call" in c.as_text()
