"""The training launcher: device mesh, depth cuts, compile cache, and the
explicit Pallas interpret choice."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.launch import train
from repro.launch.mesh import make_device_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_device_mesh_covers_every_device():
    """data × pp × tp equals the device count for every plan that fits."""
    code = textwrap.dedent("""
        import jax
        from repro.launch.mesh import make_device_mesh
        from repro.parallel.mesh import split_model_axis
        n = len(jax.devices())
        for pp, tp in [(1, 1), (2, 1), (2, 2), (4, 2), (1, 8)]:
            mesh = split_model_axis(make_device_mesh(pp=pp, tp=tp), pp, tp)
            shape = dict(mesh.shape)
            assert shape["data"] * shape["stage"] * shape["tensor"] == n
            assert (shape["stage"], shape["tensor"]) == (pp, tp)
            ids = sorted(d.id for d in mesh.devices.flat)
            assert ids == sorted(d.id for d in jax.devices()), ids
        try:
            make_device_mesh(pp=3)
        except ValueError:
            print("OK", n)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["OK", "8"]


def test_device_mesh_takes_given_devices():
    devs = jax.devices()[:1]
    mesh = make_device_mesh(pp=1, tp=1, devices=devs)
    assert list(mesh.devices.flat) == devs
    with pytest.raises(ValueError):
        make_device_mesh(pp=2, tp=1, devices=devs)


def test_build_prints_cuts_and_uses_present_devices(capsys):
    args = train.parser().parse_args(
        ["--arch", "h2o-danube3-4b", "--smoke", "--pp", "1", "--layers",
         "2", "--global-batch", "4", "--microbatches", "4"])
    spec, bundle = train.build(args)
    out = capsys.readouterr().out
    assert "cuts: layers 4 -> 2, global_batch 8 -> 4" in out
    assert spec.n_layers == 2 and spec.d_model == 64      # widths kept
    assert bundle.mesh.devices.size == len(jax.devices())
    assert bundle.plan.microbatches == 4
    assert "layers=2" in train.describe(spec, bundle)


def test_train_main_runs_and_reports_restarts(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    log = tmp_path / "log.json"
    losses = train.main(["--arch", "h2o-danube3-4b", "--smoke", "--pp", "1",
                         "--layers", "2", "--steps", "2",
                         "--log", str(log)])
    assert len(losses) == 2 and all(jnp.isfinite(jnp.asarray(losses)))
    assert '"restarts": 0' in log.read_text()


def test_compile_cache_dir_follows_env_else_checkout():
    assert train.compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/elsewhere/cc"}) == "/elsewhere/cc"
    path = train.compile_cache_dir({})
    assert path == os.path.join(ROOT, ".jax_cache")
    assert train.compile_cache_dir({}) == path          # fixed, not per run


def test_enable_compile_cache_sets_only_the_checkout_path(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cc")
    assert train.enable_compile_cache() == "/elsewhere/cc"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert train.enable_compile_cache() == os.path.join(ROOT,
                                                            ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_kernel_off_tpu_needs_an_explicit_interpret_choice():
    q = jnp.zeros((1, 8, 2, 8), jnp.float32)
    old = ops.set_interpret(None)
    try:
        assert jax.default_backend() != "tpu"
        with pytest.raises(RuntimeError, match="set_interpret"):
            ops.flash_attention(q, q, q)
        with pytest.raises(RuntimeError, match="set_interpret"):
            ops.interpret_mode()
        ops.set_interpret(True)
        assert ops.flash_attention(q, q, q).shape == q.shape
    finally:
        ops.set_interpret(old)
