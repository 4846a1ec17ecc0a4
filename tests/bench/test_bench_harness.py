"""The entry fails, and prints no result, without what a cell needs."""
import os
import shutil
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

ARGS = ["--workload", "danube3-4b.train-4k", "--seed", "3", "--seconds",
        "1", "--trace", "0"]


def entry(cwd, env=None):
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          capture_output=True, text=True, timeout=240,
                          env=dict(os.environ, JAX_PLATFORMS="cpu",
                                   **(env or {})))


def test_no_tpu_fails_without_a_result():
    proc = entry(ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_fail_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = entry(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""


def fake_jax(kind="TPU v5 lite", n=1, platform="tpu"):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    return types.SimpleNamespace(devices=lambda: [dev] * n)


def test_device_checks():
    assert harness.check_devices(fake_jax(), 1)["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.CellFailed, match="no peaks"):
        harness.check_devices(fake_jax(kind="TPU v99"), 1)
    with pytest.raises(harness.CellFailed, match="needs 4 chips"):
        harness.check_devices(fake_jax(n=1), 4)
    with pytest.raises(harness.CellFailed, match="no TPU"):
        harness.check_devices(fake_jax(platform="cpu"), 1)


def test_unknown_workload():
    with pytest.raises(harness.CellFailed):
        harness.find_cell("no-such-cell")


def test_program_must_run_the_configuration():
    cell = harness.find_cell("danube3-4b.train-4k")
    spec = types.SimpleNamespace(d_model=3840, n_layers=4, vocab=32000,
                                 d_ff=10240, n_heads=32, n_kv=8, d_head=120)
    plan = types.SimpleNamespace(stash_mode="flush", remat=True, pp=1, tp=1)
    opt = types.SimpleNamespace(lr=3e-4, b1=0.9, b2=0.95, eps=1e-8)
    bundle = types.SimpleNamespace(sched=types.SimpleNamespace(name="gpipe"),
                                   plan=plan, optimizer=opt)
    harness.check_build(cell, spec, bundle)
    plan.stash_mode = "2bw"
    with pytest.raises(harness.CellFailed, match="stash_mode"):
        harness.check_build(cell, spec, bundle)


def test_rwkv_build_check_reads_its_module():
    cell = harness.find_cell("rwkv6-1.6b.train-4k")
    spec = types.SimpleNamespace(d_model=2048, n_layers=8, vocab=65536,
                                 d_ff=7168,
                                 rwkv=types.SimpleNamespace(head_dim=64))
    plan = types.SimpleNamespace(stash_mode="flush", remat=True, pp=1, tp=1)
    opt = types.SimpleNamespace(lr=5e-4, b1=0.9, b2=0.95, eps=1e-8)
    bundle = types.SimpleNamespace(sched=types.SimpleNamespace(name="gpipe"),
                                   plan=plan, optimizer=opt)
    harness.check_build(cell, spec, bundle)
    spec.rwkv.head_dim = 32
    with pytest.raises(harness.CellFailed, match="d_head"):
        harness.check_build(cell, spec, bundle)


def test_launcher_argv_appends_the_configurations_own():
    cell = harness.find_cell("danube3-4b.train-4k")
    argv = ["--arch", "h2o-danube3-4b", "--layers", "4", "--pp", "1",
            "--tp", "1", "--schedule", "gpipe", "--microbatches", "8",
            "--global-batch", "8", "--seq-len", "4096", "--dtype",
            "bfloat16"]
    assert harness.launcher_argv(cell) == argv
    cell.cfg = dict(cell.cfg, launcher_args=["--vocab-shard", "8"])
    assert harness.launcher_argv(cell) == argv + ["--vocab-shard", "8"]
