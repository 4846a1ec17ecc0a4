"""SPMD pipeline == sequential reference, bit-level (fp32).

Each case runs in a subprocess so it can set
--xla_force_host_platform_device_count before jax initializes (the main
pytest process keeps 1 device per the task spec).

A small fast subset runs by default; the full matrix (every stash-mode /
schedule / arch combination) carries the ``slow`` marker — run it with
``pytest -m slow`` (or ``-m ''``).
"""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")

# data, pp, tp, mode, arch, zero1, schedule, virtual_stages, steps
FAST_MATRIX = [
    (1, 2, 1, "stash", "dense", 0, "auto", 1, 1),
    (2, 2, 1, "flush", "dense", 0, "auto", 1, 1),      # PipeDream-flush
    (1, 2, 1, "flush", "dense", 0, "interleaved", 2, 2),  # virtual stages
    # per-chunk version rings, per-microbatch updates (vs the native
    # async sequential oracle, storage order)
    (1, 2, 1, "stash", "dense", 0, "interleaved_async", 2, 1),
    (1, 2, 2, "stash", "dense", 0, "auto", 1, 2),   # TP within a stage
]

SLOW_MATRIX = [
    (2, 2, 2, "stash", "dense", 1, "auto", 1, 1),   # replication + TP + ZeRO-1
    (1, 4, 1, "stash", "dense", 0, "auto", 1, 1),   # deeper pipe, V=7 ring
    (1, 2, 1, "vertical", "dense", 0, "auto", 1, 1),  # vertical sync
    (1, 2, 1, "2bw", "dense", 0, "auto", 1, 1),     # 2-version accumulate
    (2, 2, 2, "stash", "moe", 1, "auto", 1, 1),     # expert-parallel stage
    (1, 2, 1, "stash", "rwkv", 0, "auto", 1, 1),    # attention-free stage
    (1, 2, 2, "stash", "hybrid", 0, "auto", 1, 1),  # mamba+moe+attn mixed
    (1, 2, 2, "flush", "dense", 0, "interleaved", 2, 1),   # interleave + TP
    (1, 2, 1, "flush", "dense8", 0, "interleaved", 4, 1),  # v=4, 8 chunks
    (1, 4, 1, "flush", "dense8", 0, "interleaved", 2, 1),  # S=4, v=2
    # async interleaved: ring rotation across rounds, v=4, TP, ZeRO-1
    (1, 4, 1, "stash", "dense8", 0, "interleaved_async", 2, 2),
    (1, 2, 1, "stash", "dense8", 0, "interleaved_async", 4, 1),
    (1, 2, 2, "stash", "dense", 0, "interleaved_async", 2, 1),
    (2, 2, 1, "stash", "dense", 1, "interleaved_async", 2, 1),
]


def _run_case(case):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "spmd_pipeline_check.py"),
         *[str(a) for a in case]],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nERR:\n{out.stderr}"
    assert "MATCH" in out.stdout


@pytest.mark.parametrize("case", FAST_MATRIX, ids=lambda c: "-".join(
    str(x) for x in c))
def test_pipeline_matches_reference(case):
    _run_case(case)


@pytest.mark.slow
@pytest.mark.parametrize("case", SLOW_MATRIX, ids=lambda c: "-".join(
    str(x) for x in c))
def test_pipeline_matches_reference_full(case):
    _run_case(case)
