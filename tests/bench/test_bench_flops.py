"""Model FLOPs per token against hand counts, and the readers that use
them."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import archs, flops, harness  # noqa: E402


def config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def test_danube3_4b_hand_count():
    cfg = config("danube3-4b")
    # per layer: q, o 3840^2 each; k, v 3840*8*120 each; SwiGLU 3*3840*10240
    assert all(archs.load(cfg).matmul_params(cfg, i) == 154_828_800
               for i in range(cfg["num_hidden_layers"]))
    per_token = flops.train_flops_per_token(cfg, 4096)
    assert per_token == pytest.approx(4.84e9, rel=5e-3)
    assert per_token * 8 * 4096 == pytest.approx(1.583e14, rel=1e-3)


def test_rwkv6_hand_count():
    cfg = config("rwkv6-1.6b")
    arch = archs.load(cfg)
    assert sum(arch.matmul_params(cfg, i) for i in range(
        cfg["num_hidden_layers"])) == pytest.approx(0.444e9, rel=5e-3)
    per_token = flops.train_flops_per_token(cfg, 4096)
    assert per_token == pytest.approx(3.47e9, rel=5e-3)
    assert per_token * 8 * 4096 == pytest.approx(1.14e14, rel=5e-3)


@pytest.mark.parametrize("name,per_token", [("danube3-4b", 4830750720.0),
                                            ("rwkv6-1.6b", 3479175168.0)])
def test_shipped_configurations_count_as_before(name, per_token):
    """The values behind the ledger's step_mfu, exactly: the terms moved
    into bench/archs and are summed layer by layer."""
    assert flops.train_flops_per_token(config(name), 4096) == per_token


def test_pp2tp2_hand_count():
    cfg = dict(config("danube3-4b"), num_hidden_layers=8)
    assert flops.train_flops_per_token(cfg, 4096) == \
        pytest.approx(8.93e9, rel=5e-3)


def test_window_binds_only_past_its_length():
    assert flops.mean_context(4096, 4096) == pytest.approx(4097 / 2)
    assert flops.mean_context(16384, 4096) < 4096
    assert flops.mean_context(16384, None) == pytest.approx(16385 / 2)


def test_step_mfu_and_compile_readers():
    rec = {"steps": 10, "tokens_per_step": 32768, "window_s": 26.0,
           "chips": 1, "peak": {"bf16_flops_per_s": 197e12},
           "flops_per_token": 4.8307e9, "first_step_s": 47.9,
           "step_times": [2.6, 2.7, 2.5]}
    mfu = harness.metric_reader("step_mfu")(rec)
    assert mfu == pytest.approx(100 * 4.8307e9 * 32768 * 10 / 26 / 197e12)
    assert harness.metric_reader("compile_s")(rec) == pytest.approx(45.3)
    mem = {"memory": [{"peak_bytes_in_use": 8, "bytes_limit": 16},
                      {"peak_bytes_in_use": 12, "bytes_limit": 16}]}
    assert harness.metric_reader("hbm_peak_frac")(mem) == pytest.approx(75)
    # XLA's temporaries, reserved apart on a TPU, count too
    mem["memory"][0]["peak_bytes_reserved"] = 6
    assert harness.metric_reader("hbm_peak_frac")(mem) == pytest.approx(87.5)
    assert harness.metric_reader("hbm_peak_frac")({"memory": [{}]}) is None
