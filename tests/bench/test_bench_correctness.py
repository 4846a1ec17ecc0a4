"""The harness's check: a sound run of each tiny cell is correct, on the
flush path the cells take and on 1F1B with weight stashing."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402


@pytest.mark.parametrize("config", ["tiny-danube", "tiny-danube-1f1b",
                                    "tiny-rwkv"])
def test_sound_run_is_correct(monkeypatch, config):
    tiny.use_tiny_cell(monkeypatch, config)
    result = tiny.run(seed=2 ** 31 + 5)
    assert result["correct"] is True, result["checks"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
