"""A tiny cell for the benchmark's CPU tests: the program's smoke presets
(configs/*.py smoke_spec) through the harness, at a size a test holds."""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402


def load(name):
    with open(os.path.join(HERE, name + ".json")) as f:
        return json.load(f)


def use_tiny_cell(monkeypatch, config: str) -> harness.Cell:
    """Point the harness at a tiny cell and keep the persistent compile
    cache off; returns the cell."""
    cell = harness.Cell("tiny.train", {"name": "tiny.train", "chips": 1},
                        load(config), load("tiny-train"),
                        harness.load_json(ROOT, "BENCHMARK.json"))
    monkeypatch.setattr(harness, "find_cell", lambda name: cell)
    argv = harness.launcher_argv
    monkeypatch.setattr(harness, "launcher_argv",
                        lambda c: argv(c) + ["--smoke"])
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    return cell


def run(seed: int, break_step=None) -> dict:
    ns = argparse.Namespace(workload="tiny.train", seed=seed, seconds=0.2,
                            trace=0)
    return harness.run(ns, t_start=time.perf_counter(), require_chip=False,
                       break_step=break_step)
