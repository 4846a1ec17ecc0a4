"""The plain reference, reached through the architecture modules
(bench/archs), gives the tiny cells' readings, and its float8 control's,
as they were before the modules existed, to the last bit
(tests/bench/parity.json, recorded with the reference that held each
mixer's blocks itself)."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import tiny  # noqa: E402
from bench import data, harness, reference, weights  # noqa: E402

SEED = 2 ** 31 + 5


@pytest.mark.parametrize("config", ["tiny-danube", "tiny-danube-1f1b",
                                    "tiny-rwkv"])
def test_reference_readings_unchanged(monkeypatch, config):
    with open(os.path.join(HERE, "parity.json")) as f:
        want = json.load(f)[config]
    cell = tiny.use_tiny_cell(monkeypatch, config)
    program = harness.Program(cell, require_chip=False)
    try:
        source = data.TrafficSource(SEED, cell.traffic,
                                    cell.cfg["vocab_size"])
        batches = [source.round_batch(i, cell.traffic["microbatches"],
                                      program.rows)
                   for i in range(harness.CHECK_STEPS)]
        got = {name: reference.Reference(cell.cfg, fp8=fp8).train(
                   weights.seed_key(SEED), program.shapes, batches)
               for name, fp8 in (("reference", False), ("control", True))}
    finally:
        program.close()
    assert got == want
