"""The control: the plain reference computed with float8 matrix products,
put in the program's place, is not correct under the cell's limits."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402
from bench import harness  # noqa: E402


def test_control_fails_a_limit(monkeypatch):
    cell = tiny.use_tiny_cell(monkeypatch, "tiny-danube")
    program = harness.Program(cell, require_chip=False)
    try:
        sound = program.start(2 ** 31 + 7)
        program.state = None
        ref = harness.reference_readings(program)
        control = harness.reference_readings(program, fp8=True)
    finally:
        program.close()
    ok, checks, _ = harness.judge(cell.cfg, sound, ref)
    assert ok, checks
    ok, checks, _ = harness.judge(cell.cfg, control, ref)
    assert not ok, checks
