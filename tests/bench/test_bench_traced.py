"""A traced run's result line carries every per-layer metric of the cell,
the seven phase readings with them: the harness lowers the step the
window ran for its HLO text and reduces the trace by phase and scope.
A CPU has no device plane in its profile, so the trace of a TPU v5 lite
window step (bench/testdata) stands in for the window's."""
import argparse
import gzip
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402
from bench import harness, trace  # noqa: E402

PHASE_METRICS = {"fwd_ms", "bwd_ms", "head_ms", "optimizer_ms", "mixer_ms",
                 "input_idle_ms", "dispatch_idle_ms"}


def recorded_trace(trace_dir):
    path = os.path.join(ROOT, "bench", "testdata", "danube3-4b-step.json.gz")
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    return ({int(k): [tuple(e) for e in v]
             for k, v in rec["devices"].items()},
            [tuple(h) for h in rec["host"]])


def test_traced_run_reports_the_phase_readings(monkeypatch):
    cell = tiny.use_tiny_cell(monkeypatch, "tiny-danube")
    monkeypatch.setattr(trace, "load", recorded_trace)
    ns = argparse.Namespace(workload="tiny.train", seed=2 ** 31 + 13,
                            seconds=0.2, trace=1)
    result = harness.run(ns, t_start=time.perf_counter(),
                         require_chip=False)
    assert result["correct"] is True, result["checks"]
    got = result["metrics"]
    assert PHASE_METRICS <= {m["name"] for m in cell.per_layer()}
    assert PHASE_METRICS <= set(got)
    assert all(got[m]["unit"] == "ms" and got[m]["value"] >= 0
               for m in PHASE_METRICS)
    assert result["device"]["busy_s"] > 0
    assert result["breakdown"]["device_ops"]
