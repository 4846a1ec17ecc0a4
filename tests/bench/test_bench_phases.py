"""Device time by phase and mixer, and idle time by program span
(bench/phases.py), on hand-built intervals and on recorded chip steps."""
import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, phases, trace  # noqa: E402

J = "jit(train_step)/while/body/closed_call"
HOST = [("bench.step", 0, 100),
        ("train.round", 0, 100), ("train.load", 0, 20),
        ("loader.draw", 2, 8), ("loader.place", 8, 18),
        ("train.dispatch", 20, 30), ("train.wait", 30, 95),
        ("train.readback", 95, 100)]
DEVICES = {0: [
    ("fusion.1", 20, 40, f"{J}/fwd/checkpoint/attention/dot_general"),
    ("fusion.2", 40, 50, f"{J}/head/transpose(jvp())/dot_general"),
    ("fusion.3", 50, 70,
     f"{J}/bwd/transpose(jvp(bwd))/jvp()/checkpoint/attention/exp"),
    ("fusion.4", 70, 80, f"{J}/bwd/jvp(wkv)/log"),
    ("copy.5", 80, 85, ""),
    ("fusion.6", 85, 90, "jit(train_step)/optimizer/sub"),
    ("fusion.7", 92, 96, "jit(train_step)/vmap(embed)/add")]}


def test_phase_strips_wrappers_and_takes_the_first():
    assert phases.scopes("a/transpose(jvp(bwd))/vmap(x)") == ["a", "bwd",
                                                              "x"]
    assert phases.phase_of(f"{J}/bwd/jvp(fwd)/attention/dot") == "bwd"
    assert phases.phase_of("jit(f)/transpose(jvp(head))/mul") == "head"
    assert phases.phase_of("jit(f)/while/body/add") == phases.OTHER
    assert phases.phase_of("") == phases.OTHER
    # a scope name inside another word is no scope
    assert phases.phase_of("jit(f)/forward/bwd_x") == phases.OTHER
    assert phases.in_mixer(f"{J}/bwd/transpose(jvp(attention))/dot")
    assert not phases.in_mixer(f"{J}/fwd/mlp/dot")


def test_reduce_hand_built_intervals():
    r = phases.reduce(DEVICES, HOST)
    ns = 1e-9
    assert r["window_s"] == pytest.approx(100 * ns)
    assert r["phases"] == {"embed": pytest.approx(4 * ns),
                           "fwd": pytest.approx(20 * ns),
                           "head": pytest.approx(10 * ns),
                           "bwd": pytest.approx(30 * ns),
                           "optimizer": pytest.approx(5 * ns),
                           "other": pytest.approx(5 * ns)}
    # the mixer cuts across fwd and bwd, attention and wkv alike
    assert r["mixer_s"] == pytest.approx(50 * ns)
    assert r["busy_s"] == pytest.approx(74 * ns)
    assert r["idle_s"] == pytest.approx(26 * ns)
    assert sum(r["phases"].values()) + r["idle_s"] == \
        pytest.approx(r["window_s"])
    # each piece of an idle interval goes to the innermost program span
    # over it: [0, 20) splits among train.load and the loader's two
    # spans nested in it; bench.step is no program span
    got = r["idle_by_span"]
    assert got == {"train.load": pytest.approx(4 * ns),
                   "loader.draw": pytest.approx(6 * ns),
                   "loader.place": pytest.approx(10 * ns),
                   "train.wait": pytest.approx(2 * ns),
                   "train.readback": pytest.approx(4 * ns)}
    assert sum(got.values()) == pytest.approx(r["idle_s"])


def test_idle_outside_program_spans_is_unattributed():
    host = [("bench.step", 0, 100), ("bench.loader", 0, 50),
            ("train.round", 50, 100)]
    devices = {0: [("fusion.1", 60, 100, f"{J}/fwd/add")],
               1: [("fusion.1", 0, 100, f"{J}/fwd/add")]}
    r = phases.reduce(devices, host)
    assert r["devices"][0]["idle_by_span"] == {
        phases.UNATTRIBUTED: pytest.approx(50e-9),
        "train.round": pytest.approx(10e-9)}
    assert r["devices"][1]["idle_by_span"] == {}
    # averaged over devices
    assert r["idle_by_span"][phases.UNATTRIBUTED] == pytest.approx(25e-9)
    assert r["phases"]["fwd"] == pytest.approx(70e-9)


def test_per_step_ms():
    r = phases.reduce(DEVICES, HOST)
    got = phases.per_step_ms(r, 2)
    assert got["fwd_ms"] == pytest.approx(1e3 * 20e-9 / 2)
    assert got["mixer_ms"] == pytest.approx(1e3 * 50e-9 / 2)
    assert got["input_idle_ms"] == pytest.approx(1e3 * 20e-9 / 2)
    assert got["dispatch_idle_ms"] == 0.0
    assert phases.per_step_ms(r, 0) == {}


def test_hlo_op_names():
    text = ('%fusion.12 = bf16[8]{0} fusion(%p), kind=kLoop, '
            'calls=%fused, metadata={op_name="jit(f)/fwd/state[\\\'w\\\']" '
            'source_file="x.py"}\n'
            '  ROOT %copy.3 = f32[] copy(%a), metadata={op_name="jit(f)/'
            'bwd/mul"}\n'
            '  %constant.1 = f32[] constant(1)\n'
            # XLA's own instructions, without metadata, do their first
            # operand's work, through chains
            '  %copy-start.4 = (f32[8]{0:T(8,128)}, u32[]) copy-start('
            '%fusion.12)\n'
            '  %copy-done.4 = f32[8]{0:T(8,128)} copy-done(%copy-start.4)\n'
            '  %reshape.5 = f32[2,4]{1,0} reshape(%constant.1)\n')
    assert phases.hlo_op_names(text) == {
        "fusion.12": "jit(f)/fwd/state[\\'w\\']", "copy.3": "jit(f)/bwd/mul",
        "copy-start.4": "jit(f)/fwd/state[\\'w\\']",
        "copy-done.4": "jit(f)/fwd/state[\\'w\\']"}


def test_names_are_the_programs():
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import obs
    assert phases.PHASES == obs.PHASES
    assert phases.MIXERS == obs.MIXERS
    assert set(phases.INPUT_SPANS) == {obs.TRAIN_LOAD, *obs.LOADER_SPANS}
    assert phases.DISPATCH_SPAN == obs.TRAIN_DISPATCH
    assert all(phases.PROGRAM_SPAN.match(n)
               for n in obs.TRAIN_SPANS + obs.LOADER_SPANS)


def recorded(name):
    with gzip.open(os.path.join(ROOT, "bench", "testdata", name), "rt") as f:
        return json.load(f)


def test_recorded_named_chip_step():
    """One window step of danube3-4b.train-4k on a TPU v5 lite, with the
    op_names of the compiled step (bench/testdata)."""
    rec = recorded("danube3-4b-step-named.json.gz")
    names = rec["op_names"]
    devices = phases.with_op_names(
        {int(k): [tuple(e) for e in v] for k, v in rec["devices"].items()},
        names)
    host = [tuple(h) for h in rec["host"]]
    r = phases.reduce(devices, host)
    assert r["window_s"] == pytest.approx(2.35556733)
    busy, idle = r["busy_s"], r["idle_s"]
    # the phases cover the busy time; phases, other and idle the window
    assert sum(r["phases"][p] for p in phases.PHASES) >= 0.95 * busy
    assert sum(r["phases"].values()) + idle == pytest.approx(r["window_s"],
                                                             rel=1e-2)
    assert all(r["phases"][p] > 0 for p in phases.PHASES)
    # the mixer is attention alone in danube
    mixer = {i for i, _, _, o in devices[0] if phases.in_mixer(o)}
    assert mixer and all("attention" in phases.scopes(names[i])
                         for i in mixer)
    assert r["mixer_s"] > 0
    # the program's spans hold most of the idle time
    spans = r["idle_by_span"]
    held = sum(t for k, t in spans.items() if k != phases.UNATTRIBUTED)
    assert held >= 0.8 * idle
    got = phases.per_step_ms(r, 1)
    assert set(got) == {"fwd_ms", "bwd_ms", "head_ms", "optimizer_ms",
                        "mixer_ms", "input_idle_ms", "dispatch_idle_ms"}
    assert all(v > 0 for v in got.values())
    text = phases.table(r, 1)
    assert "device 0: embed " in text and "idle by span: " in text
    # bench/trace.py reduces the same step to the keys it always gave
    old = trace.reduce({d: [(i, s, e) for i, s, e, _ in ops]
                        for d, ops in devices.items()}, host)
    assert set(old) == {"window_s", "devices", "device_ops", "idle_gaps"}
    assert old["devices"][0]["busy_s"] == pytest.approx(busy)


def test_existing_readers_read_the_same_on_the_existing_step():
    rec = recorded("danube3-4b-step.json.gz")
    devices = {int(k): [tuple(e) for e in v]
               for k, v in rec["devices"].items()}
    r = trace.reduce(devices, [tuple(e) for e in rec["host"]])
    record = {"trace": r, "steps": 1, "tokens_per_step": 32768,
              "window_s": r["window_s"], "chips": 1,
              "peak": {"bf16_flops_per_s": 197e12},
              "flops_per_token": 1e9, "first_step_s": 5.0,
              "step_times": [2.0],
              "memory": [{"peak_bytes_in_use": 1, "bytes_limit": 4}]}
    read = {m: harness.metric_reader(m)(record) for m in
            ("device_idle_share", "step_mfu", "hbm_peak_frac", "compile_s")}
    assert read == {
        "device_idle_share": pytest.approx(0.2726646615281214),
        "step_mfu": pytest.approx(100 * 1e9 * 32768 / 2.633775114 / 197e12),
        "hbm_peak_frac": pytest.approx(25.0),
        "compile_s": pytest.approx(3.0)}
