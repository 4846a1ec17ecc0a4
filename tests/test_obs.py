"""Observability (ISSUE 10): registry/trace/reconcile units, the
batcher's no-completions None-not-NaN regression, bucketed-trace
agreement with the real engine's ``pick_bucket`` choices, and the
registry-driven replan flip — telemetry collected through
``Registry.timer``, never hand-injected.

The analytic exactness of the trace synthesis (span counts == table
non-bubble cells, reconcile ratio == 1.0 on a modeled clock) is the CI
gate's job (scripts/obs_smoke.py); this file covers the units and the
real-engine / real-driver integration on the single CPU device."""
import collections
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import profiler as prof
from repro.core.schedule import (F_MB, SCHEDULES, pick_bucket,
                                 weighted_round_time)
from repro.launch.mesh import make_host_mesh
from repro.models import spec as spec_lib
from repro.obs import (Observability, Registry, reconcile, stage_seconds)
from repro.parallel.mesh import ParallelismPlan, split_model_axis
from repro.runtime.driver import (DriverConfig, TrainDriver,
                                  replan_from_registry)
from repro.serving.batcher import (BatchingReport, ContinuousBatchingSession,
                                   Request)
from repro.serving.engine import build_serving
from scripts.bench_check import _bad_numbers, check_metrics_snapshot

HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += dt


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_counter_gauge_series_and_kind_collision():
    reg = Registry()
    c = reg.counter("rounds_total")
    c.inc(kind="decode")
    c.inc(2, kind="decode")
    c.inc(kind="verify")
    assert c.value(kind="decode") == 3
    assert c.value(kind="verify") == 1
    assert c.value(kind="nope") == 0            # untouched series read as 0
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1, kind="decode")
    reg.gauge("pages_free").set(5)
    assert reg.gauge("pages_free").value() == 5
    with pytest.raises(TypeError, match="gauge"):
        reg.counter("pages_free")               # same name, different kind


def test_histogram_empty_stats_are_none_never_nan():
    h = Registry().histogram("round_seconds")
    st = h.stats(kind="decode")
    assert st["count"] == 0 and st["sum"] == 0.0
    assert st["mean"] is None and st["min"] is None
    assert st["p50"] is None and st["p99"] is None
    assert _bad_numbers(st) == []
    h.observe(1.0, kind="decode")
    h.observe(3.0, kind="decode")
    st = h.stats(kind="decode")
    assert st["count"] == 2 and st["mean"] == 2.0
    assert st["min"] == 1.0 and st["max"] == 3.0


def test_timer_observes_elapsed_on_pluggable_clock():
    reg = Registry()
    clock = FakeClock()
    with reg.timer("launch_phase_seconds", clock=clock,
                   phase="compile") as t:
        clock.advance(1.5)
    assert t.elapsed == 1.5
    st = reg.histogram("launch_phase_seconds").stats(phase="compile")
    assert st["count"] == 1 and st["sum"] == 1.5


def test_snapshot_passes_bench_check_schema(tmp_path):
    reg = Registry()
    reg.counter("rounds_total").inc(4, kind="decode")
    reg.gauge("pages_free").set(7)
    reg.histogram("round_seconds").observe(0.25, kind="decode")
    snap = reg.snapshot()
    assert check_metrics_snapshot(snap) == []
    assert json.loads(json.dumps(snap)) == snap     # JSON-safe
    path = tmp_path / "metrics.json"
    reg.save(str(path))
    with open(path) as f:
        assert check_metrics_snapshot(json.load(f)) == []


# ---------------------------------------------------------------------------
# trace + reconcile
# ---------------------------------------------------------------------------

def test_trace_span_counts_and_reconcile_fixed_point():
    """Rounds timed on a modeled clock charging exactly the
    weighted_round_time prediction reconcile at ratio 1.0 and carry the
    table's non-bubble cell count per stage (obs_smoke asserts the same
    invariants harder; this keeps them in the pytest net)."""
    S, R = 2, 4
    sched = SCHEDULES["serve_1f"](S, R)
    tf = np.array([1.0e-3, 2.0e-3])
    cost, wbubble = weighted_round_time(sched, tf, 0.0)
    clock = FakeClock()
    obs = Observability(trace=True, clock=clock)
    for _ in range(3):
        t0 = clock()
        clock.advance(cost)
        obs.on_round("decode", sched, t0, clock(), t_fwd=tf, t_bwd=0.0)
    cells = (np.asarray(sched.tables().fwd)[:, :, F_MB] >= 0).sum(axis=0)
    counts = obs.trace.span_counts("decode")
    assert [counts[s] for s in range(S)] == (cells * 3).tolist()
    rep = reconcile(sched, trace=obs.trace, registry=obs.registry,
                    kind="decode", t_fwd=tf)
    assert rep.rounds == 3
    assert rep.round_ratio == pytest.approx(1.0, abs=1e-9)
    assert rep.measured_bubble == pytest.approx(float(wbubble), abs=1e-9)


def test_reconcile_falls_back_to_registry_without_trace():
    sched = SCHEDULES["serve_1f"](2, 4)
    reg = Registry()
    reg.histogram("round_seconds").observe(0.5, kind="decode")
    rep = reconcile(sched, registry=reg, kind="decode")
    assert rep.rounds == 1 and rep.measured_round_s == 0.5
    # no absolute costs: unit-free comparison only
    assert rep.predicted_round_s is None and rep.round_ratio is None
    assert rep.predicted_bubble > 0
    assert "n/a" in str(rep)


def test_stage_seconds_refuses_partial_telemetry():
    reg = Registry()
    h = reg.histogram("stage_round_seconds")
    h.observe(1.0, stage=0)
    with pytest.raises(ValueError, match="stage=1"):
        stage_seconds(reg, 2)
    h.observe(2.0, stage=1)
    assert stage_seconds(reg, 2) == [1.0, 2.0]


# ---------------------------------------------------------------------------
# batcher regression: zero completions must summarize to None, not NaN
# ---------------------------------------------------------------------------

def test_empty_report_summary_has_none_latencies_not_nan():
    r = Request(rid=0, prompt=np.array([1, 2], np.int32),
                max_new_tokens=4, arrival=0)
    rep = BatchingReport(requests=[r], policy="continuous", steps=3,
                         decode_rounds=3, admit_rounds=1,
                         wall_seconds=0.25)
    s = rep.summary()
    assert s["completed"] == 0
    assert s["p50_per_token_latency_s"] is None
    assert s["p99_per_token_latency_s"] is None
    assert s["mean_ttft_s"] is None
    assert _bad_numbers(s) == []                # the bench_check gate
    assert json.loads(json.dumps(s)) == s       # survives a round-trip


# ---------------------------------------------------------------------------
# real engine: bucketed rounds traced with the pick_bucket choices
# ---------------------------------------------------------------------------

def _attn_spec(n_layers=2):
    blocks = tuple(spec_lib.BlockSpec(mixer="attn", ffn="dense")
                   for _ in range(n_layers))
    return spec_lib.ModelSpec(
        name="obs-test", d_model=64, n_layers=n_layers, n_heads=4,
        n_kv=2, d_head=16, d_ff=128, vocab=256, blocks=blocks,
        norm="rmsnorm", act="silu")


def _bucketed_session(n_slots=4, prefill=8, cache=64):
    """pp=1 on the single CPU device — full engine code path."""
    spec = _attn_spec()
    mesh = make_host_mesh(data=1, model=1)
    dmesh = split_model_axis(mesh, 1, 1)
    plan = ParallelismPlan(pp=1, tp=1, microbatches=n_slots,
                           decode_microbatches=n_slots,
                           schedule="serve_1f")
    obs = Observability(trace=True)
    sess = build_serving(spec, plan, dmesh, cache_len=cache,
                         global_batch=n_slots, prefill_len=prefill,
                         compute_dtype=jnp.float32, buckets=True, obs=obs)
    sess.start(jax.random.key(0))
    return sess, obs


def test_bucketed_trace_agrees_with_engine_bucket_log():
    """ISSUE-10 acceptance: the staggered bucket-switching trace
    (batch_smoke's shape: two early finishers shrink the bucket, a late
    arrival grows it back) must leave registry counters, trace round
    records, and span tags all agreeing with the engine's own
    ``_bucket_log`` — and the per-stage span counts must equal the
    non-bubble cells of the tables actually walked."""
    sess, obs = _bucketed_session()
    R = sess.sched.n_microbatches
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 256, 8).astype(np.int32) for _ in range(5)]
    trace = [Request(rid=i, prompt=p, max_new_tokens=m, arrival=a)
             for i, (p, m, a) in enumerate(zip(
                 prompts, [3, 3, 12, 12, 4], [0, 0, 0, 0, 5]))]

    lives = []                    # live-slot count the bucket picker saw
    orig_decode = sess.decode

    def spy_decode(tokens, bucket=None):
        lives.append(int(sess._live.sum()))
        return orig_decode(tokens, bucket)

    sess.decode = spy_decode
    report = ContinuousBatchingSession(sess).run(trace)
    assert len(report.completed) == 5

    log = list(sess._bucket_log)
    assert any(b < R for b in log), "trace never shrank the bucket"
    assert all(b in sess.buckets for b in log)

    # registry counters == the engine's own bucket log, per bucket
    ctr = obs.registry.counter("bucket_rounds_total")
    counted = collections.Counter()
    for ls in ctr.labelsets():
        counted[int(ls["bucket"])] += int(ctr.value(**ls))
    assert counted == collections.Counter(log)

    # trace rounds carry the same bucket sequence, in order
    traced = [r.bucket for r in obs.trace.rounds
              if r.kind in ("decode", "verify", "admit")]
    assert traced == log

    # decode-round tags == pick_bucket of the live count decode() saw
    decode_buckets = [r.bucket for r in obs.trace.rounds
                      if r.kind == "decode"]
    assert len(decode_buckets) == len(lives)
    assert decode_buckets == [pick_bucket(n, sess.buckets) for n in lives]

    # per-stage span counts == non-bubble cells of the walked tables
    S = sess.sched.n_stages
    expected = np.zeros(S, int)
    for rec in obs.trace.rounds:
        sched = (sess.sched if rec.bucket in (None, R)
                 else sess._bucket_scheds[rec.bucket])
        expected += (np.asarray(sched.tables().fwd)[:, :, F_MB]
                     >= 0).sum(axis=0)
    counts = obs.trace.span_counts()
    assert [counts.get(s, 0) for s in range(S)] == expected.tolist()

    # trace JSON + metrics snapshot are artifact-clean, and the
    # batcher's scheduler-level series rode the same registry
    doc = json.loads(json.dumps(obs.trace.to_json()))
    assert all(e["ph"] in ("M", "X") for e in doc["traceEvents"])
    assert check_metrics_snapshot(obs.registry.snapshot()) == []
    reg = obs.registry
    assert reg.counter("requests_completed_total").value(
        policy="continuous") == 5
    assert reg.histogram("ttft_seconds").stats(
        policy="continuous")["count"] == 5


# ---------------------------------------------------------------------------
# driver: rounds into the registry; replanning off collected telemetry
# ---------------------------------------------------------------------------

def mk_spec(n_layers=8, heads=4, d_model=256, d_ff=1024, vocab=1024):
    blocks = tuple(spec_lib.BlockSpec(mixer="attn", ffn="dense")
                   for _ in range(n_layers))
    return spec_lib.ModelSpec(name="t", d_model=d_model,
                              n_layers=n_layers, n_heads=heads,
                              n_kv=heads, d_head=max(d_model // heads, 8),
                              d_ff=d_ff, vocab=vocab, blocks=blocks,
                              norm="rmsnorm", act="silu")


def _time_stages(reg, stage_s, rounds=3):
    """Collect per-stage wall times through the registry's own timer —
    the measured path, not hand-injected numbers."""
    clock = FakeClock()
    for _ in range(rounds):
        for s, sec in enumerate(stage_s):
            with reg.timer("stage_round_seconds", clock=clock, stage=s):
                clock.advance(sec)


def test_replan_from_registry_flips_on_measured_straggler():
    """ISSUE-10 acceptance: elastic_replan flips the plan from
    telemetry collected through the registry.  Same config as
    tests/test_plan_search.py::test_rebalance_responds_to_measurements,
    but the measurements arrive via Registry.timer → stage_seconds."""
    spec = mk_spec()
    hw = dataclasses.replace(prof.TPU_V5E, link_bw=1e11, hbm_bytes=1e18)
    plan = ParallelismPlan(pp=4, tp=1, microbatches=8, stash_mode="stash")
    kw = dict(minibatch_tokens=4096, data_replicas=1)

    reg = Registry()
    _time_stages(reg, [0.1, 0.1, 0.1, 0.2])     # 2x straggler on stage 3
    p, changed = replan_from_registry(spec, plan, reg, hw, **kw)
    assert changed
    assert (p.pp, p.tp) == (2, 2)

    even = Registry()
    _time_stages(even, [0.1, 0.1, 0.1, 0.1])    # balanced: no-op
    p, changed = replan_from_registry(spec, plan, even, hw, **kw)
    assert not changed and p == plan


def test_train_driver_reports_rounds_and_stage_seconds(tmp_path):
    from repro.core.pipeline import build_pipeline
    from repro.data.pipeline import ShardedLoader, SyntheticLM
    from repro.optim import SGDM

    spec = _attn_spec(n_layers=2)
    plan = ParallelismPlan(pp=1, tp=1, microbatches=2, stash_mode="stash")
    mesh = make_host_mesh(data=1, model=1)
    dmesh = split_model_axis(mesh, 1, 1)
    obs = Observability(trace=True)
    bundle = build_pipeline(spec, plan, dmesh, seq_len=16, global_batch=4,
                            optimizer=SGDM(lr=0.01),
                            compute_dtype=jnp.float32, obs=obs)
    loader = ShardedLoader(SyntheticLM(spec.vocab, 16),
                           bundle.batch_specs())
    # the driver inherits obs from the bundle; stage_seconds_fn feeds
    # the histograms replan_from_registry reads (the SPMD step is one
    # fused program — the host cannot time stages individually)
    driver = TrainDriver(bundle, loader, str(tmp_path), DriverConfig(),
                         stage_seconds_fn=lambda step: [0.01])
    state = jax.jit(bundle.init_state,
                    out_shardings=bundle.state_shardings())(
        jax.random.key(0))
    driver.run(state, 3)

    reg = obs.registry
    assert reg.counter("rounds_total").value(kind="train") == 3
    assert reg.histogram("round_seconds").stats(kind="train")["count"] == 3
    assert reg.histogram("stage_round_seconds").stats(stage=0)["count"] == 3
    assert stage_seconds(reg, 1) == [pytest.approx(0.01)]
    # a training round is traced by the profiler (host spans, device
    # scopes), never synthesized from the table: the registry has it all
    assert obs.trace.rounds == []
    assert reg.histogram("round_seconds").stats(kind="train")["sum"] == \
        pytest.approx(sum(driver.stage_times))


# ---------------------------------------------------------------------------
# launcher flags: --trace-out / --metrics-out produce valid artifacts
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serve_trace_and_metrics_flags(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    tr, mt = tmp_path / "trace.json", tmp_path / "metrics.json"
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "qwen3-14b",
         "--smoke", "--tokens", "4", "--host-devices", "2", "--batch", "2",
         "--trace-out", str(tr), "--metrics-out", str(mt)],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nERR:\n{out.stderr}"
    assert "reconcile[" in out.stdout

    doc = json.loads(tr.read_text())
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert spans and all(e["args"]["phase"] in ("F", "B", "bubble")
                         for e in spans)
    snap = json.loads(mt.read_text())
    assert check_metrics_snapshot(snap, "metrics.json") == []
    hist_names = {r["name"] for r in snap["histograms"]}
    assert {"round_seconds", "launch_phase_seconds"} <= hist_names
