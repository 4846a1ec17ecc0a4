"""The program names its own work in a profiler trace (repro.obs): device
scopes reach the compiled step's op_name metadata, and the training
loop's host spans reach the profiler's trace."""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from repro import obs
from repro.launch import train

HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")
OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')


def parts(op_name):
    """Scope names on an op_name path, autodiff wrappers split off."""
    return set(re.split(r"[/()]", op_name))


def compiled_op_names(bundle):
    shapes = jax.eval_shape(bundle.init_state, jax.random.key(0))
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, bundle.state_shardings())
    text = jax.jit(bundle.train_step).lower(
        state, bundle.batch_specs()).compile().as_text()
    return set(OP_NAME.findall(text))


def tiny_bundle(config):
    """The benchmark's tiny cell (tests/bench/<config>.json) as built by
    the launcher."""
    def load(name):
        with open(os.path.join(HERE, "bench", name + ".json")) as f:
            return json.load(f)
    cell = harness.Cell("tiny.train", {"name": "tiny.train", "chips": 1},
                        load(config), load("tiny-train"), {})
    args = train.parser().parse_args(harness.launcher_argv(cell)
                                     + ["--smoke"])
    return train.build(args, devices=jax.devices()[:1])[1]


@pytest.mark.parametrize("config,mixer,absent", [
    ("tiny-danube", obs.ATTENTION, obs.WKV),
    ("tiny-rwkv", obs.WKV, obs.ATTENTION)])
def test_compiled_step_carries_phase_and_mixer_scopes(config, mixer,
                                                      absent):
    names = compiled_op_names(tiny_bundle(config))
    seen = set().union(*map(parts, names))
    assert set(obs.PHASES) <= seen
    assert mixer in seen and absent not in seen
    # the recompute and the backward of the mixer both sit under bwd
    assert any({obs.BWD, mixer, "jvp"} <= parts(n) for n in names)
    assert any({obs.BWD, mixer, "transpose"} <= parts(n) for n in names)


def test_pp2_step_names_its_sends_and_per_microbatch_updates():
    """pp=2 1F1B on two emulated host devices: every stage-to-stage
    transfer is under ``send``, and the per-microbatch stage update is
    ``optimizer``, outside ``bwd``."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "scopes_check.py"), "2",
         "1f1b"], capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nERR:\n{out.stderr}"
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(got["permutes"]) >= 2
    assert all(obs.SEND in parts(n) for n in got["permutes"])
    assert any(obs.FWD in parts(n) for n in got["permutes"])
    assert any(obs.BWD in parts(n) for n in got["permutes"])
    per_mb = [n for n in got["op_names"]
              if "while" in n and obs.OPTIMIZER in parts(n)]
    assert per_mb and not any(obs.BWD in parts(n) for n in per_mb)


def test_driver_rounds_emit_their_spans(tmp_path):
    """Two rounds under jax.profiler.trace: each train.* and loader.*
    span once a round, inside that round's train.round."""
    from jax.profiler import ProfileData

    from repro.core.pipeline import build_pipeline
    from repro.data.pipeline import ShardedLoader, SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.models import spec as spec_lib
    from repro.optim import SGDM
    from repro.parallel.mesh import ParallelismPlan, split_model_axis
    from repro.runtime.driver import DriverConfig, TrainDriver

    blocks = tuple(spec_lib.BlockSpec(mixer="attn", ffn="dense")
                   for _ in range(2))
    spec = spec_lib.ModelSpec(
        name="span-test", d_model=32, n_layers=2, n_heads=2, n_kv=2,
        d_head=16, d_ff=64, vocab=64, blocks=blocks, norm="rmsnorm",
        act="silu")
    plan = ParallelismPlan(pp=1, tp=1, microbatches=2, stash_mode="flush",
                           schedule="gpipe")
    dmesh = split_model_axis(make_host_mesh(data=1, model=1), 1, 1)
    bundle = build_pipeline(spec, plan, dmesh, seq_len=8, global_batch=2,
                            optimizer=SGDM(lr=0.01),
                            compute_dtype=jnp.float32)
    loader = ShardedLoader(SyntheticLM(spec.vocab, 8), bundle.batch_specs())
    driver = TrainDriver(bundle, loader, str(tmp_path / "ckpt"),
                         DriverConfig(checkpoint_every=1))
    state = jax.jit(bundle.init_state,
                    out_shardings=bundle.state_shardings())(
        jax.random.key(0))
    state, step = driver.run(state, 1)          # compiles
    with jax.profiler.trace(str(tmp_path / "trace")):
        driver.run(state, 3, step)
    path = next(os.path.join(d, f) for d, _, fs in os.walk(tmp_path / "trace")
                for f in fs if f.endswith(".xplane.pb"))
    pd = ProfileData.from_file(path)
    names = set(obs.TRAIN_SPANS + obs.LOADER_SPANS)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for p in pd.planes if p.name == "/host:CPU"
             for line in p.lines for e in line.events if e.name in names]
    rounds = sorted((s, e) for n, s, e in spans if n == obs.TRAIN_ROUND)
    assert len(rounds) == 2
    for s0, e0 in rounds:
        inside = [n for n, s, e in spans if s0 <= s and e <= e0]
        assert sorted(inside) == sorted(names)
