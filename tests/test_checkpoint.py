"""Fault tolerance (paper §4): per-stage local checkpoints, restart from
the last round completed by ALL stages, driver crash/replay determinism,
and elastic stage resharding."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.checkpoint.manager import CheckpointManager, reshard_stages
from repro.core.pipeline import build_pipeline
from repro.core.reference import reference_init_state
from repro.data.pipeline import ShardedLoader, SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.optim import SGDM
from repro.parallel.mesh import ParallelismPlan, split_model_axis
from repro.runtime.driver import DriverConfig, TrainDriver


def _tiny_state(pp=2, mode="stash"):
    cfg = configs.get("qwen3_14b")
    spec = cfg.smoke_spec()
    plan = cfg.SMOKE_PLAN.with_(pp=pp, stash_mode=mode)
    opt = SGDM(lr=0.01)
    state = reference_init_state(spec, plan, opt, jax.random.key(0))
    return spec, plan, state


def test_save_restore_roundtrip(tmp_path):
    spec, plan, state = _tiny_state()
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(3, state, plan.pp)
    assert mgr.latest_complete_round() == 3
    template = jax.tree.map(lambda a: np.zeros(a.shape, a.dtype), state)
    restored = mgr.restore(3, template)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(state),
            jax.tree_util.tree_leaves_with_path(restored)):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_partial_save_is_ignored(tmp_path):
    """A crash mid-dump leaves an incomplete manifest; restart must fall
    back to the previous complete round — the paper's exact semantics."""
    spec, plan, state = _tiny_state()
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, state, plan.pp)
    mgr.save(2, state, plan.pp, fail_after_stage=0)   # stage 1 never lands
    assert mgr.latest_complete_round() == 1
    mgr.save(4, state, plan.pp)
    assert mgr.latest_complete_round() == 4


def _driver_setup(tmp_path, failure_hook=None, steps_between_ckpt=2):
    """pp=1 pipeline on the single CPU device (still scan + stash +
    per-tick head updates — the full train_step code path)."""
    cfg = configs.get("qwen3_14b")
    spec = cfg.smoke_spec()
    plan = ParallelismPlan(pp=1, tp=1, microbatches=2, stash_mode="stash",
                           zero1=False)
    mesh = make_host_mesh(data=1, model=1)
    dmesh = split_model_axis(mesh, 1, 1)
    opt = SGDM(lr=0.01)
    bundle = build_pipeline(spec, plan, dmesh, seq_len=16, global_batch=4,
                            optimizer=opt, compute_dtype=jnp.float32)
    loader = ShardedLoader(SyntheticLM(spec.vocab, 16),
                           bundle.batch_specs())
    driver = TrainDriver(bundle, loader, str(tmp_path),
                         DriverConfig(checkpoint_every=steps_between_ckpt),
                         failure_hook=failure_hook)
    state = jax.jit(bundle.init_state,
                    out_shardings=bundle.state_shardings())(
        jax.random.key(0))
    return bundle, driver, state


@pytest.mark.slow
def test_driver_restart_replays_identically(tmp_path):
    """Kill the run at step 5, restart from the last checkpoint, and the
    final state must equal an uninterrupted run (deterministic data)."""
    # uninterrupted baseline
    bundle, driver, state = _driver_setup(tmp_path / "a")
    ref_state, _ = driver.run(state, 8)
    ref_losses = [m["loss"] for m in driver.metrics_log]

    crashes = {"armed": True}

    def hook(step):
        if step == 5 and crashes["armed"]:
            crashes["armed"] = False
            raise RuntimeError("simulated node failure")

    bundle2, driver2, state2 = _driver_setup(tmp_path / "b",
                                             failure_hook=hook)
    out_state, step = driver2.run(state2, 8)
    assert step == 8
    losses = [m["loss"] for m in driver2.metrics_log]
    # replayed rounds produce identical losses as the uninterrupted run
    np.testing.assert_allclose(losses[-1], ref_losses[-1], rtol=1e-6)
    got = jax.device_get(out_state["params"]["head"])
    want = jax.device_get(ref_state["params"]["head"])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_driver_gives_up_after_max_restarts(tmp_path):
    def hook(step):
        raise RuntimeError("always down")

    bundle, driver, state = _driver_setup(tmp_path, failure_hook=hook)
    driver.cfg.max_restarts = 2
    with pytest.raises(RuntimeError):
        driver.run(state, 4)


def test_driver_reports_restart_count(tmp_path):
    """An injected fault is survived (restore + replay) and counted, so
    a caller that must not hide faults can see it; a clean run counts 0."""
    fired = []

    def hook(step):
        if step == 1 and not fired:
            fired.append(step)
            raise RuntimeError("simulated node failure")

    bundle, driver, state = _driver_setup(tmp_path / "faulty",
                                          failure_hook=hook)
    _, step = driver.run(state, 3)
    # no checkpoint yet: round 0 is replayed from scratch, identically
    assert step == 3 and len(driver.metrics_log) == 4
    assert driver.metrics_log[0] == driver.metrics_log[1]
    assert driver.restarts == 1
    assert driver.faults == ["step 1: RuntimeError: simulated node failure"]

    bundle, clean, state = _driver_setup(tmp_path / "clean")
    clean.run(state, 2)
    assert clean.restarts == 0 and clean.faults == []


def test_truncated_manifest_is_skipped(tmp_path):
    """A torn MANIFEST.json (crash mid-write on a pre-atomic layout, or
    a disk fault) must read as 'round incomplete', not crash the restart
    scan with json.JSONDecodeError."""
    spec, plan, state = _tiny_state()
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, state, plan.pp)
    mgr.save(2, state, plan.pp)
    assert mgr.latest_complete_round() == 2
    mf = tmp_path / "round_00000002" / "MANIFEST.json"
    raw = mf.read_text()
    mf.write_text(raw[: len(raw) // 2])          # deliberately truncated
    assert mgr.latest_complete_round() == 1
    # the older round is still restorable
    template = jax.tree.map(lambda a: np.zeros(a.shape, a.dtype), state)
    restored = mgr.restore(1, template)
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["stages"]["layer_0"]["mlp"]["w1"]),
        np.asarray(state["params"]["stages"]["layer_0"]["mlp"]["w1"]))


def test_manifest_write_is_atomic(tmp_path):
    """save() must never leave a MANIFEST.json.tmp behind and the final
    manifest must always parse (written via tmp + os.replace)."""
    import json

    spec, plan, state = _tiny_state()
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(0, state, plan.pp)
    d = tmp_path / "round_00000000"
    assert not (d / "MANIFEST.json.tmp").exists()
    with open(d / "MANIFEST.json") as f:
        m = json.load(f)
    assert m["done"] and m["stages"] == list(range(plan.pp))


def test_save_restore_preserves_dtypes(tmp_path):
    """bf16 leaves must survive the npz round-trip bit-exactly: np.savez
    silently degrades ml_dtypes bfloat16 to a raw void ``|V2``, so the
    manager dumps the uint16 payload and views it back through the
    template dtype (seed bug: restore died on the void array)."""
    key = jax.random.key(7)
    mk = lambda k, shape, dt: jax.random.normal(
        jax.random.fold_in(key, k), shape, jnp.float32).astype(dt)
    state = {
        "params": {
            "stages": {"layer_0": {"w": mk(0, (2, 4, 8), jnp.bfloat16),
                                   "b": mk(1, (2, 4), jnp.float32)}},
            "embed": mk(2, (16, 8), jnp.bfloat16),
            "layer_windows": jnp.full((2, 1), -1, jnp.int32),
        },
        "step": jnp.zeros((), jnp.int32),
    }
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(0, state, 2)
    assert mgr.latest_complete_round() == 0
    template = jax.tree.map(lambda a: np.zeros(a.shape, a.dtype), state)
    restored = mgr.restore(0, template)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(state),
            jax.tree_util.tree_leaves_with_path(restored)):
        assert pa == pb
        assert np.asarray(b).dtype == np.asarray(a).dtype, pa
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), pa


def test_reshard_stages_preserves_global_layers():
    """pp=2 -> pp=4 -> pp=2 roundtrip keeps every global layer's params."""
    spec, plan, state = _tiny_state(pp=2)
    stages = state["params"]["stages"]
    re4 = reshard_stages(stages, 2, 4)
    back = reshard_stages(re4, 4, 2)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(stages),
            jax.tree_util.tree_leaves_with_path(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # spot-check: global layer 3 = (stage 1, pos 1) at pp=2
    #                            = (stage 3, pos 0) at pp=4
    a = np.asarray(stages["layer_1"]["mlp"]["w1"][1])
    b = np.asarray(re4["layer_0"]["mlp"]["w1"][3])
    np.testing.assert_array_equal(a, b)


def test_restart_budget_resets_on_checkpoint(tmp_path):
    """max_restarts bounds CONSECUTIVE failures, not sporadic ones: three
    spread-out faults with successful checkpoints between them must not
    abort a run whose budget is two (the counter resets on each complete
    checkpoint — seed bug: it never reset, so any long run died)."""
    faults = {2, 5, 9}

    def hook(step):
        if step in faults:
            faults.discard(step)
            raise RuntimeError("sporadic failure")

    bundle, driver, state = _driver_setup(tmp_path, failure_hook=hook,
                                          steps_between_ckpt=2)
    driver.cfg.max_restarts = 2
    state, step = driver.run(state, 12)
    assert step == 12
    assert not faults          # every fault actually fired once


def test_reshard_state_interleaved_roundtrip():
    """stash pp=2 -> interleaved pp=2 v=2 -> back: every global layer's
    params/opt survive the storage-order chunk regrouping (the restart
    sync point makes the schedule switch exact)."""
    from repro.core.schedule import ScheduleInterleaved1F1B
    from repro.runtime.driver import reshard_state_for_plan

    spec, plan, state = _tiny_state(pp=2)
    inter = plan.with_(pp=2, tp=1, schedule="interleaved",
                       stash_mode="flush", virtual_stages=2)
    host = jax.device_get(state)
    fwd = reshard_state_for_plan(host, spec, plan, inter)
    # storage row p = s*v + j holds model chunk j*S + s: with 4 chunks of
    # 1 layer each, rows hold global layers [0, 2, 1, 3]
    order = ScheduleInterleaved1F1B(2, 2, virtual_stages=2) \
        .storage_chunk_order()
    assert list(order) == [0, 2, 1, 3]
    src = np.asarray(host["params"]["stages"]["layer_1"]["mlp"]["w1"][0])
    dst = np.asarray(fwd["params"]["stages"]["layer_0"]["mlp"]["w1"][2])
    np.testing.assert_array_equal(src, dst)   # global layer 1 -> row 2
    # interleaved target is flush-family: the stash ring is dropped
    assert "ring" not in fwd["stash"]
    back = reshard_state_for_plan(fwd, spec, inter, plan)
    for key in ("params", "opt_stages"):
        for (pa, a), (pb, b) in zip(
                jax.tree_util.tree_leaves_with_path(host[key]),
                jax.tree_util.tree_leaves_with_path(back[key])):
            assert pa == pb
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the 1F1B target rebuilds its ring seeded with the live weights
    assert "ring" in back["stash"]
    ring = back["stash"]["ring"]["layer_0"]["mlp"]["w1"]
    assert ring.shape[0] == plan.make_schedule().stash_slots
    np.testing.assert_array_equal(
        np.asarray(ring[0]),
        np.asarray(back["params"]["stages"]["layer_0"]["mlp"]["w1"]))


def test_reshard_to_async_interleaved_builds_chunk_major_ring():
    """1F1B stash -> async interleaved: the chunks regroup into storage
    order exactly as for flush-interleaved, and the target's per-chunk
    ring comes up chunk-major ([stash_slots, S·v, ...]) with every
    version seeded from the regrouped live weights."""
    from repro.runtime.driver import reshard_state_for_plan

    spec, plan, state = _tiny_state(pp=2)          # 1f1b stash, has ring
    host = jax.device_get(state)
    asyn = plan.with_(pp=2, tp=1, schedule="interleaved_async",
                      stash_mode="stash", virtual_stages=2)
    out = reshard_state_for_plan(host, spec, plan, asyn)
    sched = asyn.make_schedule()
    # same storage regrouping as flush-interleaved: global layer 1 -> row 2
    src = np.asarray(host["params"]["stages"]["layer_1"]["mlp"]["w1"][0])
    dst = np.asarray(out["params"]["stages"]["layer_0"]["mlp"]["w1"][2])
    np.testing.assert_array_equal(src, dst)
    ring = out["stash"]["ring"]["layer_0"]["mlp"]["w1"]
    assert ring.shape[0] == sched.stash_slots
    assert ring.shape[1] == 4                      # S·v chunk rows
    for slot in range(sched.stash_slots):
        np.testing.assert_array_equal(
            np.asarray(ring[slot]),
            np.asarray(out["params"]["stages"]["layer_0"]["mlp"]["w1"]))
    # round-trip back to plain 1F1B restores every layer's params/opt
    back = reshard_state_for_plan(out, spec, asyn, plan)
    for key in ("params", "opt_stages"):
        for (pa, a), (pb, b) in zip(
                jax.tree_util.tree_leaves_with_path(host[key]),
                jax.tree_util.tree_leaves_with_path(back[key])):
            assert pa == pb
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_reshard_schedule_only_change_rebuilds_ring():
    """plan_search can flip the schedule at the SAME (pp, v) — e.g.
    stash -> flush to shed the version ring under a tight HBM budget.
    The reshard must drop/rebuild the ring even though no layer moves
    (review catch: the old early-return kept the ring, mismatching the
    new bundle's state template)."""
    from repro.runtime.driver import reshard_state_for_plan

    spec, plan, state = _tiny_state(pp=2)          # stash family: has ring
    host = jax.device_get(state)
    assert "ring" in host["stash"]
    flush = plan.with_(stash_mode="flush")
    out = reshard_state_for_plan(host, spec, plan, flush)
    assert "ring" not in out["stash"]
    np.testing.assert_array_equal(
        np.asarray(out["params"]["stages"]["layer_0"]["mlp"]["w1"]),
        np.asarray(host["params"]["stages"]["layer_0"]["mlp"]["w1"]))
    back = reshard_state_for_plan(out, spec, flush, plan)
    ring = back["stash"]["ring"]["layer_0"]["mlp"]["w1"]
    assert ring.shape[0] == plan.make_schedule().stash_slots
    # identical ring layout (stash <-> vertical share it): true no-op
    vert = plan.with_(stash_mode="vertical")
    assert reshard_state_for_plan(host, spec, plan, vert) is host
