#!/usr/bin/env python
"""Bring-up check: pipelined training of h2o-danube3-4b on a TPU v5e.

  python chip_smoke.py               one chip: published widths, 4 of 24
                                     layers, pp=1 tp=1, 1F1B with weight
                                     stashing, R=8 microbatches of one
                                     4096-token sequence, bf16 compute,
                                     fp32 Adam state; 4 steps through the
                                     launcher's build() and TrainDriver
  python chip_smoke.py --four-chips  four chips: 8 layers as pp=2 x tp=2,
                                     R=4 x 256 tokens, fp32 compute,
                                     3 steps; each step's loss and each
                                     weight's total update compared with
                                     the sequential oracle
                                     (core/reference.py) run on the host
                                     CPU from the same state and batches

Everything runs in this one process.  Weights are random from a fixed
seed and data is the launcher's synthetic stream.  The printed times are
bring-up observations, not a benchmark.  The script exits non-zero, and
prints no result, when JAX finds no TPU, when a loss is not finite, when
the training driver restarted, or when the four-chip losses or weights
leave the stated tolerances.  Its last line on success is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""
import argparse
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ARCH = "h2o-danube3-4b"
ONE_CHIP = ["--layers", "4", "--pp", "1", "--tp", "1", "--microbatches",
            "8", "--global-batch", "8"]
STEPS = 4
# the oracle runs on the host CPU, so the compared run is shorter: R=4
# microbatches of one 256-token sequence
FOUR_CHIPS = ["--layers", "8", "--pp", "2", "--tp", "2", "--microbatches",
              "4", "--global-batch", "4", "--seq-len", "256", "--dtype",
              "float32"]
FOUR_CHIP_STEPS = 3
# Both sides compute in fp32 with HIGHEST matmul precision, so only
# reduction order (tensor-parallel psums, TPU vs CPU kernels) and the
# TPU's transcendental approximations separate them: ~1e-6 relative per
# op.  On 4 emulated CPU devices at the smoke size the gap is below
# 1.5e-06.
# |SPMD loss - oracle loss| allowed at each step: the tp>1 bound of
# tests/spmd_pipeline_check.py.
LOSS_ATOL = 5e-4
# Each weight's total update over the steps, final - initial, is
# compared: ||spmd - oracle|| / ||oracle update|| per leaf.  A plain
# max-abs bound does not fit Adam: its step is lr * g / (|g| + eps), so
# an element whose gradient is at noise level moves by up to lr either
# way.  eps = 1e-8 damps that to a relative error ~1e-3 at most (at
# the smoke size on emulated devices: 9.39e-06); one missed
# microbatch update of the 4 x 3 moves it by ~0.1 or more.
PARAM_RTOL = 1e-2


def fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def train_driver(train, spec, bundle, ckpt_dir, steps):
    """The launcher's TrainDriver with fault replay off: any fault on
    the chip surfaces as itself instead of a silent restart."""
    from repro.runtime.driver import DriverConfig, TrainDriver
    return TrainDriver(bundle, train.make_loader(spec, bundle), ckpt_dir,
                       DriverConfig(checkpoint_every=steps + 1,
                                    max_restarts=0))


def run_steps(train, argv, devices, out, steps, on_init=None):
    """Build through the launcher, train ``steps`` rounds, check them.

    ``on_init(bundle, state)`` sees the initial state before training
    and may return a value (the steps donate the state's buffers).
    Returns (spec, bundle, driver, final state, losses, on_init's
    value)."""
    import jax
    args = train.parser().parse_args(["--arch", ARCH, *argv])
    spec, bundle = train.build(args, devices=devices)
    print(train.describe(spec, bundle))
    ckpt = tempfile.mkdtemp(prefix="ckpt-", dir=out)
    driver = train_driver(train, spec, bundle, ckpt, steps)
    state = train.init_state(bundle)
    seen = on_init(bundle, state) if on_init else None
    state, step = driver.run(state, steps)
    jax.block_until_ready(state)
    shutil.rmtree(ckpt)
    losses = [m["loss"] for m in driver.metrics_log]
    for i, loss in enumerate(losses):
        print(f"step {i}: loss {loss!r} ({driver.stage_times[i]!r} s)")
    if driver.restarts:
        fail(f"the training driver restarted {driver.restarts} times: "
             f"{driver.faults}")
    if step != steps or len(losses) != steps:
        fail(f"ran {step} steps with {len(losses)} losses, wanted {steps}")
    if not all(math.isfinite(x) for x in losses):
        fail(f"non-finite loss: {losses}")
    return spec, bundle, driver, state, losses, seen


def one_chip(train, devices, out):
    spec, bundle, driver, _, losses, _ = run_steps(
        train, ONE_CHIP, devices[:1], out, STEPS)
    times = driver.stage_times
    steady = statistics.median(times[1:])
    stats = devices[0].memory_stats() or {}
    result = {"arch": spec.name, "d_model": spec.d_model,
              "heads": [spec.n_heads, spec.n_kv], "d_head": spec.d_head,
              "d_ff": spec.d_ff, "vocab": spec.vocab,
              "layers": spec.n_layers, "plan": str(bundle.plan),
              "seq_len": bundle.seq_len, "losses": losses,
              "first_step_seconds": times[0],
              "compile_seconds": times[0] - steady,
              "steady_step_seconds": steady,
              "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
              "bytes_limit": stats.get("bytes_limit")}
    print(f"compile_seconds {result['compile_seconds']!r} (first step "
          f"{times[0]!r} s less the steady step)")
    print(f"steady_step_seconds {steady!r} (median of steps 1..)")
    print(f"peak_bytes_in_use {result['peak_bytes_in_use']!r} of "
          f"{result['bytes_limit']!r}")
    return result


def check_placement(bundle, state):
    """Stage and tensor shards of one stage-stacked weight must land on
    distinct devices (one (stage, tensor) block per device)."""
    import jax
    from jax.sharding import PartitionSpec

    from repro.parallel.mesh import AXIS_STAGE, AXIS_TENSOR
    specs, _ = jax.tree.flatten_with_path(
        bundle.state_pspecs["params"]["stages"],
        is_leaf=lambda p: isinstance(p, PartitionSpec))
    leaves, _ = jax.tree.flatten_with_path(state["params"]["stages"])
    path, leaf = next((p, a) for (p, ps), (_, a) in zip(specs, leaves)
                      if AXIS_STAGE in ps and AXIS_TENSOR in ps)
    blocks = {}
    for shard in leaf.addressable_shards:
        key = tuple(sl.start or 0 for sl in shard.index)
        blocks.setdefault(key, set()).add(shard.device.id)
    print(f"placement of stages{jax.tree_util.keystr(path)} "
          f"(block start -> device ids): "
          f"{ {k: sorted(v) for k, v in sorted(blocks.items())} }")
    if len(blocks) != 4 or any(len(ids) != 1 for ids in blocks.values()) \
            or len(set.union(*blocks.values())) != 4:
        fail(f"stage x tensor shards are not on 4 distinct devices: "
             f"{blocks}")


class JitUpdate:
    """An optimizer with its update jitted: the same arithmetic in one
    fused pass per call, which is what keeps the oracle's per-microbatch
    updates of full-width stages affordable on the host CPU."""

    def __init__(self, opt):
        import jax
        self.init, self.update = opt.init, jax.jit(opt.update)


def update_gaps(got, want, init):
    """Per float leaf of the params: ||got - want|| / ||want - init||,
    the relative error of the total update; other leaves must be equal.
    Fetches one leaf at a time, so the host holds three at most."""
    import jax
    gaps = {}
    flat = jax.tree_util.tree_flatten_with_path(got)[0]
    for (path, g), w, w0 in zip(flat, jax.tree.leaves(want),
                                jax.tree.leaves(init)):
        name = jax.tree_util.keystr(path)
        g, w, w0 = (np.asarray(a) for a in (g, w, w0))
        if not np.issubdtype(w.dtype, np.floating):
            if not np.array_equal(g, w):
                fail(f"{name} differs from the oracle's")
            continue
        g, w, w0 = (a.astype(np.float64) for a in (g, w, w0))
        moved = np.linalg.norm(w - w0)
        gaps[name] = float(np.linalg.norm(g - w) / moved) if moved else \
            float(np.abs(g - w).max())
    return gaps


def four_chips(train, devices, out):
    import jax

    from repro.core.reference import reference_train_step
    jax.config.update("jax_default_matmul_precision", "highest")

    cpu = jax.devices("cpu")[0]

    def place_and_keep(bundle, state):
        check_placement(bundle, state)
        # the oracle's initial state, copied leaf by leaf through the
        # host (the steps donate `state`)
        return jax.tree.map(lambda a: jax.device_put(np.asarray(a), cpu),
                            state)

    spec, bundle, driver, state, losses, ref = run_steps(
        train, FOUR_CHIPS, devices, out, FOUR_CHIP_STEPS,
        on_init=place_and_keep)
    ref_init = ref["params"]
    R, bmb, _ = bundle.batch_shapes["tokens"].shape
    ref_losses = []
    opt = JitUpdate(bundle.optimizer)
    with jax.default_device(cpu):
        for i in range(FOUR_CHIP_STEPS):
            t0 = time.perf_counter()
            batch = driver.loader.source.round_batch(i, R, bmb)
            ref, m = reference_train_step(spec, bundle.plan, ref, batch, opt)
            ref_losses.append(float(m["loss"]))
            print(f"oracle step {i}: loss {ref_losses[-1]!r} "
                  f"({time.perf_counter() - t0!r} s)", flush=True)
    diffs = [abs(a - b) for a, b in zip(losses, ref_losses)]
    print(f"spmd   losses {losses}")
    print(f"oracle losses {ref_losses}")
    print(f"max |spmd - oracle| {max(diffs)!r}, tolerance {LOSS_ATOL!r} "
          "per step")
    if max(diffs) > LOSS_ATOL:
        fail(f"pp=2 x tp=2 losses leave the oracle by {diffs}")
    gaps = update_gaps(state["params"], ref["params"], ref_init)
    worst = max(gaps, key=gaps.get)
    print(f"max relative update gap {gaps[worst]!r} at params{worst}, "
          f"tolerance {PARAM_RTOL!r} per weight")
    if gaps[worst] > PARAM_RTOL:
        fail(f"pp=2 x tp=2 weights leave the oracle: "
             f"{ {k: v for k, v in gaps.items() if v > PARAM_RTOL} }")
    return {"arch": spec.name, "layers": spec.n_layers,
            "plan": str(bundle.plan), "seq_len": bundle.seq_len,
            "spmd_losses": losses, "oracle_losses": ref_losses,
            "loss_tolerance": LOSS_ATOL, "update_gaps": gaps,
            "update_tolerance": PARAM_RTOL}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the pp=2 x tp=2 phase and its oracle")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "chip_smoke"))
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX finds no TPU (platform {devices[0].platform!r})")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        fail(f"{want} chips needed, JAX finds {len(devices)}")

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch import train
    cache = train.enable_compile_cache()
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"compile cache: {cache} ({warm} entries before this run)")
    os.makedirs(args.out, exist_ok=True)
    if args.four_chips:
        result = four_chips(train, devices[:4], args.out)
    else:
        result = one_chip(train, devices, args.out)
    kind = devices[0].device_kind
    result["device"] = {"platform": devices[0].platform, "kind": kind,
                        "count": len(devices)}
    name = "four_chips.json" if args.four_chips else "one_chip.json"
    with open(os.path.join(args.out, name), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"ok": True, "device": result["device"]}))


if __name__ == "__main__":
    main()
