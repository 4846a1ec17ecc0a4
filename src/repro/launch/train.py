import os
import sys

if __name__ == "__main__" and "--host-devices" in sys.argv:
    _n = sys.argv[sys.argv.index("--host-devices") + 1]
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_n} "
        + os.environ.get("XLA_FLAGS", ""))
"""End-to-end pipelined training driver.

Builds the (arch × plan) pipeline on a mesh over every device present
(data × pp × tp = device count), feeds the deterministic synthetic LM
stream through the fault-tolerant TrainDriver (periodic per-stage
checkpoints, restart-from-last-complete-round), and logs loss per round.

CPU example (the --smoke config fits a laptop):
  python -m repro.launch.train --arch qwen3-14b --smoke --steps 20 \\
      --host-devices 4 --ckpt /tmp/ckpt

One TPU v5e, h2o-danube3-4b at published widths cut to 4 of 24 layers:
  python -m repro.launch.train --arch h2o-danube3-4b --layers 4 \\
      --pp 1 --tp 1 --microbatches 8 --global-batch 8 --steps 5
"""
import argparse        # noqa: E402
import contextlib      # noqa: E402
import json            # noqa: E402
import tempfile        # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs                          # noqa: E402
from repro.core.pipeline import build_pipeline     # noqa: E402
from repro.data.pipeline import ShardedLoader, SyntheticLM, vlm_patch_stub  # noqa: E402
from repro.launch.mesh import make_device_mesh     # noqa: E402
from repro.obs import Observability, reconcile     # noqa: E402
from repro.optim.optimizers import by_name         # noqa: E402
from repro.parallel.mesh import split_model_axis   # noqa: E402
from repro.runtime.driver import DriverConfig, TrainDriver  # noqa: E402

# the checkout this module runs from (src/repro/launch/train.py)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def compile_cache_dir(environ=os.environ) -> str:
    """Where JAX's persistent compile cache lives.

    ``JAX_COMPILATION_CACHE_DIR`` when it is set; otherwise one fixed
    directory inside the checkout (the path is part of the cache key, so
    it never moves between runs).
    """
    return (environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(CHECKOUT, ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; entry points only.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here.
    """
    path = compile_cache_dir()
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="the config's reduced same-family spec and plan")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the model to its first N layers (widths "
                         "unchanged)")
    ap.add_argument("--pp", type=int, default=None,
                    help="pipeline stages (default: the config's plan)")
    ap.add_argument("--tp", type=int, default=None,
                    help="tensor-parallel degree (default: the plan's)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=None,
                    help="default 64 with --smoke, else train_4k's")
    ap.add_argument("--global-batch", type=int, default=None,
                    help="default 8 with --smoke, else train_4k's")
    ap.add_argument("--microbatches", type=int, default=None,
                    help="R per round (default 2 with --smoke, else "
                         "the plan's)")
    ap.add_argument("--dtype", type=str, default=None,
                    choices=[None, "bfloat16", "float32"],
                    help="compute dtype (default float32 with --smoke, "
                         "else bfloat16)")
    from repro.core.schedule import SCHEDULES
    ap.add_argument("--schedule", type=str, default=None,
                    choices=[None, *sorted(SCHEDULES)],
                    help="override the plan's pipeline schedule")
    ap.add_argument("--virtual-stages", type=int, default=None,
                    help="model chunks per stage (interleaved schedule)")
    ap.add_argument("--plan-search", action="store_true",
                    help="let plan_search pick (pp, tp, schedule, "
                         "virtual_stages) under the HBM budget")
    ap.add_argument("--optimizer", type=str, default=None)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--ckpt", type=str, default=None,
                    help="checkpoint directory (default: a fresh "
                         "temporary directory, removed at exit)")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--host-devices", type=int, default=None)
    ap.add_argument("--log", type=str, default=None)
    ap.add_argument("--trace-out", type=str, default=None,
                    help="write a Chrome trace-event JSON of every "
                         "training round (one track per stage; open in "
                         "Perfetto / chrome://tracing)")
    ap.add_argument("--metrics-out", type=str, default=None,
                    help="write the metrics-registry snapshot JSON "
                         "(schema-checked by scripts/bench_check.py)")
    return ap


def build(args, obs=None, devices=None):
    """(spec, bundle) for ``args``; prints every cut from the config.

    The mesh covers ``devices`` (default ``jax.devices()``): data takes
    what pp × tp leaves.
    """
    cfg = configs.get(args.arch)
    if args.smoke:
        spec, plan = cfg.smoke_spec(), cfg.SMOKE_PLAN.with_(microbatches=2)
        seq_len, global_batch = 64, 8
    else:
        spec, plan = cfg.full_spec(), cfg.PLAN
        shape = configs.SHAPES["train_4k"]
        seq_len, global_batch = shape.seq_len, shape.global_batch
    published = (spec.n_layers, global_batch)
    if args.layers:
        spec = spec.with_depth(args.layers)
    plan = plan.with_(**{k: v for k, v in (
        ("pp", args.pp), ("tp", args.tp),
        ("microbatches", args.microbatches)) if v})
    seq_len = args.seq_len or seq_len
    global_batch = args.global_batch or global_batch
    from repro.core.schedule import (plan_kwargs_for_schedule,
                                     virtual_stages_error)
    err = virtual_stages_error(args.schedule, args.virtual_stages)
    if err:
        raise SystemExit(err)
    if args.schedule:
        plan = plan.with_(**plan_kwargs_for_schedule(
            args.schedule, virtual_stages=args.virtual_stages,
            stash_mode=plan.stash_mode))
    if spec.frontend == "vision":
        seq_len = max(seq_len, spec.n_patches + 16)
    n_dev = len(jax.devices() if devices is None else devices)
    if args.plan_search:
        from repro.runtime.driver import plan_search_report
        plan = plan_search_report(spec, plan, seq_len=seq_len,
                                  global_batch=global_batch,
                                  data_replicas=n_dev // (plan.pp * plan.tp)
                                  ).plan
    if (spec.n_layers, global_batch) != published:
        print(f"cuts: layers {published[0]} -> {spec.n_layers}, "
              f"global_batch {published[1]} -> {global_batch} "
              f"(seq_len {seq_len})")
    mesh = make_device_mesh(pp=plan.pp, tp=plan.tp, devices=devices)
    dmesh = split_model_axis(mesh, plan.pp, plan.tp)
    name, lr = cfg.OPTIMIZER
    opt = by_name(args.optimizer or name, args.lr or lr)
    dtype = args.dtype or ("float32" if args.smoke else "bfloat16")
    bundle = build_pipeline(spec, plan, dmesh, seq_len=seq_len,
                            global_batch=global_batch, optimizer=opt,
                            compute_dtype=jnp.dtype(dtype), obs=obs)
    return spec, bundle


def make_loader(spec, bundle) -> ShardedLoader:
    """The deterministic synthetic LM stream, placed per the bundle."""
    src = SyntheticLM(spec.vocab, bundle.seq_len
                      - (spec.n_patches if spec.frontend == "vision" else 0))
    extra = vlm_patch_stub(spec.d_model) if spec.frontend == "vision" else None
    return ShardedLoader(src, bundle.batch_specs(), extra_fn=extra)


def init_state(bundle, seed: int = 0):
    return jax.jit(bundle.init_state,
                   out_shardings=bundle.state_shardings())(
        jax.random.key(seed))


def describe(spec, bundle) -> str:
    from repro.core.schedule import weighted_round_time
    plan = bundle.plan
    _, bubble = weighted_round_time(bundle.sched)
    return (f"arch={spec.name} d_model={spec.d_model} "
            f"heads={spec.n_heads}/{spec.n_kv} d_head={spec.d_head} "
            f"d_ff={spec.d_ff} vocab={spec.vocab} layers={spec.n_layers}\n"
            f"plan: pp={plan.pp} tp={plan.tp} schedule={bundle.sched.name}"
            + (f" v={plan.virtual_stages}" if plan.virtual_stages > 1
               else "")
            + f" stash={plan.stash_mode} R={plan.microbatches} "
            f"seq_len={bundle.seq_len} mesh={dict(bundle.mesh.shape)} "
            f"predicted_bubble={bubble:.3f}")


def main(argv=None):
    args = parser().parse_args(argv)
    enable_compile_cache()
    obs = Observability(trace=bool(args.trace_out))
    spec, bundle = build(args, obs=obs)
    print(describe(spec, bundle))
    with contextlib.ExitStack() as stack:
        ckpt = args.ckpt or stack.enter_context(
            tempfile.TemporaryDirectory(prefix="repro_ckpt_"))
        driver = TrainDriver(bundle, make_loader(spec, bundle), ckpt,
                             DriverConfig(checkpoint_every=args.ckpt_every))
        state = init_state(bundle)
        with obs.timer("launch_phase_seconds", phase="run") as t:
            state, step = driver.run(state, args.steps)
    dt = t.elapsed
    losses = [m["loss"] for m in driver.metrics_log]
    print(f"arch={spec.name} steps={step} time={dt:.1f}s "
          f"restarts={driver.restarts} "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    for fault in driver.faults:
        print(f"  recovered from fault at {fault}")
    print(" ", reconcile(bundle.sched, trace=obs.trace,
                         registry=obs.registry, kind="train"))
    obs.save(trace_out=args.trace_out, metrics_out=args.metrics_out)
    if args.trace_out:
        print(f"wrote pipeline trace to {args.trace_out}")
    if args.metrics_out:
        print(f"wrote metrics snapshot to {args.metrics_out}")
    if args.log:
        with open(args.log, "w") as f:
            json.dump({"arch": spec.name, "losses": losses,
                       "seconds": dt, "restarts": driver.restarts}, f)
    return losses


if __name__ == "__main__":
    main()
