"""Run one benchmark cell once: set-up, a timed window, the check.

Everything a cell needs is found by name: the ``workloads`` entry of
BENCHMARK.json names its configuration (``bench/configs/<config>.json``
through the ``configs`` entry's ``file``) and its traffic
(``bench/traffic/<traffic>.json``), and each per-layer metric is read by
``bench/metrics/<metric>.py``.  A cell is added by adding files and
entries, never by editing this module.

The architecture is found the same way: the configuration's "mixer"
names ``bench/archs/<mixer>.py`` (its interface: bench/archs), which
gives the reference its layers, the FLOP count its terms, the build
check its widths and the trace its mixer's scopes.  An architecture is
added by adding, and editing no file that is there:

* ``bench/archs/<mixer>.py``;
* ``bench/configs/<name>.json``, with ``launcher_args`` (passed to the
  launcher after the cell's own arguments: a chip's share of the
  experts, a slice of the vocabulary), ``aux_loss_weight`` (where a
  block returns an auxiliary loss), ``reduced`` and ``assumed`` where
  needed;
* ``bench/traffic/<traffic>.json``, where the cell needs its own traffic;
* ``bench/metrics/<scope>_ms.py`` for a scope of its own, reading
  ``record["phases"]["scope_ms"]``;
* its entries in BENCHMARK.json.

The run builds the training step through the program's launcher
(``repro.launch.train.build``) on the cell's chips, makes the state on
the device from ``--seed`` with the benchmark's own weights, and drives
the program's ``TrainDriver`` with the benchmark's token stream placed by
the program's ``ShardedLoader``.  Set-up ends after the first
``CHECK_STEPS`` steps, the first of which compiles the step; they give
the readings that the plain reference (``bench/reference.py``) is
compared with once the window has closed.
The window then runs steps back to back, each dispatched when the last
has returned, for ``--seconds``.  With ``--trace 1`` a profiler traces
the window; once it has closed, the trace and the HLO text of the step
it ran give ``record["trace"]`` (bench/trace.py) and ``record["phases"]``
(bench/phases.py): device ms a step by phase, under each of the mixer's
scopes, and device idle ms under the program's host spans.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import re
import shutil
import statistics
import sys
import tempfile
import time
from typing import Callable, Optional
from unittest import mock

from bench import CellFailed, archs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CHECK_STEPS = 3


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    cfg: dict
    traffic: dict
    bench: dict

    @property
    def chips(self) -> int:
        return self.workload["chips"]

    def per_layer(self):
        """This cell's per-layer metrics: those that list it, or that
        list no cells and move an end-to-end metric it reports."""
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if self.name in m.get("workloads", ())
                or ("workloads" not in m and m["moves"] in e2e)]

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", (self.name,))]


def find_cell(name: str) -> Cell:
    bench = load_json(ROOT, "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise CellFailed(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(name, w, load_json(ROOT, conf["file"]),
                load_json(BENCH, "traffic", w["traffic"] + ".json"), bench)


def metric_reader(name: str) -> Callable[[dict], Optional[float]]:
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_devices(jax, chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise CellFailed(f"JAX finds no TPU (platform "
                         f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise CellFailed(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    peaks = load_json(BENCH, "peaks.json")
    if devs[0].device_kind not in peaks:
        raise CellFailed(f"no peaks for device kind "
                         f"{devs[0].device_kind!r} in bench/peaks.json")
    return peaks[devs[0].device_kind]


def launcher_argv(cell: Cell) -> list:
    cfg, tr, plan = cell.cfg, cell.traffic, cell.cfg["plan"]
    dp = cell.chips // (plan["pp"] * plan["tp"])
    rows = tr["microbatches"] * tr["rows_per_microbatch"] * dp
    return ["--arch", cfg["arch"],
            "--layers", str(cfg["num_hidden_layers"]),
            "--pp", str(plan["pp"]), "--tp", str(plan["tp"]),
            "--schedule", plan["schedule"],
            "--microbatches", str(tr["microbatches"]),
            "--global-batch", str(rows),
            "--seq-len", str(tr["seq_len"]), "--dtype", cfg["dtype"],
            *cfg.get("launcher_args", ())]


def check_build(cell: Cell, spec, bundle):
    """The program has to run what the configuration states."""
    cfg, plan = cell.cfg, cell.cfg["plan"]
    d = cfg["hidden_size"]
    want = {"d_model": d, "n_layers": cfg["num_hidden_layers"],
            "vocab": cfg["vocab_size"], "d_ff": cfg["intermediate_size"],
            "schedule": plan["schedule"], "stash_mode": plan["stash_mode"],
            "remat": plan["remat"], "pp": plan["pp"], "tp": plan["tp"]}
    got = {"d_model": spec.d_model, "n_layers": spec.n_layers,
           "vocab": spec.vocab, "d_ff": spec.d_ff,
           "schedule": bundle.sched.name,
           "stash_mode": bundle.plan.stash_mode,
           "remat": bundle.plan.remat, "pp": bundle.plan.pp,
           "tp": bundle.plan.tp}
    mixer_want, mixer_got = archs.load(cfg).spec_check(cfg, spec)
    want.update(mixer_want)
    got.update(mixer_got)
    opt = bundle.optimizer
    for k in ("lr", "b1", "b2", "eps"):
        want[k], got[k] = cfg["optimizer"][k], getattr(opt, k)
    diff = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if diff:
        raise CellFailed(f"the program departs from the configuration "
                         f"(program, configuration): {diff}")


def check_dtypes(cfg: dict, shapes):
    """The program has to keep its weights in the dtypes the configuration
    states: ``param_dtype``, float32 for ``float32_params``."""
    import jax

    from bench import weights
    keep = [re.compile(p) for p in cfg.get("keep", ())]
    wide = [re.compile(p) for p in cfg.get("float32_params", ())]
    bad = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        name = weights.leaf_name(path)
        if any(p.search(name) for p in keep):
            continue
        want = ("float32" if any(p.search(name) for p in wide)
                else cfg["param_dtype"])
        if str(leaf.dtype) != want:
            bad[name] = (str(leaf.dtype), want)
    if bad:
        raise CellFailed(f"the program keeps weights in other dtypes than "
                         f"the configuration states (program, "
                         f"configuration): {bad}")


def make_state(jax, bundle, cfg, key):
    """The program's own init_state, with every weight drawn by
    bench/weights.py from ``key`` in one jitted call on the device."""
    from repro.core import pipeline

    from bench import weights
    made = pipeline.init_params

    def seeded(spec, plan, k, dtype):
        params, pspecs = made(spec, plan, k, dtype)
        return weights.generate(cfg, k, params), pspecs

    # a function of its own, so that no trace of init_state made without
    # the patch (jax.eval_shape shares jit's trace cache) is reused
    with mock.patch.object(pipeline, "init_params", seeded):
        return jax.jit(lambda k: bundle.init_state(k),
                       out_shardings=bundle.state_shardings())(key)


class AnnotatedLoader:
    """The program's ShardedLoader, inside a host span of its own."""

    def __init__(self, inner):
        self.inner, self.source = inner, inner.source

    def get(self, step):
        import jax
        with jax.profiler.TraceAnnotation("bench.loader"):
            return self.inner.get(step)


def program_readings(jax, cfg):
    """Jitted readers of the program's state in the reference's naming:
    Adam's first moment (after round one) and each leaf's change from the
    seed's weights (after the checked rounds)."""
    from bench import reference
    n, pp = cfg["num_hidden_layers"], cfg["plan"]["pp"]

    # inside jit, so that the layout's slices fuse into the reductions
    # and copy nothing
    def grads(state):
        return reference.leaf_norms(reference.to_reference(
            {"stages": state["opt_stages"]["m"],
             "head": state["opt_head"]["m"]["h"],
             "final_norm": state["opt_head"]["m"]["f"],
             "embed": state["opt_embed"]["m"]}, n, pp))

    def change(key, params):
        return reference.change_norms(cfg, key, params,
                                      reference.to_reference(params, n, pp))

    floats = lambda d: {k: float(v) for k, v in d.items()}
    return (lambda s: {"grad": floats(jax.jit(grads)(s))},
            lambda k, p: floats(jax.jit(change)(k, p)))


def compile_counter(jax):
    """A list that grows by one for every XLA compilation."""
    seen = []

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)
    jax.monitoring.register_event_duration_secs_listener(listener)
    return seen


class Program:
    """The system under test for one cell: the launcher-built step, the
    benchmark's loader around the program's ShardedLoader, and the
    program's TrainDriver with restarts and checkpoints off."""

    def __init__(self, cell: Cell, *, require_chip: bool = True,
                 break_step: Optional[Callable] = None):
        sys.path.insert(0, os.path.join(ROOT, "src"))
        import jax

        from repro.launch import train
        self.jax, self.cell, self.cfg = jax, cell, cell.cfg
        self.peak = (check_devices(jax, cell.chips) if require_chip
                     else load_json(BENCH, "peaks.json")["TPU v5 lite"])
        train.enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.compiles = compile_counter(jax)
        self.devices = jax.devices()[:cell.chips]
        args = train.parser().parse_args(launcher_argv(cell))
        with contextlib.redirect_stdout(sys.stderr):
            spec, bundle = train.build(args, devices=self.devices)
            print(train.describe(spec, bundle))
        check_build(cell, spec, bundle)
        self.shapes = jax.eval_shape(bundle.init_state,
                                     jax.random.key(0))["params"]
        check_dtypes(cell.cfg, self.shapes)
        if break_step is not None:
            bundle = dataclasses.replace(
                bundle, train_step=break_step(bundle.train_step))
        self.bundle = bundle
        self.rows = bundle.batch_shapes["tokens"].shape[1]
        self.read_grads, self.read_change = program_readings(jax, self.cfg)
        self.ckpt = tempfile.mkdtemp(prefix="bench-ckpt-")
        self.state = self.driver = None
        self.references = {}

    def close(self):
        self.state = self.driver = self.references = None
        gc.collect()
        shutil.rmtree(self.ckpt, ignore_errors=True)

    def batches(self, steps: int):
        """The started seed's first ``steps`` rounds, as the loader drew
        them."""
        return [self.source.round_batch(i, self.cell.traffic["microbatches"],
                                        self.rows) for i in range(steps)]

    def start(self, seed: int) -> dict:
        """State from the seed and the checked steps through the window's
        own driver and loader; returns the program's readings."""
        from bench import data, weights
        from repro.data.pipeline import ShardedLoader
        from repro.runtime.driver import DriverConfig, TrainDriver
        self.key = weights.seed_key(seed)
        self.source = data.TrafficSource(seed, self.cell.traffic,
                                         self.cfg["vocab_size"])
        loader = AnnotatedLoader(ShardedLoader(self.source,
                                               self.bundle.batch_specs()))
        self.driver = TrainDriver(self.bundle, loader, self.ckpt,
                                  DriverConfig(checkpoint_every=1 << 62,
                                               max_restarts=0))
        state = make_state(self.jax, self.bundle, self.cfg, self.key)
        state, self.step = self.driver.run(state, 1, 0)
        prog = self.read_grads(state)
        # one round a call, as the window runs them: on a TPU v5 lite
        # danube3-4b ran out of device memory in the second round of a
        # call that ran several
        for step in range(1, CHECK_STEPS):
            state, self.step = self.driver.run(state, step + 1, step)
        prog["change"] = self.read_change(self.key, state["params"])
        prog["loss"] = [m["loss"]
                        for m in self.driver.metrics_log[:CHECK_STEPS]]
        self.state = state
        return prog

    def window(self, seconds: float, trace: bool) -> dict:
        """Steps back to back for ``seconds``; returns the record that the
        metric readers take.  Frees the program's state at the end."""
        jax, driver = self.jax, self.driver
        trace_dir = None
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        n_compiles = len(self.compiles)
        state, step = self.state, self.step
        self.state = None
        t0 = time.perf_counter()
        while True:
            with jax.profiler.StepTraceAnnotation("bench.step",
                                                  step_num=step):
                state, step = driver.run(state, step + 1, step)
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
        if trace_dir:
            jax.profiler.stop_trace()
        if driver.restarts:
            raise CellFailed(f"the driver restarted: {driver.faults}")
        stats = [d.memory_stats() or {} for d in self.devices]
        del state
        gc.collect()
        from bench import flops
        tr = self.cell.traffic
        record = {
            "t_window": t0, "chips": self.cell.chips, "peak": self.peak,
            "steps": step - CHECK_STEPS, "window_s": t1 - t0,
            "tokens_per_step": int(math.prod(
                self.bundle.batch_shapes["tokens"].shape)),
            "flops_per_token": flops.train_flops_per_token(self.cfg,
                                                           tr["seq_len"]),
            "first_step_s": driver.stage_times[0],
            "step_times": driver.stage_times[CHECK_STEPS:],
            "losses": [m["loss"] for m in driver.metrics_log],
            "compiles": len(self.compiles) - n_compiles,
            "memory": stats, "trace": None, "phases": None}
        print(f"memory after the window: {stats}", file=sys.stderr)
        if trace_dir:
            from bench import phases
            from bench import trace as trace_lib
            try:
                devices, host = trace_lib.load(trace_dir)
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)
            record["trace"] = trace_lib.reduce(devices, host)
            named = phases.with_op_names(
                devices, phases.hlo_op_names(self.step_hlo()))
            r = phases.reduce(named, host, archs.load(self.cfg).SCOPES)
            if record["steps"]:
                print(phases.table(r, record["steps"]), file=sys.stderr)
                record["phases"] = phases.per_step_ms(r, record["steps"])
        return record

    def step_hlo(self) -> str:
        """The HLO text of the step the window ran: the driver's own
        jitted step, lowered for the state's and the batch's shapes, so
        that its instruction names are those of the trace."""
        jax, b = self.jax, self.bundle
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            jax.eval_shape(b.init_state, jax.random.key(0)),
            b.state_shardings())
        return self.driver._jit_step.lower(
            state, b.batch_specs()).compile().as_text()


def reference_readings(program: Program, *, fp8: bool = False,
                       **kw) -> dict:
    """The plain reference over the checked steps of the started seed;
    one Reference (its jitted pieces) for each precision per program."""
    from bench import reference
    if fp8 not in program.references:
        program.references[fp8] = reference.Reference(program.cfg, fp8=fp8)
    return program.references[fp8].train(
        program.key, program.shapes, program.batches(CHECK_STEPS), **kw)


def judge(cfg: dict, prog: dict, ref: dict):
    """(correct, checks, gaps): every number compared beside its limit."""
    from bench import reference
    gap = reference.gaps(prog, ref)
    checks = {k: {"value": gap[k], "limit": lim}
              for k, lim in cfg["limits"].items()}
    correct = all(c["limit"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    return correct, checks, gap


def run(argv_ns, *, t_start: float, require_chip: bool = True,
        break_step: Optional[Callable] = None) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``break_step(train_step) -> train_step`` plants a fault in the timed
    path and ``require_chip=False`` skips the look for a TPU: both for
    the benchmark's own tests only."""
    cell = find_cell(argv_ns.workload)
    program = Program(cell, require_chip=require_chip,
                      break_step=break_step)
    try:
        prog = program.start(argv_ns.seed)
        record = program.window(argv_ns.seconds, bool(argv_ns.trace))
        setup_s = record["t_window"] - t_start
        print(f"setup_s {setup_s!r}; window: {record['steps']} steps in "
              f"{record['window_s']!r} s; compilations in the window "
              f"{record['compiles']}; losses {record['losses']}",
              file=sys.stderr)
        # the check, once the window has closed and the state is freed
        t_ref = time.perf_counter()
        ref = reference_readings(program)
        print(f"reference: {time.perf_counter() - t_ref!r} s, losses "
              f"{ref['loss']} (program {prog['loss']})", file=sys.stderr)
    finally:
        program.close()
    correct, checks, gap = judge(cell.cfg, prog, ref)
    correct = correct and record["steps"] > 0

    if argv_ns.trace:
        metrics = {}
        for m in cell.per_layer():
            value = metric_reader(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"tokens_per_s": record["steps"] * record["tokens_per_step"]
                  / record["window_s"], "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end()}
    d0 = program.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(program.jax.devices()),
              "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0)
                                       for s in record["memory"])}
    result = {"correct": bool(correct), "attempted": record["steps"],
              "failed": 0, "metrics": metrics, "device": device}
    if record["trace"]:
        t = record["trace"]
        device["busy_s"] = statistics.mean(v["busy_s"]
                                           for v in t["devices"].values())
        device["window_s"] = t["window_s"]
        result["breakdown"] = {"device_ops": t["device_ops"],
                               "idle_gaps": t["idle_gaps"]}
    result["checks"] = checks
    for k, c in checks.items():
        at = f" (at {gap['at'][k]})" if k in gap["at"] else ""
        print(f"check {k} {c['value']!r} limit {c['limit']!r}{at}",
              file=sys.stderr)
    return result
