"""Subprocess worker: the op_name metadata of a compiled pipelined step.

Usage: scopes_check.py PP SCHEDULE

Builds the h2o-danube3-4b smoke step through ``launch/train.py::build``
on PP emulated host devices with the given schedule, compiles it, and
prints one JSON line: the ``op_name`` of every collective-permute in the
compiled HLO, and every ``op_name`` on the step's instructions.
"""
import sys

pp, schedule = int(sys.argv[1]), sys.argv[2]

import os  # noqa: E402

os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={pp}"

import json  # noqa: E402
import re  # noqa: E402

import jax  # noqa: E402

from repro.launch import train  # noqa: E402

OP = re.compile(r'^\s*(?:ROOT )?%?\S+ = \S+ ([\w-]+)\(.*?'
                r'\bop_name="((?:[^"\\]|\\.)*)"', re.M)

args = train.parser().parse_args(
    ["--arch", "h2o-danube3-4b", "--smoke", "--pp", str(pp), "--tp", "1",
     "--schedule", schedule, "--microbatches", "2", "--global-batch", "2",
     "--seq-len", "16"])
spec, bundle = train.build(args)
shapes = jax.eval_shape(bundle.init_state, jax.random.key(0))
state = jax.tree.map(
    lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
    shapes, bundle.state_shardings())
text = jax.jit(bundle.train_step).lower(
    state, bundle.batch_specs()).compile().as_text()
ops = OP.findall(text)
print(json.dumps({
    "permutes": [n for op, n in ops if op.startswith("collective-permute")],
    "op_names": sorted({n for _, n in ops})}))
