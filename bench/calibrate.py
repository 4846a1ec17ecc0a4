#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip, in one process.

For each seed: the program's checked steps against the plain reference
(the lower readings).  For the first ``--control`` seeds also the control,
the reference computed with float8 matrix products put in the program's
place, and two planted faults, each in the reference put in the
program's place: "half of the batch left out, the mean taken over the
rest" and "the optimizer's state dropped between steps" (the upper
readings).  One JSON line per reading goes to ``--out``.  Last, the
memory that XLA plans for the driver's own compiled step.

  python3 bench/calibrate.py --workload danube3-4b.train-4k \
      --seed 5000 --seeds 12 --control 3 --out cal.jsonl

The benchmark's own runs never run this.
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness  # noqa: E402

NUMBERS = ("loss_gap", "grad_gap", "grad_median_gap", "change_gap",
           "change_median_gap")


def step_memory(program) -> dict:
    """XLA's plan for the driver's compiled step, per device."""
    import jax
    shapes = jax.eval_shape(program.bundle.init_state, jax.random.key(0))
    ma = program.driver._jit_step.lower(
        shapes, program.bundle.batch_shapes).compile().memory_analysis()
    return {"argument": ma.argument_size_in_bytes,
            "output": ma.output_size_in_bytes,
            "alias": ma.alias_size_in_bytes,
            "temp": ma.temp_size_in_bytes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True, help="first seed")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3,
                    help="seeds that also run the control and the fault")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    program = harness.Program(cell)
    half = cell.traffic["microbatches"] // 2
    rows = []
    with open(args.out, "a") as out:
        from bench import reference

        def emit(seed, kind, prog, ref, seconds):
            gap = reference.gaps(prog, ref)
            row = {"workload": cell.name, "seed": seed, "kind": kind,
                   "seconds": seconds, **gap, "readings": prog,
                   "reference": ref}
            rows.append(row)
            out.write(json.dumps(row) + "\n")
            out.flush()
            print(kind, seed, {k: gap[k] for k in NUMBERS}, gap["at"],
                  flush=True)

        # every seed's program steps first, so that no program of the
        # reference's runs on the device before the program's steps
        started = []
        for i in range(args.seeds):
            t0 = time.perf_counter()
            prog = program.start(args.seed + i)
            program.state = None
            gc.collect()
            started.append((args.seed + i, prog, program.key,
                            program.source, time.perf_counter() - t0))
        memory = step_memory(program)
        for i, (seed, prog, program.key, program.source, t_prog) in \
                enumerate(started):
            t0 = time.perf_counter()
            ref = harness.reference_readings(program)
            emit(seed, "program", prog, ref,
                 [t_prog, time.perf_counter() - t0])
            if i < args.control:
                t0 = time.perf_counter()
                ctl = harness.reference_readings(program, fp8=True)
                emit(seed, "control_fp8", ctl, ref,
                     [time.perf_counter() - t0])
                t0 = time.perf_counter()
                flt = harness.reference_readings(
                    program, microbatches_used=half)
                emit(seed, "fault_half_batch", flt, ref,
                     [time.perf_counter() - t0])
                t0 = time.perf_counter()
                flt = harness.reference_readings(program, drop_state=True)
                emit(seed, "fault_state_dropped", flt, ref,
                     [time.perf_counter() - t0])
    print("memory:", json.dumps(memory), flush=True)
    program.close()
    for kind in sorted({r["kind"] for r in rows}):
        sel = [r for r in rows if r["kind"] == kind]
        agg = max if kind == "program" else min
        print(f"{kind} over {len(sel)} seeds, "
              f"{'largest' if agg is max else 'smallest'}: "
              + ", ".join(f"{k} {agg(r[k] for r in sel)!r}"
                          for k in NUMBERS))
    return 0


if __name__ == "__main__":
    sys.exit(main())
