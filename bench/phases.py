"""Device time by training phase and sequence mixer, and device idle time
by the program's host spans, from a JAX profiler trace of the window.

It reads the names the program gives its own work (``repro/obs``):

* device scopes, which reach each HLO op's ``op_name`` metadata.  An
  op's phase is the first of PHASES on its ``op_name`` path, once the
  autodiff and batching wrappers (``jvp(``, ``transpose(``, ``vmap(``)
  are stripped; an op under none is OTHER.  An op is in the mixer if
  any of MIXERS is on its path, so the mixer cuts across ``fwd`` and
  ``bwd``.  Time is each op's self time (bench/trace.py), so the
  phases and OTHER add up to the device's busy time;
* host spans of the program (``train.*``, ``loader.*``) on the thread
  that ran the window.  Each device-idle interval is split among the
  innermost program spans over it, by overlap; what no program span
  covers is UNATTRIBUTED.

The names are the benchmark's own copy of the program's, so that no
change to the program can move what is measured; the tests hold the
two copies equal.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace

PHASES = ("embed", "fwd", "head", "bwd", "optimizer")
MIXERS = ("attention", "wkv")
OTHER = "other"
UNATTRIBUTED = "unattributed"
PROGRAM_SPAN = re.compile(r"^(?:train|loader)\.")
INPUT_SPANS = ("train.load", "loader.draw", "loader.place")
DISPATCH_SPAN = "train.dispatch"
_WRAPPER = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
# an HLO instruction of the compiled module's text: its name and its
# first operand; and the op_name of its metadata
_HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%?([^\s=]+) = (?:\(.*?\)|\S+) "
                        r"[\w-]+\((?:%([^\s,)]+))?")
_HLO_OP_NAME = re.compile(r'metadata=\{op_name="((?:[^"\\]|\\.)*)"')

Op = Tuple[str, float, float, str]     # (instruction, start, end, op_name)


def scopes(op_name: str) -> List[str]:
    """The parts of an ``op_name`` path with wrappers stripped:
    "jit(f)/bwd/transpose(jvp(attention))/dot" -> [.., "bwd",
    "attention", "dot"]."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPER.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPER.match(part)
        out.append(part)
    return out


def phase_of(op_name: str) -> str:
    return next((p for p in scopes(op_name) if p in PHASES), OTHER)


def in_mixer(op_name: str) -> bool:
    return any(p in MIXERS for p in scopes(op_name))


def idle_by_span(idle: Sequence[trace.Interval],
                 spans: Sequence[Tuple[str, float, float]]
                 ) -> Dict[str, float]:
    """Nanoseconds of ``idle`` under each innermost span (the shortest
    that covers a piece), UNATTRIBUTED where none does."""
    out: Dict[str, float] = defaultdict(float)
    for a, b in idle:
        over = [(n, s, e) for n, s, e in spans if s < b and e > a]
        cuts = sorted({a, b} | {t for _, s, e in over for t in (s, e)
                                if a < t < b})
        for s0, s1 in zip(cuts, cuts[1:]):
            mid = (s0 + s1) / 2
            inside = [(e - s, n) for n, s, e in over if s <= mid <= e]
            out[min(inside)[1] if inside else UNATTRIBUTED] += s1 - s0
    return dict(out)


def reduce(devices: Dict[int, List[Op]],
           host: List[Tuple[str, float, float]]) -> dict:
    """devices: {id: [(instruction, start_ns, end_ns, op_name)]}; host:
    the spans of the thread that ran the window.

    Returns seconds inside the window (from the first ``bench.step`` to
    the end of the last), per device and averaged over devices: busy,
    idle, each phase and OTHER, the mixer, and idle by program span."""
    steps = [(s, e) for n, s, e in host if n == trace.STEP_SPAN]
    if not steps or not devices:
        raise ValueError("the trace holds no bench.step span or no device")
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    spans = [(n, s, e) for n, s, e in host if PROGRAM_SPAN.match(n)]
    per_device = {}
    for dev, ops in sorted(devices.items()):
        names = {i: o for i, _, _, o in ops}
        phases = dict.fromkeys(PHASES + (OTHER,), 0.0)
        mixer = 0.0
        for i, t in trace.self_times([(i, s, e) for i, s, e, _ in ops],
                                     lo, hi).items():
            phases[phase_of(names[i])] += t / 1e9
            mixer += t / 1e9 if in_mixer(names[i]) else 0.0
        busy = trace.union([(s, e) for _, s, e, _ in ops], lo, hi)
        idle = trace.gaps(busy, lo, hi)
        per_device[dev] = {
            "busy_s": trace.length(busy) / 1e9,
            "idle_s": trace.length(idle) / 1e9, "phases": phases,
            "mixer_s": mixer,
            "idle_by_span": {n: t / 1e9 for n, t in
                             idle_by_span(idle, spans).items()}}
    n = len(per_device)

    def mean(get):
        return sum(get(d) for d in per_device.values()) / n

    keys = {k for d in per_device.values() for k in d["idle_by_span"]}
    return {"window_s": (hi - lo) / 1e9, "devices": per_device,
            "busy_s": mean(lambda d: d["busy_s"]),
            "idle_s": mean(lambda d: d["idle_s"]),
            "phases": {p: mean(lambda d: d["phases"][p])
                       for p in PHASES + (OTHER,)},
            "mixer_s": mean(lambda d: d["mixer_s"]),
            "idle_by_span": {k: mean(lambda d: d["idle_by_span"].get(k, 0.0))
                             for k in sorted(keys)}}


def per_step_ms(r: dict, steps: int) -> Dict[str, Optional[float]]:
    """The per-layer numbers, device milliseconds a window step."""
    if not steps:
        return {}
    ms = lambda s: 1e3 * s / steps
    idle = r["idle_by_span"]
    return {"fwd_ms": ms(r["phases"]["fwd"]),
            "bwd_ms": ms(r["phases"]["bwd"]),
            "head_ms": ms(r["phases"]["head"]),
            "optimizer_ms": ms(r["phases"]["optimizer"]),
            "mixer_ms": ms(r["mixer_s"]),
            "input_idle_ms": ms(sum(idle.get(k, 0.0) for k in INPUT_SPANS)),
            "dispatch_idle_ms": ms(idle.get(DISPATCH_SPAN, 0.0))}


def table(r: dict, steps: int) -> str:
    """The reduction as text: ms a step, per device."""
    lines = [f"window {r['window_s']!r} s, {steps} steps; ms a step:"]
    for dev, d in sorted(r["devices"].items()):
        row = [f"{p} {1e3 * t / steps:.3f}" for p, t in d["phases"].items()]
        row += [f"mixer {1e3 * d['mixer_s'] / steps:.3f}",
                f"idle {1e3 * d['idle_s'] / steps:.3f}"]
        lines.append(f"  device {dev}: " + ", ".join(row))
        lines.append("    idle by span: " + ", ".join(
            f"{k} {1e3 * t / steps:.3f}"
            for k, t in sorted(d["idle_by_span"].items(),
                               key=lambda x: -x[1])))
    return "\n".join(lines)


def hlo_op_names(text: str) -> Dict[str, str]:
    """{instruction name: op_name} of a compiled module's HLO text.

    An instruction that XLA made and left without metadata (a layout
    copy, an async copy's halves, a reshape of the head's logits) does
    its first operand's work: it takes that operand's op_name, through
    any chain of such instructions."""
    names: Dict[str, str] = {}
    operand: Dict[str, str] = {}
    for line in text.splitlines():
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        op = _HLO_OP_NAME.search(line)
        if op:
            names[m.group(1)] = op.group(1).replace('\\"', '"')
        elif m.group(2):
            operand[m.group(1)] = m.group(2)
    for name in operand:
        seen, src = {name}, operand[name]
        while src not in names and src in operand and src not in seen:
            seen.add(src)
            src = operand[src]
        if src in names:
            names[name] = names[src]
    return names


def with_op_names(devices: Dict[int, list], op_names: Dict[str, str]
                  ) -> Dict[int, List[Op]]:
    """bench/trace.py's device ops, each with its instruction's op_name
    ("" where it has none)."""
    return {d: [(i, s, e, op_names.get(i, "")) for i, s, e in ops]
            for d, ops in devices.items()}


def load(trace_dir: str, hlo_text: str) -> Tuple[dict, list]:
    """(devices, host spans) as :func:`reduce` takes them, from the
    newest .xplane.pb under trace_dir and the HLO text of the step it
    ran (``compiled.as_text()``)."""
    devices, host = trace.load(trace_dir)
    return with_op_names(devices, hlo_op_names(hlo_text)), host
