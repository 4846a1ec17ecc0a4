"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The trace is read with ``jax.profiler.ProfileData`` here and nowhere
else.  A device plane ("/device:TPU:<n>") has a line of XLA operations;
the busy time of a device is the length of the union of its operation
intervals inside the window, its idle share is one less busy over the
window, and its collective time is the union of the intervals of the
operations that XLA names as collectives.  The window runs from the
start of the first ``bench.step`` host span to the end of the last.  An
idle gap on a device is labelled with the innermost host span on the
benchmark's own thread that covers the gap's midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

STEP_SPAN = "bench.step"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|ragged-all-to-all", re.I)
TOP = 10

Interval = Tuple[float, float]          # (start, end), nanoseconds


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Merged intervals clipped to [lo, hi], in order."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] around merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    """Innermost host span that covers the gap's midpoint."""
    mid = (gap[0] + gap[1]) / 2
    inside = [(e - s, n) for n, s, e in spans if s <= mid <= e]
    return min(inside)[1] if inside else "outside the benchmark's spans"


def op_name(name: str) -> str:
    """XLA names an op event by its whole HLO instruction; keep the
    instruction's name ("%fusion.12 = (...) fusion(...)" -> "fusion.12")."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(ops: Sequence[Tuple[str, float, float]], lo: float,
               hi: float) -> Dict[str, float]:
    """Nanoseconds inside [lo, hi] in which each op ran and none of the
    ops nested in it did (a while loop's body ops are nested in it)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []          # [name, end, covered-by-children]

    def close(frame, s, e):
        out[frame[0]] += max(0.0, min(e, hi) - max(s, lo) - frame[2])

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            frame = stack.pop()
            close(frame, frame[3], frame[1])
        if stack:
            stack[-1][2] += max(0.0, min(e, hi) - max(s, lo))
        stack.append([name, e, 0.0, s])
    while stack:
        frame = stack.pop()
        close(frame, frame[3], frame[1])
    return out


def reduce(devices: Dict[int, List[Tuple[str, float, float]]],
           host: List[Tuple[str, float, float]]) -> dict:
    """devices: {id: [(op name, start_ns, end_ns)]}; host: the spans of the
    thread that ran the window, [(name, start_ns, end_ns)].

    Returns window_s, per-device busy_s / idle_share / collective_s, the
    device ops that took most time (seconds per device, averaged) and the
    longest idle gaps of device 0 by host label.  An op's time is its
    self time: what ops nested in it ran is theirs."""
    steps = [(s, e) for n, s, e in host if n == STEP_SPAN]
    if not steps or not devices:
        raise ValueError("the trace holds no bench.step span or no device")
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    window = hi - lo
    per_device, op_time = {}, defaultdict(float)
    first = min(devices)
    idle_gaps: List[Tuple[str, float]] = []
    for dev, ops in sorted(devices.items()):
        busy = union([(s, e) for _, s, e in ops], lo, hi)
        coll = union([(s, e) for n, s, e in ops if COLLECTIVE.search(n)],
                     lo, hi)
        per_device[dev] = {"busy_s": length(busy) / 1e9,
                           "idle_share": 1.0 - length(busy) / window,
                           "collective_s": length(coll) / 1e9}
        for n, t in self_times(ops, lo, hi).items():
            op_time[n] += t / 1e9 / len(devices)
        if dev == first:
            idle_gaps = sorted(((label(g, host), (g[1] - g[0]) / 1e9)
                                for g in gaps(busy, lo, hi)),
                               key=lambda x: -x[1])[:TOP]
    top = sorted(op_time.items(), key=lambda x: -x[1])[:TOP]
    return {"window_s": window / 1e9, "devices": per_device,
            "device_ops": [[n, t] for n, t in top],
            "idle_gaps": [[n, t] for n, t in idle_gaps]}


def load(trace_dir: str) -> Tuple[dict, list]:
    """(devices, host spans) from the newest .xplane.pb under trace_dir."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(files[-1]))


def from_profile(pd) -> Tuple[dict, list]:
    devices: Dict[int, list] = {}
    host: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns)
                        for e in line.events if e.duration_ns > 0]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events]
                if any(n == STEP_SPAN for n, _, _ in events):
                    host = events
    return devices, host
