"""The benchmark's trace reduction and the readers built on it."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, trace  # noqa: E402

HOST = [("bench.step", 0, 100), ("bench.loader", 0, 10),
        ("bench.step", 100, 200), ("bench.loader", 100, 130)]
DEVICES = {0: [("fusion.1", 10, 90), ("fusion.3", 95, 100),
               ("all-reduce.3", 140, 160),
               ("fusion.2", 160, 200), ("fusion.2", 200, 230)],
           1: [("fusion.1", 0, 200)]}


def test_union_merges_and_clips():
    assert trace.union([(5, 20), (0, 10), (30, 40), (35, 60)], 0, 50) == \
        [(0, 20), (30, 50)]
    assert trace.union([(60, 70)], 0, 50) == []


def test_gaps_cover_the_rest_of_the_window():
    busy = [(0, 20), (30, 40)]
    assert trace.gaps(busy, 0, 100) == [(20, 30), (40, 100)]
    assert trace.gaps([], 0, 5) == [(0, 5)]


def test_reduce_hand_built_intervals():
    r = trace.reduce(DEVICES, HOST)
    assert r["window_s"] == pytest.approx(200e-9)
    d0, d1 = r["devices"][0], r["devices"][1]
    assert d0["busy_s"] == pytest.approx(145e-9)
    assert d0["idle_share"] == pytest.approx(0.275)
    assert d0["collective_s"] == pytest.approx(20e-9)
    assert d1["idle_share"] == pytest.approx(0.0)
    assert d1["collective_s"] == 0
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(140e-9)
    assert ops["fusion.2"] == pytest.approx(20e-9)
    assert ops["all-reduce.3"] == pytest.approx(10e-9)
    assert [n for n, _ in r["device_ops"]][0] == "fusion.1"
    # device 0's gaps, longest first, labelled by the innermost host span
    assert r["idle_gaps"] == [["bench.loader", pytest.approx(40e-9)],
                              ["bench.loader", pytest.approx(10e-9)],
                              ["bench.step", pytest.approx(5e-9)]]


def test_reduce_needs_a_step_span_and_a_device():
    with pytest.raises(ValueError):
        trace.reduce(DEVICES, [("bench.loader", 0, 10)])
    with pytest.raises(ValueError):
        trace.reduce({}, HOST)


def record(tr):
    return {"trace": tr, "memory": [], "step_times": []}


def test_readers_from_a_reduced_trace():
    r = trace.reduce(DEVICES, HOST)
    idle = harness.metric_reader("device_idle_share")(record(r))
    assert idle == pytest.approx(13.75)


def test_readers_say_nothing_without_a_trace():
    assert harness.metric_reader("device_idle_share")(record(None)) is None
    assert harness.metric_reader("step_mfu")(
        {"steps": 0, "tokens_per_step": 1, "window_s": 1.0, "chips": 1,
         "peak": {"bf16_flops_per_s": 1.0}, "flops_per_token": 1.0}) is None


def test_recorded_chip_step():
    """One window step of danube3-4b.train-4k, recorded on a TPU v5 lite
    with --trace 1 (bench/testdata)."""
    import gzip
    import json
    path = os.path.join(ROOT, "bench", "testdata", "danube3-4b-step.json.gz")
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    devices = {int(k): [tuple(e) for e in v]
               for k, v in rec["devices"].items()}
    r = trace.reduce(devices, [tuple(e) for e in rec["host"]])
    assert r["window_s"] == pytest.approx(2.633775114)
    d0 = r["devices"][0]
    assert d0["busy_s"] == pytest.approx(2.62659374)
    assert d0["idle_share"] == pytest.approx(0.002726646615281214)
    assert d0["collective_s"] == 0
    # self times: the step's while loops hold everything and own little
    names = [n for n, _ in r["device_ops"]]
    assert names[0] == "subtract_select_fusion.61"
    assert not any(n.startswith("while") for n in names)
    assert sum(t for _, t in r["device_ops"]) < d0["busy_s"]
    assert r["idle_gaps"][0][0] == "bench.step"
    assert r["idle_gaps"][0][1] == pytest.approx(0.004185554)


def test_from_profile_reads_device_ops_and_the_step_thread():
    from types import SimpleNamespace as NS
    ev = lambda name, s, d: NS(name=name, start_ns=s, duration_ns=d)
    pd = NS(planes=[
        NS(name="/device:TPU:0", lines=[
            NS(name="Steps", events=[ev("0", 0, 100)]),
            NS(name="XLA Ops", events=[
                ev("%fusion.3 = bf16[8]{0} fusion(...)", 10, 20),
                ev("%copy.1 = f32[] copy(...)", 40, 0)])]),
        NS(name="/device:TPU:0 SparseCore", lines=[]),
        NS(name="/host:CPU", lines=[
            NS(name="other", events=[ev("x", 0, 5)]),
            NS(name="python3", events=[ev("bench.step", 0, 100),
                                       ev("bench.loader", 0, 5)])])])
    devices, host = trace.from_profile(pd)
    assert devices == {0: [("fusion.3", 10, 30)]}
    assert host == [("bench.step", 0, 100), ("bench.loader", 0, 5)]
