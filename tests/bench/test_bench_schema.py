"""BENCHMARK.json keeps the contract's names, units and shapes, and every
cell resolves to files of its own."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
NUMBERS = {"loss_gap", "grad_gap", "grad_median_gap", "change_gap",
           "change_median_gap"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) <= 64 * 1024


def test_names_and_units(bench):
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert (group, entry["name"]) not in seen
            seen.add((group, entry["name"]))
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}


def test_every_cell_resolves_to_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        cfg = load(configs[w["config"]]["file"])
        assert cfg["num_hidden_layers"] % cfg["plan"]["pp"] == 0
        assert w["chips"] % (cfg["plan"]["pp"] * cfg["plan"]["tp"]) == 0
        assert cfg["limits"] and set(cfg["limits"]) <= NUMBERS
        assert all(v > 0 for v in cfg["limits"].values())
        tr = load("bench", "traffic", w["traffic"] + ".json")
        assert tr["seq_len"] > 0 and tr["microbatches"] > 0
        used.add(w["config"])
    assert used == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= \
        max(1, len(bench["workloads"]) // 2)
    for m in bench["end_to_end"] + bench["per_layer"]:
        for cell in m.get("workloads", ()):
            assert cell in {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))


def test_configs_name_their_cuts(bench):
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    widths = re.compile(r"hidden|intermediate|latent|state|projection|"
                        r"_dim$|_rank$|head|expansion|experts_per_tok")
    for c in bench["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert c["source"].startswith("https://")
        cfg = load(c["file"])
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        for key in c["reduced"]:
            assert NAME.match(key)
            assert key == "num_hidden_layers" or not widths.search(key)


def test_check_budget_fits(bench):
    cells = 24
    runs = 2 + 14 * cells
    total = runs * (bench["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert total <= 43200


def test_every_cell_reports_what_the_contract_asks(bench):
    import sys
    sys.path.insert(0, ROOT)
    from bench import harness
    for w in bench["workloads"]:
        cell = harness.find_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end()}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer()
