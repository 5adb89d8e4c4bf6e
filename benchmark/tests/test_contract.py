"""``BENCHMARK.json`` against the files it names and the contract's shape."""

import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"] and 1 <= bench["run_seconds"] <= 51
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        group = [e["name"] for e in bench[kind]]
        assert len(group) == len(set(group))
        names += group
    for w in bench["workloads"]:
        names += [w["config"], w["traffic"]]
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
        assert os.path.exists(os.path.join(BENCH, "end_to_end", m["name"] + ".json"))
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]


def test_every_entry_has_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        used.add(w["config"])
        assert os.path.exists(os.path.join(ROOT, configs[w["config"]]["file"]))
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            cell = json.load(f)
        assert cell["config"] == w["config"] and cell["traffic"] == w["traffic"]
        assert cell["limits"]
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        for group in traffic["groups"]:
            assert os.path.exists(os.path.join(BENCH, "statements", group["statement"] + ".py"))
    assert used == set(configs)
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            assert set(c["reduced"]) == set(json.load(f)["reduced"])


def test_every_layer_metric_has_a_reader_and_moves_what_its_cells_report(bench):
    cells = [w["name"] for w in bench["workloads"]]
    reported = {m["name"]: set(m.get("workloads", cells)) for m in bench["end_to_end"]}
    layers = set()
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        with open(os.path.join(BENCH, "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(BENCH, "reducers", spec["reducer"] + ".py"))
        assert set(m.get("workloads", cells)) <= reported[m["moves"]]
        layers.add(m["layer"])
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in bench["per_layer"])
        assert sum(cell in r for r in reported.values()) >= 2  # setup_s and one more


def test_the_harness_names_no_cell_statement_or_metric(bench):
    with open(os.path.join(BENCH, "run.py")) as f:
        text = f.read()
    listed = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
              for e in bench[k] if e["name"] != "setup_s"]  # the harness's own clock
    listed += [os.path.splitext(f)[0] for f in os.listdir(os.path.join(BENCH, "statements"))]
    assert not [n for n in listed if n in text and n != "__pycache__"]
