"""BENCHMARK.json keeps to the contract's shapes, and every name in it
finds its files."""
import json
import os
import re

import pytest

import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks"]
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(common.ROOT, "BENCHMARK.json")) < 64 * 1024


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("benchmarks/")
        with open(os.path.join(common.ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank")) and "hidden" not in key
        for text in (c["source"], c["why"]):
            assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_workloads(bench):
    names = [w["name"] for w in bench["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(names)) == len(names) and len(set(pairs)) == len(pairs)
    assert "serve_mixed_open_draw2" not in names
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] == 1
        assert 1 <= len(w["why"]) <= 200
        traffic = common.load_json("traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(common.HERE, "kinds", traffic["kind"] + ".py"))
        assert os.path.exists(os.path.join(common.HERE, "limits", w["name"] + ".json"))


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    every = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(every)) == len(every)
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert m["moves"] in e2e and m["workloads"] and set(m["workloads"]) <= cells
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:  # each cell reports the metric it moves
            assert cell in moved.get("workloads", cells)
        spec = common.load_json("metrics", m["name"] + ".json")
        assert os.path.exists(os.path.join(common.HERE, "readers", spec["reader"] + ".py"))
    for cell in cells:  # setup_s, another end-to-end metric, a per-layer one
        assert len(common.metric_names(bench, cell, "end_to_end")) >= 2
        assert common.metric_names(bench, cell, "per_layer")
    mfu = [m for m in bench["per_layer"] if "mfu" in re.split(r"[._]", m["name"])]
    assert mfu and all(m["unit"] == "%" for m in mfu)
