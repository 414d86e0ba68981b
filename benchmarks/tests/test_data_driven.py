"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric by adding files only: the harness finds each by its name."""
import json
import os
import shutil

import common


def test_added_files_are_found(tmp_path, monkeypatch):
    # a copy of the benchmark in which only NEW files and entries appear
    root = tmp_path / "checkout"
    shutil.copytree(common.HERE, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "records"))
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = {p: os.path.getmtime(p) for p in _files(root / "benchmarks")}

    b = root / "benchmarks"
    (b / "configs" / "new_cfg.json").write_text(json.dumps(
        {"name": "new_cfg", "builder": "new_builder", "reduced": []}))
    (b / "builders" / "new_builder.py").write_text(
        "def build(config, dry):\n    return {'hello': config['name']}\n")
    (b / "traffic" / "new_mix.json").write_text(json.dumps({"kind": "new_kind"}))
    (b / "kinds" / "new_kind.py").write_text(
        "def run(ctx):\n    return {'facts': {'x': 2.0}}\n")
    (b / "metrics" / "new.metric.json").write_text(json.dumps(
        {"reader": "fact", "args": {"key": "x", "scale": 10}}))
    (b / "limits" / "new_cell.json").write_text(json.dumps({"limits": {}}))
    bench["configs"].append({"name": "new_cfg", "source": "s", "why": "w",
                             "file": "benchmarks/configs/new_cfg.json", "reduced": []})
    bench["workloads"].append({"name": "new_cell", "config": "new_cfg",
                               "traffic": "new_mix", "chips": 1, "why": "w"})
    bench["per_layer"].append({"name": "new.metric", "unit": "x", "better": "lower",
                               "source": "program_counter", "layer": "l",
                               "moves": "setup_s", "workloads": ["new_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    monkeypatch.setattr(common, "HERE", str(b))
    monkeypatch.setattr(common, "ROOT", str(root))
    monkeypatch.syspath_prepend(str(b))
    _, cell, config, traffic = common.load_cell("new_cell", str(root))
    assert cell["traffic"] == "new_mix" and config["builder"] == "new_builder"
    built = common.module("builders", config["builder"]).build(config, True)
    assert built == {"hello": "new_cfg"}
    out = common.module("kinds", traffic["kind"]).run({})
    spec = common.load_json("metrics", "new.metric.json")
    value = common.module("readers", spec["reader"]).read(out["facts"], spec["args"])
    assert value == 20.0
    assert "new.metric" in common.metric_names(bench, "new_cell", "per_layer")
    assert "new.metric" not in common.metric_names(bench, "train_e2e", "per_layer")
    # no file that was there has been touched
    assert all(os.path.getmtime(p) == t for p, t in before.items())


def _files(top):
    return [os.path.join(d, f) for d, _, fs in os.walk(top) for f in fs]
