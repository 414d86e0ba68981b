"""The result line's metrics, assembled from a run's facts as `run.py`
does after a window (a dry rehearsal stops before this)."""
import json
import os

import pytest

import common
import run

with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def _model():
    from alphafold2_tpu.models import Alphafold2Config

    return Alphafold2Config(dim=16, depth=1, heads=2, dim_head=8)


def _facts():
    return {"train_step_s": 5.0, "setup_s": 20.0, "window_s": 40.0,
            "grid": (48, 4, 16), "model_cfg": _model(), "planned_hbm_bytes": 7.4e9,
            "device_kind": "TPU v5 lite", "trace": {"idle_share": 0.02}}


@pytest.mark.parametrize("cell", CELLS)
def test_end_to_end_metrics_of_a_cell(cell):
    got = run.metrics_of(BENCH, cell, "end_to_end", _facts())
    assert set(got) == set(common.metric_names(BENCH, cell, "end_to_end"))
    assert "setup_s" in got and len(got) >= 2
    units = {m["name"]: m["unit"] for m in BENCH["end_to_end"]}
    assert all(v["unit"] == units[k] and v["value"] > 0 for k, v in got.items())


@pytest.mark.parametrize("cell", CELLS)
def test_per_layer_metrics_of_a_cell(cell):
    got = run.metrics_of(BENCH, cell, "per_layer", _facts())
    assert set(got) == set(common.metric_names(BENCH, cell, "per_layer"))
    shares = [v["value"] for k, v in got.items() if "mfu" in k]
    assert shares and all(0 < v < 100 for v in shares)


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    facts = _facts()
    del facts["trace"]
    got = run.metrics_of(BENCH, "train_e2e", "per_layer", facts)
    assert "device.idle_share.train" not in got and "step.mfu_required.train" in got
