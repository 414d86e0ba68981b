"""The result line's metrics, assembled from a run's facts as `run.py`
does after a window (a dry rehearsal stops before this). Each cell's
facts come from its own kind (`dry_facts()` beside `run()`): toy sizes,
made-up times, every fact a reader of that cell asks for."""
import json
import os

import pytest

import common
import run

with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def _facts(cell):
    _, _, config, traffic = common.load_cell(cell)
    facts = common.module("kinds", traffic["kind"]).dry_facts(config, traffic)
    return dict(facts, device_kind="TPU v5 lite")


@pytest.mark.parametrize("cell", CELLS)
def test_end_to_end_metrics_of_a_cell(cell):
    got = run.metrics_of(BENCH, cell, "end_to_end", _facts(cell))
    assert set(got) == set(common.metric_names(BENCH, cell, "end_to_end"))
    assert "setup_s" in got and len(got) >= 2
    units = {m["name"]: m["unit"] for m in BENCH["end_to_end"]}
    assert all(v["unit"] == units[k] and v["value"] > 0 for k, v in got.items())


@pytest.mark.parametrize("cell", CELLS)
def test_per_layer_metrics_of_a_cell(cell):
    """Every listed metric of the cell is on the traced line, none null."""
    got = run.metrics_of(BENCH, cell, "per_layer", _facts(cell))
    assert set(got) == set(common.metric_names(BENCH, cell, "per_layer"))
    assert all(v["value"] is not None and v["value"] > 0 for v in got.values())
    shares = [v["value"] for k, v in got.items() if v["unit"] == "%"]
    assert len(shares) >= 8 and all(0 < v < 100 for v in shares)


DECODER_CELLS = [c for c in CELLS
                 if common.load_cell(c)[3]["kind"].startswith("lm_train_steps")]


@pytest.mark.parametrize("cell", DECODER_CELLS)
def test_every_decoder_line_reads_the_expert_loops_cliff(cell):
    """The window's own rows walked over held, and the share of its
    layer-steps that walked a second block, are on every decoder cell's
    traced line."""
    got = run.metrics_of(BENCH, cell, "per_layer", _facts(cell))
    assert got["moe.extra_block_share.lm_train"]["unit"] == "x"
    assert 0 < got["moe.extra_block_share.lm_train"]["value"] < 1
    assert got["moe.rows_walked_over_held.lm_train"]["value"] >= 1


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    facts = _facts("train_e2e")
    del facts["trace"], facts["scopes"]
    got = run.metrics_of(BENCH, "train_e2e", "per_layer", facts)
    assert "device.idle_share.train" not in got and "step.mfu_required.train" in got
    assert not [k for k in got if "device_share" in k or "roofline" in k]


# --- B: the window's two quotients -------------------------------------------

@pytest.mark.parametrize("steps,want_all,want_less", [
    ([3.12] * 13, 3.12, 3.12),                       # no stall: equal
    ([3.12] * 12 + [5.415], 3.2965, 3.12),           # one stalled step
    ([3.12] * 11 + [5.415, 4.25], 3.3835, 3.2142),   # two: both statistics pay
])
def test_the_window_less_its_slowest_step(steps, want_all, want_less):
    stats = common.step_stats(steps, sum(steps))
    assert stats["steps"] == len(steps)
    # train_step_s stays the whole window over ALL its steps
    assert stats["train_step_s"] == pytest.approx(want_all, abs=1e-4)
    assert stats["train_step_less_slowest_s"] == pytest.approx(want_less, abs=1e-4)


def test_a_window_of_one_step_has_no_second_statistic():
    stats = common.step_stats([5.0], 5.0)
    assert stats["train_step_s"] == 5.0 and stats["train_step_less_slowest_s"] is None


def test_the_heartbeat_sees_a_process_kept_off_the_cpu():
    import time

    beat = common.Heartbeat(every=0.005)
    time.sleep(0.05)
    assert beat.take() < 0.04  # a sleeping main thread does not hold it up
    beat.stop()


def test_the_kernels_counters_only_grow():
    first, second = common.host_waits(), common.host_waits()
    assert set(first) == set(second) <= {"runq", "steal", "pressure"}
    assert all(0 <= first[k] <= second[k] for k in first)


# --- C: set-up by phase --------------------------------------------------------

def test_setup_facts_split_at_the_chip():
    setup = common.Setup(t_process_start=100.0)
    setup.last = 100.0
    setup.phases = [(common.IMPORTS_PHASE, 12.0), ("weights", 16.0), ("compile", 24.0)]
    setup.last = 152.0
    facts = setup.facts()
    # setup_s is still the whole of it; the halves add up to it
    assert facts["setup_s"] == 52.0
    assert facts["setup_imports_and_device_s"] == 12.0
    assert facts["setup_after_device_s"] == 40.0


@pytest.mark.parametrize("cell", CELLS)
def test_both_halves_of_setup_are_on_the_traced_line(cell):
    got = run.metrics_of(BENCH, cell, "per_layer", _facts(cell))
    assert (got["setup.imports_and_device_s"]["value"]
            + got["setup.after_device_s"]["value"]) == pytest.approx(
        run.metrics_of(BENCH, cell, "end_to_end", _facts(cell))["setup_s"]["value"])
