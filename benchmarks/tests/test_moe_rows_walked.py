"""The window's expert-loop readers against made-up per-step metrics:
`readers/moe_rows_walked.py` (rows walked over held, summed over every step
and layer) and `readers/moe_extra_block_share.py` (the layer-steps that
walked a second block), and nothing where there is nothing to read."""
import types

import numpy as np
import pytest

import common
from kinds import lm_train_steps

from alphafold2_tpu.ops import moe

CFG = types.SimpleNamespace(router_width_key="num_experts", num_experts=64,
                            num_experts_per_tok=8, held=(0, 16))
BLOCK = moe.block_rows_for(16384, 8, 16, 64)
DECODER_CELLS = ("train_lm_moe_8k", "train_lm_cca_moe_8k", "train_lm_swa_moe_8k")
METRICS = ("moe.rows_walked_over_held.lm_train", "moe.extra_block_share.lm_train")


def _read(facts, reader="moe_rows_walked"):
    return common.module("readers", reader).read(facts, {})


def _window(held):
    """Facts of a window whose layers held `held` (steps, layers), with the
    rows the program's own plan walks for them."""
    held = np.asarray(held, np.float64)
    return {"model_cfg": CFG, "lm_shape": (2, 8192), "moe_held_by_step": held,
            "moe_rows_walked_by_step": np.asarray(moe.rows_walked(held, BLOCK))}


def test_reads_the_loops_own_plan():
    """The ratio is the window's own: every layer-step's rows over every
    layer-step's load, not the plan asked at the mean load."""
    facts = _window([[35100.0, 30000.0], [33000.0, 36000.0]])
    assert BLOCK == 40960
    assert _read(facts) == pytest.approx(4 * BLOCK / (35100 + 30000 + 33000 + 36000))
    assert _read(facts, "moe_extra_block_share") == 0.0


def test_a_layer_step_past_one_block_counts_whole():
    """One layer-step of eight over a block walks two: the ratio pays the
    second block whole, the share reads one in eight, where the plan at the
    mean load (under a block) reads neither."""
    held = np.full((4, 2), 33000.0)
    held[2, 1] = 52404.0
    facts = _window(held)
    assert held.mean() < BLOCK
    assert _read(facts) == pytest.approx(9 * BLOCK / held.sum())
    assert _read(facts) > 1.25
    assert _read(facts, "moe_extra_block_share") == 0.125
    # a layer-step AT one block walks one
    held[2, 1] = BLOCK
    assert _read(_window(held), "moe_extra_block_share") == 0.0


def test_reads_whole_chunks_from_a_tree_before_the_loop():
    """A tree from before the loop over live blocks states no rows walked
    in its steps (`expert_window` hands nothing on): both metrics are left
    out, whatever the program's plan module offers."""
    metrics = [{"moe_assignments_held": np.array([35100.0])}] * 3
    facts = {"model_cfg": CFG, "lm_shape": (2, 8192), "assignments_held": 35100.0,
             **lm_train_steps.expert_window(metrics)}
    assert _read(facts) is None
    assert _read(facts, "moe_extra_block_share") is None


def test_nothing_to_read_without_an_expert_layer_or_a_load(monkeypatch):
    facts = _window([[35100.0, 30000.0]])
    assert _read(dict(facts, model_cfg=types.SimpleNamespace()),
                 "moe_extra_block_share") is None
    assert _read(dict(facts, moe_held_by_step=np.zeros((1, 2)))) is None
    assert _read(dict(facts, moe_rows_walked_by_step=None)) is None
    assert _read({}) is None and _read({}, "moe_extra_block_share") is None
    monkeypatch.delattr(moe, "block_rows_for")
    assert _read(facts, "moe_extra_block_share") is None


def test_the_window_is_read_from_the_steps_own_metrics():
    """`expert_window` stacks what each step returned, step by layer."""
    import jax.numpy as jnp

    metrics = [{"moe_rows_walked": jnp.array([BLOCK, 2.0 * BLOCK]),
                "moe_assignments_held": jnp.array([30000.0, 45000.0]), "loss": jnp.array(1.0)},
               {"moe_rows_walked": jnp.array([BLOCK, BLOCK]),
                "moe_assignments_held": jnp.array([31000.0, 39000.0]), "loss": jnp.array(1.0)}]
    got = lm_train_steps.expert_window(metrics)
    assert got["moe_held_by_step"].tolist() == [[30000.0, 45000.0], [31000.0, 39000.0]]
    assert got["moe_rows_walked_by_step"].shape == (2, 2)
    assert lm_train_steps.expert_window([]) == {}


@pytest.mark.parametrize("metric", METRICS)
def test_every_decoder_cell_reports_it_and_the_trunk_cell_does_not(metric):
    bench = common.load_cell("train_e2e")[0]
    for cell in DECODER_CELLS:
        assert metric in common.metric_names(bench, cell, "per_layer")
        _, _, config, traffic = common.load_cell(cell)
        facts = common.module("kinds", traffic["kind"]).dry_facts(config, traffic)
        reader = common.load_json("metrics", metric + ".json")["reader"]
        assert _read(facts, reader) > (1.0 if reader == "moe_rows_walked" else 0.0)
    assert metric not in common.metric_names(bench, "train_e2e", "per_layer")
