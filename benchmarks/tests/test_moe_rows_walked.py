"""`readers/moe_rows_walked.py` against made-up facts: the plan of a tree
with the loop over live blocks, the plan of one that walked whole chunks,
and nothing where there is nothing to read."""
import types

import common

from alphafold2_tpu.ops import moe

CFG = types.SimpleNamespace(router_width_key="num_experts", num_experts=64,
                            num_experts_per_tok=8, held=(0, 16))


def _read(facts):
    return common.module("readers", "moe_rows_walked").read(facts, {})


def test_reads_the_loops_own_plan():
    block = moe.block_rows_for(16384, 8, 16, 64)
    got = _read({"model_cfg": CFG, "lm_shape": (2, 8192), "assignments_held": 35100.0})
    assert got == -(-35100 // block) * block / 35100.0
    assert 1.0 <= got < 1.0 + block / 35100.0


def test_reads_whole_chunks_from_a_tree_before_the_loop(monkeypatch):
    monkeypatch.delattr(moe, "rows_walked")
    monkeypatch.setattr(moe, "chunk_rows_for", lambda *plan: 65536, raising=False)
    facts = {"model_cfg": CFG, "lm_shape": (2, 8192), "assignments_held": 35100.0}
    assert _read(facts) == 65536 / 35100.0
    assert _read(dict(facts, assignments_held=70000.0)) == 2 * 65536 / 70000.0
    monkeypatch.delattr(moe, "chunk_rows_for")
    assert _read(facts) is None


def test_nothing_to_read_without_an_expert_layer_or_a_load():
    facts = {"model_cfg": CFG, "lm_shape": (2, 8192), "assignments_held": 35100.0}
    assert _read(dict(facts, model_cfg=types.SimpleNamespace())) is None
    assert _read(dict(facts, assignments_held=None)) is None
    assert _read(dict(facts, assignments_held=0.0)) is None
    assert _read({}) is None


def test_every_decoder_cell_reports_it_and_the_trunk_cell_does_not():
    bench = common.load_cell("train_e2e")[0]
    for cell in ("train_lm_moe_8k", "train_lm_cca_moe_8k", "train_lm_swa_moe_8k"):
        assert "moe.rows_walked_over_held.lm_train" in common.metric_names(
            bench, cell, "per_layer")
        _, _, config, traffic = common.load_cell(cell)
        facts = common.module("kinds", traffic["kind"]).dry_facts(config, traffic)
        assert _read(facts) >= 1.0
    assert "moe.rows_walked_over_held.lm_train" not in common.metric_names(
        bench, "train_e2e", "per_layer")
