"""`flops_ouro.py` against a hand count at the toy size of the cell's dry
rehearsal, and the readers this cell uses against made-up facts: every
count takes every pass, and no share of a peak or a roofline can pass 100%
where the program's own count is the yardstick's."""
import types

import common
import flops_ouro

TOY = types.SimpleNamespace(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    num_hidden_layers=6, layers=2, intermediate_size=96, vocab_size=256, total_ut_steps=4)


def test_forward_by_hand():
    batch, length = 2, 64
    n = batch * length
    ops = flops_ouro.decoder_fwd_op_flops(TOY, batch, length)
    # two layers, four passes: eight applications of q, k, v, o at 64 -> 64
    assert ops["attn_proj"] == 8 * 2 * n * 64 * 256
    # 2080 causal pairs a sequence and head, 16 lanes for qk and 16 for pv
    assert ops["attn_core"] == 8 * 2 * (2 * 4 * 2080) * 32
    assert ops["dense_mlp"] == 8 * 2 * n * 3 * 64 * 96
    # one gate output and one head a pass, the head over the 63 rows with a target
    assert ops["exit_gate"] == 4 * 2 * n * 64
    assert ops["head"] == 4 * 2 * 2 * 63 * 64 * 256
    assert flops_ouro.decoder_required_train_flops(TOY, batch, length) == 3 * sum(ops.values())
    # a pass fewer is a quarter less of everything but nothing else
    three = types.SimpleNamespace(**{**vars(TOY), "total_ut_steps": 3})
    assert flops_ouro.decoder_fwd_flops(three, batch, length) == 0.75 * sum(ops.values())


def test_roofline_inputs_by_hand():
    batch, length = 2, 64
    n = batch * length
    assert flops_ouro.attn_core_full_train_flops(TOY, batch, length) == 3 * 8 * 2 * (2 * 4 * 2080) * 32
    assert flops_ouro.dense_mlp_train_flops(TOY, batch, length) == 3 * 8 * 2 * n * 3 * 64 * 96
    # q, k, v and out at 4 heads of 16 lanes, bf16, three passes, eight applications
    assert flops_ouro.attn_core_full_train_bytes(TOY, batch, length) == 3 * 8 * n * 4 * 64 * 2
    assert (flops_ouro.dense_mlp_train_bytes(TOY, batch, length)
            == 3 * 8 * (3 * 64 * 96 * 2 + 2 * n * 64 * 2))


def test_the_program_counts_the_same(monkeypatch):
    from alphafold2_tpu.utils import flops as original

    cfg = types.SimpleNamespace(**vars(TOY), model_type="ouro")
    assert (original.decoder_fwd_op_flops(cfg, 2, 64)
            == flops_ouro.decoder_fwd_op_flops(cfg, 2, 64))
    assert (original.decoder_required_train_flops(cfg, 2, 8192)
            == flops_ouro.decoder_required_train_flops(cfg, 2, 8192))


def test_the_cells_readers_read_the_made_up_facts():
    bench, _, config, traffic = common.load_cell("train_lm_loop_8k")
    facts = common.module("kinds", traffic["kind"]).dry_facts(config, traffic)
    facts["device_kind"] = "TPU v5 lite"
    peaks = common.peaks_for("TPU v5 lite")
    mfu = common.module("readers", "mfu_required_by_module").read(facts, {"module": "flops_ouro"})
    need = flops_ouro.decoder_required_train_flops(facts["model_cfg"], *facts["lm_shape"])
    assert mfu == 100.0 * need / facts["train_step_s"] / peaks["bf16_flops"]
    roofline = common.module("readers", "roofline_share_lm")
    for name, work, scope in (("lm.loop_core.roofline_share.train", "attn_core_full",
                               "gqa_attn/attn_core"),
                              ("lm.loop_mlp.roofline_share.train", "dense_mlp", "dense_mlp")):
        spec = common.load_json("metrics", name + ".json")
        assert spec["args"] == {"scope": scope, "work": work, "module": "flops_ouro"}
        got = roofline.read(facts, spec["args"])
        flops = getattr(flops_ouro, work + "_train_flops")(facts["model_cfg"], *facts["lm_shape"])
        moved = getattr(flops_ouro, work + "_train_bytes")(facts["model_cfg"], *facts["lm_shape"])
        least = max(flops / peaks["bf16_flops"], moved / peaks["hbm_bytes_per_s"])
        assert abs(got - 100.0 * least / (0.04 / facts["trace_steps"])) < 1e-9
    # every per-layer metric of the cell finds something in the made-up facts
    run = __import__("run")
    line = run.metrics_of(bench, "train_lm_loop_8k", "per_layer", facts)
    assert set(line) == set(common.metric_names(bench, "train_lm_loop_8k", "per_layer"))
    for name in ("lm.dense_mlp.device_share.train", "lm.exit_gate.device_share.train",
                 "lm.gqa_attn.device_share.train", "lm.head_loss.device_share.train"):
        assert 0 < line[name]["value"] < 100
    # the expert layer's metrics are not this cell's
    assert not [k for k in line if k.startswith("moe.")]
