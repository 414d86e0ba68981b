"""`flops_mellum.py` against a hand count at the toy size of the cell's dry
rehearsal, and the readers this cell uses against made-up facts."""
import types

import common
import flops_mellum

TOY = types.SimpleNamespace(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_hidden_layers=4, num_experts=8, num_experts_per_tok=2, experts_held=(0, 4),
    moe_intermediate_size=32, vocab_size=256, sliding_window=24,
    layer_types=("sliding_attention",) * 3 + ("full_attention",) + ("sliding_attention",) * 3)


def test_band_pairs_by_hand():
    # 24 keys for each of the 64 - 24 late queries, 1 + 2 + .. + 24 for the early ones
    assert flops_mellum.band_pairs(64, 24) == 40 * 24 + 300
    assert flops_mellum.band_pairs(64, None) == flops_mellum.band_pairs(64, 64) == 2080
    assert flops_mellum.band_pairs(64, 100) == 2080
    assert flops_mellum.band_pairs(64, 1) == 64
    brute = sum(1 for i in range(64) for j in range(64) if i - 24 < j <= i)
    assert flops_mellum.band_pairs(64, 24) == brute


def test_forward_by_hand():
    batch, length = 2, 64
    n = batch * length
    ops = flops_mellum.decoder_fwd_op_flops(TOY, batch, length)
    # q 64->64, k 64->32, v 64->32, o 64->64: 192 columns of 64, four layers
    assert ops["gqa_proj"] == 4 * 2 * n * 64 * 192
    # three window layers of the four that are run: 1260 pairs a sequence and
    # query head, 16 for qk and 16 for pv; one full layer: 2080 pairs
    assert ops["attn_core_window"] == 3 * 2 * (2 * 4 * 1260) * 32
    assert ops["attn_core"] == 1 * 2 * (2 * 4 * 2080) * 32
    assert ops["router"] == 4 * 2 * n * 64 * 8
    # half of the experts held, two picks a token: n assignments of 3 products 64 x 32
    assert ops["experts"] == 4 * 2 * n * 3 * 64 * 32
    assert ops["head"] == 2 * 2 * 63 * 64 * 256
    assert flops_mellum.decoder_required_train_flops(TOY, batch, length) == 3 * sum(ops.values())
    counted = flops_mellum.decoder_fwd_op_flops(TOY, batch, length, assignments=10.0)
    assert counted["experts"] == 4 * 2 * 10.0 * 3 * 64 * 32


def test_roofline_inputs_by_hand():
    batch, length = 2, 64
    n = batch * length
    assert (flops_mellum.attn_core_window_train_flops(TOY, batch, length)
            == 3 * 3 * 2 * (2 * 4 * 1260) * 32)
    assert (flops_mellum.attn_core_full_train_flops(TOY, batch, length)
            == 3 * 1 * 2 * (2 * 4 * 2080) * 32)
    # q and out at 4 heads, k and v at 2, of 16 lanes, bf16, three passes
    assert flops_mellum.attn_core_window_train_bytes(TOY, batch, length) == 3 * 3 * n * (2 * 64 + 2 * 32) * 2
    assert flops_mellum.attn_core_full_train_bytes(TOY, batch, length) == 3 * 1 * n * (2 * 64 + 2 * 32) * 2
    weights = 4 * 3 * 64 * 32 * 2
    assert (flops_mellum.experts_train_bytes(TOY, batch, length, assignments=50.0)
            == 3 * 4 * (weights + 2 * 50.0 * 64 * 2))


def test_the_cells_readers_read_the_made_up_facts():
    bench, _, config, traffic = common.load_cell("train_lm_swa_moe_8k")
    facts = common.module("kinds", traffic["kind"]).dry_facts(config, traffic)
    facts["device_kind"] = "TPU v5 lite"
    peaks = common.peaks_for("TPU v5 lite")
    mfu = common.module("readers", "mfu_required_by_module").read(facts, {"module": "flops_mellum"})
    need = flops_mellum.decoder_required_train_flops(facts["model_cfg"], *facts["lm_shape"])
    assert mfu == 100.0 * need / facts["train_step_s"] / peaks["bf16_flops"]
    roofline = common.module("readers", "roofline_share_lm")
    for name, work, scope in (("lm.swa_core.roofline_share.train", "attn_core_window",
                               "gqa_attn/attn_core_window"),
                              ("lm.gqa_core.roofline_share.train", "attn_core_full",
                               "gqa_attn/attn_core")):
        spec = common.load_json("metrics", name + ".json")
        assert spec["args"]["scope"] == scope and spec["args"]["work"] == work
        got = roofline.read(facts, spec["args"])
        flops = getattr(flops_mellum, work + "_train_flops")(facts["model_cfg"], *facts["lm_shape"])
        moved = getattr(flops_mellum, work + "_train_bytes")(facts["model_cfg"], *facts["lm_shape"])
        least = max(flops / peaks["bf16_flops"], moved / peaks["hbm_bytes_per_s"])
        assert abs(got - 100.0 * least / (0.04 / facts["trace_steps"])) < 1e-9
    # every per-layer metric of the cell finds something in the made-up facts
    run = __import__("run")
    line = run.metrics_of(bench, "train_lm_swa_moe_8k", "per_layer", facts)
    assert set(line) == set(common.metric_names(bench, "train_lm_swa_moe_8k", "per_layer"))


def test_tiles_share_reads_the_programs_plan():
    tiles = common.module("readers", "core_tiles_share")
    cfg = types.SimpleNamespace(sliding_window=1024, num_attention_heads=32,
                                qk_head_dim=128, v_head_dim=128, compute_dtype="bfloat16")
    assert tiles.read({"model_cfg": cfg, "lm_shape": (2, 8192)}, {}) == 15 / 36
    # a configuration without a window, or a run without a shape, gives nothing
    cfg.sliding_window = None
    assert tiles.read({"model_cfg": cfg, "lm_shape": (2, 8192)}, {}) is None
    assert tiles.read({}, {}) is None
    _, _, config, traffic = common.load_cell("train_lm_cca_moe_8k")
    facts = common.module("kinds", traffic["kind"]).dry_facts(config, traffic)
    assert tiles.read(facts, {}) is None
