"""`flops_zaya.py` against a hand count at the toy size of the cell's dry
rehearsal, and the two readers this cell brought against made-up facts."""
import types

import common
import flops_zaya

TOY = types.SimpleNamespace(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_hidden_layers=3, router_hidden_size=16, num_experts=8, num_experts_per_tok=1,
    experts_held=(0, 4), moe_intermediate_size=32, cca_time0=2, cca_time1=2,
    vocab_size=256)


def test_forward_by_hand():
    batch, length = 2, 64
    n = batch * length
    ops = flops_zaya.decoder_fwd_op_flops(TOY, batch, length)
    # q 64->64, k 64->32, v1 + v2 64->32, o 64->64: 192 columns of 64
    assert ops["cca_proj"] == 3 * 2 * n * 64 * 192
    # 6 heads, 2 taps, 16 x 16 a head
    assert ops["cca_conv"] == 3 * 2 * n * 2 * 6 * 16 * 16
    # 64 * 65 / 2 pairs a sequence and query head, 16 for qk and 16 for pv
    assert ops["attn_core"] == 3 * 2 * (2 * 4 * 2080) * 32
    assert ops["router"] == 3 * 2 * n * (64 * 16 + 2 * 16 * 16 + 16 * 8)
    # half of the experts held: n / 2 assignments of 3 products 64 x 32
    assert ops["experts"] == 3 * 2 * (n / 2) * 3 * 64 * 32
    assert ops["head"] == 2 * 2 * 63 * 64 * 256
    assert flops_zaya.decoder_required_train_flops(TOY, batch, length) == 3 * sum(ops.values())
    counted = flops_zaya.decoder_fwd_op_flops(TOY, batch, length, assignments=10.0)
    assert counted["experts"] == 3 * 2 * 10.0 * 3 * 64 * 32


def test_roofline_inputs_by_hand():
    batch, length = 2, 64
    n = batch * length
    assert flops_zaya.attn_core_train_flops(TOY, batch, length) == 3 * 3 * 2 * (2 * 4 * 2080) * 32
    # q and out at 4 heads, k and v at 2, of 16 lanes, bf16, three passes, three layers
    assert flops_zaya.attn_core_train_bytes(TOY, batch, length) == 3 * 3 * n * (2 * 64 + 2 * 32) * 2
    weights = 4 * 3 * 64 * 32 * 2
    assert (flops_zaya.experts_train_bytes(TOY, batch, length, assignments=50.0)
            == 3 * 3 * (weights + 2 * 50.0 * 64 * 2))


def test_the_cells_readers_read_the_made_up_facts():
    _, _, config, traffic = common.load_cell("train_lm_cca_moe_8k")
    facts = common.module("kinds", traffic["kind"]).dry_facts(config, traffic)
    facts["device_kind"] = "TPU v5 lite"
    mfu = common.module("readers", "mfu_required_by_module").read(facts, {"module": "flops_zaya"})
    need = flops_zaya.decoder_required_train_flops(facts["model_cfg"], *facts["lm_shape"])
    assert mfu == 100.0 * need / facts["train_step_s"] / common.peaks_for("TPU v5 lite")["bf16_flops"]
    keys = common.module("readers", "scope_share_keys")
    mix = keys.read(facts, {"keys": ["cca_attn/conv_mix", "cca_attn/qk_norm_rope",
                                     "cca_attn/value_shift"]})
    assert abs(mix - 100.0 * 3 * 0.04 / facts["scopes"]["busy_s"]) < 1e-9
    # a program without these scopes (the parent's) gives nothing, and no error
    assert keys.read(facts, {"keys": ["mla_attn/rope"]}) is None
    assert keys.read({}, {"keys": ["cca_attn/conv_mix"]}) is None
