"""The benchmark's copy of the decoder's FLOP count
(benchmarks/flops_lm.py) equals the program's (utils/flops.py), as
benchmarks/tests/test_flops_copy.py holds the trunk's; and the count is
the arithmetic PERF.md states for the shipped configuration."""
import importlib.util
import json
import os

import pytest

from alphafold2_tpu.models.decoder import DecoderConfig
from alphafold2_tpu.utils import flops as original

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, *parts):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


flops_lm = _load("bench_flops_lm", "benchmarks", "flops_lm.py")
builder = _load("bench_builder_decoder_lm", "benchmarks", "builders", "decoder_lm.py")

TOY = DecoderConfig(
    vocab_size=256, hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=8,
    num_experts_per_tok=2, n_shared_experts=2, routed_scaling_factor=2.448,
    experts_held=(2, 6))


def _shipped():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "kanana2_30b_a3b_ep8_l5.json")) as f:
        return builder.build(json.load(f), False)["cfg"]


@pytest.mark.parametrize("case", ["toy", "shipped", "shipped_counted"])
def test_copy_equals_original(case):
    cfg, shape, extra = {
        "toy": (TOY, (2, 64), {}),
        "shipped": (_shipped(), (2, 8192), {}),
        "shipped_counted": (_shipped(), (2, 8192), {"assignments": 12345.0}),
    }[case]
    for name in ("decoder_fwd_op_flops", "decoder_fwd_flops",
                 "decoder_required_train_flops"):
        assert (getattr(flops_lm, name)(cfg, *shape, **extra)
                == getattr(original, name)(cfg, *shape, **extra))


def test_shipped_configuration_counts():
    cfg = _shipped()
    ops = flops_lm.decoder_fwd_op_flops(cfg, 2, 8192)
    total = sum(ops.values())
    assert flops_lm.decoder_required_train_flops(cfg, 2, 8192) == 3.0 * total
    assert 45.6e12 < 3.0 * total < 45.8e12  # 45.71 TFLOP a step (PERF.md)
    # the causal half: L (L + 1) / 2 pairs a head and sequence, qk 192 + v 128
    assert ops["attn_core"] == 5 * 2.0 * (2 * 32 * 8192 * 8193 / 2) * 320
    # 0.75 N assignments a MoE layer: top-6 of 128, 16 held
    assert ops["experts"] == 4 * 2.0 * (0.75 * 16384) * 3 * 2048 * 768
    assert 0.44 < ops["attn_core"] / total < 0.46


def test_rooflines_count_three_passes():
    cfg = _shipped()
    assert flops_lm.attn_core_train_flops(cfg, 2, 8192) == (
        3.0 * flops_lm.decoder_fwd_op_flops(cfg, 2, 8192)["attn_core"])
    assert flops_lm.experts_train_flops(cfg, 2, 8192, 10000.0) == (
        3.0 * 4 * 2.0 * 10000.0 * 3 * 2048 * 768)
    # q, k (192) and v, out (128) of 32 heads, bf16, 5 layers, 3 passes
    assert flops_lm.attn_core_train_bytes(cfg, 2, 8192) == (
        3.0 * 5 * 16384 * 32 * 640 * 2)
    weights = 16 * 3 * 2048 * 768 * 2
    assert flops_lm.experts_train_bytes(cfg, 2, 8192, 10000.0) == (
        3.0 * 4 * (weights + 2 * 10000.0 * 2048 * 2))
