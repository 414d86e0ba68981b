"""Analytic FLOP and byte accounting for the decoder language model's
training step: the benchmark's own copy of the decoder's count in
`alphafold2_tpu/utils/flops.py` (copied for PR 27;
`tests/test_decoder_flops.py` holds the two equal), plus the bytes the
two kernels' rooflines need. A later PR may change the program, not the
yardstick. `cfg` is any object with DecoderConfig's fields.
"""

from __future__ import annotations


def decoder_fwd_op_flops(cfg, batch: int, length: int, assignments=None) -> dict:
    """Matmul FLOPs one forward of the decoder language model REQUIRES on
    `batch` sequences of `length` tokens, by op (models/decoder.py,
    training/lm.py), summed over the layers. `cfg` is any object with
    DecoderConfig's fields.

    The attention core counts the causal half of the logits only: each
    query and the keys at or before it, L (L + 1) / 2 pairs a sequence and
    head, at `qk_head_dim` for q k^T and `v_head_dim` for p v. The routed
    experts count the token-assignments HELD here: `assignments` a MoE
    layer where given (the router's own count), else the uniform
    expectation N * top_k * held / n_routed_experts. The head counts the
    L - 1 rows of a sequence that have a target."""
    n = batch * length
    d, h = cfg.hidden_size, cfg.num_attention_heads
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    layers = cfg.num_hidden_layers
    n_dense = cfg.first_k_dense_replace
    n_moe = layers - n_dense
    lo, hi = cfg.experts_held or (0, cfg.n_routed_experts)
    if assignments is None:
        assignments = n * cfg.num_experts_per_tok * (hi - lo) / cfg.n_routed_experts
    pairs = batch * h * length * (length + 1) / 2.0
    f = cfg.moe_intermediate_size
    return {
        "mla_proj": layers * 2.0 * n * (
            d * h * (nope + rope) + d * (cfg.kv_lora_rank + rope)
            + cfg.kv_lora_rank * h * (nope + dv) + h * dv * d),
        "attn_core": layers * 2.0 * pairs * (nope + rope + dv),
        "dense_mlp": n_dense * 2.0 * n * 3 * d * cfg.intermediate_size,
        "router": n_moe * 2.0 * n * d * cfg.n_routed_experts,
        "experts": n_moe * 2.0 * assignments * 3 * d * f,
        "shared_expert": n_moe * 2.0 * n * 3 * d * cfg.n_shared_experts * f,
        "head": 2.0 * batch * (length - 1) * d * cfg.vocab_size,
    }


def decoder_fwd_flops(cfg, batch: int, length: int, assignments=None) -> float:
    return sum(decoder_fwd_op_flops(cfg, batch, length, assignments).values())


def decoder_required_train_flops(cfg, batch: int, length: int,
                                 assignments=None) -> float:
    """Operations one optimizer step REQUIRES: forward once, backward at
    twice the forward; what `jax.checkpoint` computes again is not
    counted."""
    return 3.0 * decoder_fwd_flops(cfg, batch, length, assignments)


# --- what the two rooflines read --------------------------------------------
#
# A step REQUIRES 3 x the forward of each op (forward once, backward at
# twice). The bytes are the least a step has to move through HBM for the op
# at `itemsize` bytes an element: operands read and results written once a
# pass, three passes; logits, probabilities and the experts' hidden rows
# never need to leave the chip's fast memory and are not counted.

def attn_core_train_flops(cfg, batch: int, length: int) -> float:
    return 3.0 * decoder_fwd_op_flops(cfg, batch, length)["attn_core"]


def attn_core_train_bytes(cfg, batch: int, length: int, itemsize: int = 2) -> float:
    n, h = batch * length, cfg.num_attention_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    once = n * h * (2 * qk + 2 * cfg.v_head_dim) * itemsize  # q, k, v in; out
    return 3.0 * cfg.num_hidden_layers * once


def experts_train_flops(cfg, batch: int, length: int, assignments=None) -> float:
    return 3.0 * decoder_fwd_op_flops(cfg, batch, length, assignments)["experts"]


def experts_train_bytes(cfg, batch: int, length: int, assignments=None,
                        itemsize: int = 2) -> float:
    n = batch * length
    lo, hi = cfg.experts_held or (0, cfg.n_routed_experts)
    if assignments is None:
        assignments = n * cfg.num_experts_per_tok * (hi - lo) / cfg.n_routed_experts
    weights = (hi - lo) * 3 * cfg.hidden_size * cfg.moe_intermediate_size * itemsize
    rows = 2 * assignments * cfg.hidden_size * itemsize  # x in, y out
    n_moe = cfg.num_hidden_layers - cfg.first_k_dense_replace
    return 3.0 * n_moe * (weights + rows)
