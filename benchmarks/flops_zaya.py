"""Analytic FLOP and byte accounting for the `zaya` decoder's training
step (ZAYA1: compressed convolutional attention, grouped keys, a top-1
mixture behind an MLP router, a tied head): the benchmark's own copy of
the program's count (`alphafold2_tpu/utils/flops.py zaya_fwd_op_flops`;
`tests/test_zaya_cell.py` holds the two equal), under the names
`flops_lm.py` gives the `deepseek_v3` decoder's so that the same readers
take either module, plus the bytes the two kernels' rooflines need. A
later PR may change the program, not the yardstick. `cfg` is any object
with ZayaConfig's fields.
"""

from __future__ import annotations


def _held_assignments(cfg, n: int, assignments):
    if assignments is not None:
        return assignments
    lo, hi = cfg.experts_held or (0, cfg.num_experts)
    return n * cfg.num_experts_per_tok * (hi - lo) / cfg.num_experts


def decoder_fwd_op_flops(cfg, batch: int, length: int, assignments=None) -> dict:
    """Matmul FLOPs one forward REQUIRES on `batch` sequences of `length`
    tokens, by op, summed over the layers.

    CCA's four projections at their latent widths (q: h dh, k: hk dh, the
    two value halves hk dh together, o: h dh); the grouped convolution
    (`cca_time1` taps of dh x dh a head; the depthwise one is no matrix
    product). The attention core counts the causal half of the logits
    only, L (L + 1) / 2 pairs a sequence and QUERY head, dh for q k^T and
    dh for p v: grouped keys save bytes, not operations. The router counts
    its down-projection and its three layers. The experts count the
    token-assignments HELD here: `assignments` a layer where given (the
    router's own count), else the uniform expectation N * top_k * held /
    num_experts. The tied head counts the L - 1 rows of a sequence that
    have a target."""
    n = batch * length
    d, h, hk, dh = (cfg.hidden_size, cfg.num_attention_heads,
                    cfg.num_key_value_heads, cfg.head_dim)
    layers, r = cfg.num_hidden_layers, cfg.router_hidden_size
    pairs = batch * h * length * (length + 1) / 2.0
    return {
        "cca_proj": layers * 2.0 * n * d * (2 * h * dh + 2 * hk * dh),
        "cca_conv": layers * 2.0 * n * cfg.cca_time1 * (h + hk) * dh * dh,
        "attn_core": layers * 2.0 * pairs * 2 * dh,
        "router": layers * 2.0 * n * (d * r + 2 * r * r + r * cfg.num_experts),
        "experts": layers * 2.0 * _held_assignments(cfg, n, assignments)
        * 3 * d * cfg.moe_intermediate_size,
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
# As `flops_lm.py`: a step REQUIRES 3 x the forward of each op, and the
# bytes are the least a step has to move through HBM for it at `itemsize`
# bytes an element: operands read and results written once a pass, three
# passes. The core's k and v count at the KEY heads' width, whatever
# implements the grouping: a core that repeats them to the query heads'
# count moves more, and reads a smaller share.

def attn_core_train_flops(cfg, batch: int, length: int) -> float:
    return 3.0 * decoder_fwd_op_flops(cfg, batch, length)["attn_core"]


def attn_core_train_bytes(cfg, batch: int, length: int, itemsize: int = 2) -> float:
    n, dh = batch * length, cfg.head_dim
    lanes = 2 * cfg.num_attention_heads * dh + 2 * cfg.num_key_value_heads * dh
    return 3.0 * cfg.num_hidden_layers * n * lanes * itemsize  # q, out; k, v


def experts_train_flops(cfg, batch: int, length: int, assignments=None) -> float:
    return 3.0 * decoder_fwd_op_flops(cfg, batch, length, assignments)["experts"]


def experts_train_bytes(cfg, batch: int, length: int, assignments=None,
                        itemsize: int = 2) -> float:
    lo, hi = cfg.experts_held or (0, cfg.num_experts)
    weights = (hi - lo) * 3 * cfg.hidden_size * cfg.moe_intermediate_size * itemsize
    rows = (2 * _held_assignments(cfg, batch * length, assignments)
            * cfg.hidden_size * itemsize)  # x in, y out
    return 3.0 * cfg.num_hidden_layers * (weights + rows)
