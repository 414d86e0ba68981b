"""Analytic FLOP and byte accounting for the `mellum` decoder's training
step (Mellum 2: grouped-query attention in sliding-window and full causal
layers, a softmax top-k mixture of narrow experts, an untied head): the
benchmark's own copy of the program's count
(`alphafold2_tpu/utils/flops.py mellum_fwd_op_flops`;
`tests/test_mellum_cell.py` holds the two equal), under the names
`flops_lm.py` gives the `deepseek_v3` decoder's so that the same readers
take either module, plus the bytes the three kernels' rooflines need. A
later PR may change the program, not the yardstick. `cfg` is any object
with MellumConfig's fields.
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def _held_assignments(cfg, n: int, assignments):
    if assignments is not None:
        return assignments
    lo, hi = cfg.experts_held or (0, cfg.num_experts)
    return n * cfg.num_experts_per_tok * (hi - lo) / cfg.num_experts


def band_pairs(length: int, window) -> float:
    """(query, key) pairs a sequence and head under the causal mask: each
    query and the keys at or before it, at most `window` of them (None:
    all, length (length + 1) / 2)."""
    w = length if window is None else min(window, length)
    return w * (w + 1) / 2.0 + (length - w) * w


def _layers_of(cfg):
    """(window layers, full layers) among the layers that are run."""
    kinds = list(cfg.layer_types)[:cfg.num_hidden_layers]
    n_window = sum(kind == SLIDING for kind in kinds)
    return n_window, len(kinds) - n_window


def decoder_fwd_op_flops(cfg, batch: int, length: int, assignments=None) -> dict:
    """Matmul FLOPs one forward REQUIRES on `batch` sequences of `length`
    tokens, by op, summed over the layers.

    The four projections (q and o at h dh, k and v at hk dh). The core of a
    window layer counts the pairs of the BAND only (each query and the at
    most `sliding_window` keys that end with its own), the core of a full
    layer the causal half of the logits; both at the QUERY heads' count, dh
    for q k^T and dh for p v: grouped keys save bytes, not operations. The
    router is one projection to all `num_experts`. The experts count the
    token-assignments HELD here: `assignments` a layer where given (the
    router's own count), else the uniform expectation N * top_k * held /
    num_experts. The untied head counts the L - 1 rows of a sequence that
    have a target."""
    n = batch * length
    d, h, hk, dh = (cfg.hidden_size, cfg.num_attention_heads,
                    cfg.num_key_value_heads, cfg.head_dim)
    layers = cfg.num_hidden_layers
    n_window, n_full = _layers_of(cfg)
    pair_flops = 2.0 * batch * h * 2 * dh
    return {
        "gqa_proj": layers * 2.0 * n * d * (2 * h * dh + 2 * hk * dh),
        "attn_core_window": n_window * pair_flops * band_pairs(length, cfg.sliding_window),
        "attn_core": n_full * pair_flops * band_pairs(length, None),
        "router": layers * 2.0 * n * d * cfg.num_experts,
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


# --- what the three rooflines read ------------------------------------------
#
# As `flops_lm.py`: a step REQUIRES 3 x the forward of each op, and the
# bytes are the least a step has to move through HBM for it at `itemsize`
# bytes an element: operands read and results written once a pass, three
# passes. A core's k and v count at the KEY heads' width, whatever
# implements the grouping: a core that repeats them to the query heads'
# count moves more, and reads a smaller share. The window layers' core is
# asked for the pairs of the band and no more: a schedule that walks whole
# tiles across the band's edges does more, and reads a smaller share.

def _core_bytes(cfg, n_layers: int, n: int, itemsize: int) -> float:
    lanes = (2 * cfg.num_attention_heads + 2 * cfg.num_key_value_heads) * cfg.head_dim
    return 3.0 * n_layers * n * lanes * itemsize  # q, out; k, v


def attn_core_window_train_flops(cfg, batch: int, length: int) -> float:
    return 3.0 * decoder_fwd_op_flops(cfg, batch, length)["attn_core_window"]


def attn_core_window_train_bytes(cfg, batch: int, length: int, itemsize: int = 2) -> float:
    return _core_bytes(cfg, _layers_of(cfg)[0], batch * length, itemsize)


def attn_core_full_train_flops(cfg, batch: int, length: int) -> float:
    return 3.0 * decoder_fwd_op_flops(cfg, batch, length)["attn_core"]


def attn_core_full_train_bytes(cfg, batch: int, length: int, itemsize: int = 2) -> float:
    return _core_bytes(cfg, _layers_of(cfg)[1], batch * length, itemsize)


def experts_train_flops(cfg, batch: int, length: int, assignments=None) -> float:
    return 3.0 * decoder_fwd_op_flops(cfg, batch, length, assignments)["experts"]


def experts_train_bytes(cfg, batch: int, length: int, assignments=None,
                        itemsize: int = 2) -> float:
    lo, hi = cfg.experts_held or (0, cfg.num_experts)
    weights = (hi - lo) * 3 * cfg.hidden_size * cfg.moe_intermediate_size * itemsize
    rows = (2 * _held_assignments(cfg, batch * length, assignments)
            * cfg.hidden_size * itemsize)  # x in, y out
    return 3.0 * cfg.num_hidden_layers * (weights + rows)
