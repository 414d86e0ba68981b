"""Analytic FLOP and byte accounting for the `ouro` decoder's training step
(Ouro: a dense stack of multi-head attention and SwiGLU layers run
`total_ut_steps` times over the same weights, an untied head and an exit
gate after every pass): the benchmark's own copy of the program's count
(`alphafold2_tpu/utils/flops.py ouro_fwd_op_flops`;
`tests/test_ouro_cell.py` holds the two equal), under the names
`flops_lm.py` gives the `deepseek_v3` decoder's so that the same readers
take either module, plus the bytes the two rooflines need. Every count
takes EVERY pass: `total_ut_steps` x `layers` layer applications and T
heads. A later PR may change the program, not the yardstick. `cfg` is any
object with OuroConfig's fields.
"""

from __future__ import annotations


def _applications(cfg) -> int:
    """Layer applications a forward: each built layer once a pass."""
    return cfg.total_ut_steps * cfg.layers


def decoder_fwd_op_flops(cfg, batch: int, length: int, assignments=None) -> dict:
    """Matmul FLOPs one forward REQUIRES on `batch` sequences of `length`
    tokens, by op, summed over the passes and the layers.

    The four projections (q, k, v, o at h dh lanes each: one key head a
    query head); the core's causal half of the logits, dh for q k^T and
    dh for p v; the SwiGLU's three products at `intermediate_size`; a
    pass's exit gate (one output a token) and untied head (the L - 1 rows
    of a sequence that have a target). Norms, RoPE and softmax are not
    matrix products and are not counted. `assignments` is unused (no
    expert layer)."""
    del assignments
    n = batch * length
    d, h, hk, dh = (cfg.hidden_size, cfg.num_attention_heads,
                    cfg.num_key_value_heads, cfg.head_dim)
    passes, apps = cfg.total_ut_steps, _applications(cfg)
    pairs = batch * h * length * (length + 1) / 2.0
    return {
        "attn_proj": apps * 2.0 * n * d * (2 * h * dh + 2 * hk * dh),
        "attn_core": apps * 2.0 * pairs * 2 * dh,
        "dense_mlp": apps * 2.0 * n * 3 * d * cfg.intermediate_size,
        "exit_gate": passes * 2.0 * n * d,
        "head": passes * 2.0 * batch * (length - 1) * d * cfg.vocab_size,
    }


def decoder_fwd_flops(cfg, batch: int, length: int, assignments=None) -> float:
    return sum(decoder_fwd_op_flops(cfg, batch, length, assignments).values())


def decoder_required_train_flops(cfg, batch: int, length: int,
                                 assignments=None) -> float:
    """Operations one optimizer step REQUIRES: forward once, backward at
    twice the forward; what `jax.checkpoint` computes again is not
    counted."""
    return 3.0 * decoder_fwd_flops(cfg, batch, length, assignments)


# --- what the two rooflines read ---------------------------------------------
#
# As `flops_lm.py`: a step REQUIRES 3 x the forward of each op, and the bytes
# are the least a step has to move through HBM for it at `itemsize` bytes an
# element: operands read and results written once a pass (forward, and the
# backward's two), three passes, for each of the T x layers applications.

def attn_core_full_train_flops(cfg, batch: int, length: int) -> float:
    return 3.0 * decoder_fwd_op_flops(cfg, batch, length)["attn_core"]


def attn_core_full_train_bytes(cfg, batch: int, length: int, itemsize: int = 2) -> float:
    """q and out at the query heads' width, k and v at the key heads'."""
    lanes = (2 * cfg.num_attention_heads + 2 * cfg.num_key_value_heads) * cfg.head_dim
    return 3.0 * _applications(cfg) * batch * length * lanes * itemsize


def dense_mlp_train_flops(cfg, batch: int, length: int) -> float:
    return 3.0 * decoder_fwd_op_flops(cfg, batch, length)["dense_mlp"]


def dense_mlp_train_bytes(cfg, batch: int, length: int, itemsize: int = 2) -> float:
    """The three weight matrices, x in and y out; the (rows, intermediate)
    product need not leave the chip."""
    weights = 3 * cfg.hidden_size * cfg.intermediate_size * itemsize
    rows = 2 * batch * length * cfg.hidden_size * itemsize
    return 3.0 * _applications(cfg) * (weights + rows)
