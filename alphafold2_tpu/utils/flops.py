"""Analytic model-FLOP accounting for the Alphafold2 trunk workload.

Why not XLA's `compiled.cost_analysis()["flops"]`: it counts the body of
a `lax.scan` / `lax.while_loop` ONCE, not times the trip count. The
north-star forward is a scan over reversible layers whose attention is
itself `lax.map`-tiled, so the reported number is ~2 orders of magnitude
low (measured: 0.607 TFLOP reported for a depth-12 forward whose matmul
arithmetic is 186 TFLOP). Every MFU computed from it is garbage. These
formulas count the matmul FLOPs (2*M*N*K per dot) of the model as
configured — the ~(1-3)% of elementwise/softmax/norm work is
deliberately excluded, so the count is a slight UNDERestimate and MFU
derived from it is conservative.

Validated against XLA's own count on a fully-unrolled dense (no-scan)
configuration in tests/test_flops.py, where cost_analysis IS complete.

Shape conventions (alphafold2_apply): pair grid (b, n, n, dim) with
n = 3*crop when full-atom elongated; MSA (b, r, c, dim). Reference
workload: reference train_pre.py:59-64 / BASELINE.md config 5.
"""

from __future__ import annotations

from alphafold2_tpu.models.config import Alphafold2Config


def attention_flops(
    tokens_q: float,
    tokens_kv: float,
    j_eff: float,
    dim: int,
    inner: int,
) -> float:
    """One multi-head attention pass (ops/attention.py attention_apply).

    tokens_q / tokens_kv: total query / key-value tokens projected.
    j_eff: keys each query actually attends (after folding/compression).
    """
    proj_q_out = 4.0 * tokens_q * dim * inner  # to_q + to_out
    proj_kv = 4.0 * tokens_kv * dim * inner  # to_kv (k and v)
    attn = 4.0 * tokens_q * j_eff * inner  # QK^T + attn@V
    return proj_q_out + proj_kv + attn


def ff_flops(tokens: float, dim: int, mult: int = 4) -> float:
    """GEGLU feed-forward (ops/feedforward.py): d -> 2*mult*d -> ... ->
    mult*d -> d."""
    return tokens * (4.0 * mult * dim * dim + 2.0 * mult * dim * dim)


def trunk_layer_op_flops(
    cfg: Alphafold2Config, n: int, r: int, c: int
) -> dict:
    """Per-op matmul FLOPs of ONE trunk layer at pair side n, MSA r x c.

    Mirrors models/trunk.py trunk_layer_apply: pair axial self-attention
    (row+col), MSA axial self-attention (row+col, tied rows cost the
    same contraction count), cross-attention both directions
    (mode-dependent, each including its k+v compression conv), and the
    feed-forwards (2 sequential / 4 reversible,
    models/reversible.py seq_ff2/msa_ff2). The decomposition bench
    (scripts/bench_decompose.py ops leg) consumes these keys directly —
    one formula source, so the per-op table always sums to
    trunk_layer_flops.
    """
    d, w = cfg.dim, cfg.heads * cfg.dim_head
    rho = max(1, cfg.cross_attn_compress_ratio)
    # grouped strided KV-compression conv (ops/attention.py
    # _compress_conv: inner->inner, kernel rho, groups=heads), applied
    # to k AND v: 4*j_kv*w^2/heads per cross direction
    conv = (lambda j_kv: 4.0 * j_kv * w * w / cfg.heads) if rho > 1 else (
        lambda j_kv: 0.0)

    ops = {
        # two passes (rows then cols), each a full QKVO over the n^2
        # grid and n-token attention within each line
        "pair_axial": 2 * attention_flops(n * n, n * n, n, d, w),
    }
    if r and c:
        ops["msa_axial"] = (
            attention_flops(r * c, r * c, c, d, w)  # along rows
            + attention_flops(r * c, r * c, r, d, w)  # along cols
        )
        if cfg.cross_attn_mode == "aligned":
            f = max(1, n // c)  # elongation factor (column fold)
            # pair<-msa: the context folds to (b*c, r) — every pair
            # token attends its column's r MSA rows, compressed rho-fold
            ops["cross_pair_from_msa"] = attention_flops(
                n * n, r * c, max(1.0, r / rho), d, w
            ) + conv(r * c)
            # msa<-pair: every MSA token attends its column's n*f pair
            # tokens (compressed)
            ops["cross_msa_from_pair"] = attention_flops(
                r * c, n * n, max(1.0, n * f / rho), d, w
            ) + conv(n * n)
        else:  # flat: all-to-all between the flattened streams
            ops["cross_pair_from_msa"] = attention_flops(
                n * n, r * c, r * c / rho, d, w) + conv(r * c)
            ops["cross_msa_from_pair"] = attention_flops(
                r * c, n * n, n * n / rho, d, w) + conv(n * n)

    ffs_per_stream = 2 if cfg.reversible else 1
    ops["ff_pair"] = ffs_per_stream * ff_flops(n * n, d)
    if r and c:
        ops["ff_msa"] = ffs_per_stream * ff_flops(r * c, d)
    return ops


def trunk_layer_flops(cfg: Alphafold2Config, n: int, r: int, c: int) -> float:
    """Matmul FLOPs of ONE trunk layer (sum of trunk_layer_op_flops)."""
    return sum(trunk_layer_op_flops(cfg, n, r, c).values())


def model_fwd_flops(cfg: Alphafold2Config, n: int, r: int, c: int) -> float:
    """Whole alphafold2_apply forward: trunk + distogram head (the
    front's embedding lookups and outer-sum are matmul-free)."""
    head = 2.0 * n * n * cfg.dim * cfg.num_buckets
    return cfg.depth * trunk_layer_flops(cfg, n, r, c) + head


def train_step_flops(
    cfg: Alphafold2Config,
    n: int,
    r: int,
    c: int,
    grad_accum: int = 1,
) -> float:
    """One optimizer step (or equivalently one value_and_grad) of the
    trunk workload.

    Backward of a matmul chain costs ~2x its forward; the reversible
    trunk RECOMPUTES the forward during backward (models/reversible.py),
    and so does a remat'd sequential trunk (cfg.remat: per-layer
    jax.checkpoint) — fwd multiplier 4 for either, 3 for plain
    sequential. Geometry (distogram centering + MDS + Kabsch) is
    O(iters * n^2) elementwise plus tiny 3x3 SVDs — well under 1% of
    the trunk at model scale — and is excluded.
    """
    mult = 4.0 if (cfg.reversible or cfg.remat) else 3.0
    return grad_accum * mult * model_fwd_flops(cfg, n, r, c)


def deepseek_fwd_op_flops(cfg, batch: int, length: int, assignments=None) -> dict:
    """Matmul FLOPs one forward of the `deepseek_v3` decoder language model
    REQUIRES on `batch` sequences of `length` tokens, by op
    (models/decoder.py, training/lm.py), summed over the layers. `cfg` is
    any object with DecoderConfig's fields.

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


def zaya_fwd_op_flops(cfg, batch: int, length: int, assignments=None) -> dict:
    """The same for the `zaya` family (`cfg`: any object with ZayaConfig's
    fields): CCA's four projections at their latent widths, the grouped
    convolution (the depthwise one is no matrix product), the causal half
    of the logits at the QUERY heads' count (grouped keys save bytes, not
    operations), the router's down-projection and MLP, the assignments
    HELD, the tied head's L - 1 rows a sequence."""
    n = batch * length
    d, h, hk, dh = (cfg.hidden_size, cfg.num_attention_heads,
                    cfg.num_key_value_heads, cfg.head_dim)
    layers, r = cfg.num_hidden_layers, cfg.router_hidden_size
    lo, hi = cfg.experts_held or (0, cfg.num_experts)
    if assignments is None:
        assignments = n * cfg.num_experts_per_tok * (hi - lo) / cfg.num_experts
    pairs = batch * h * length * (length + 1) / 2.0
    return {
        "cca_proj": layers * 2.0 * n * d * (2 * h * dh + 2 * hk * dh),
        "cca_conv": layers * 2.0 * n * cfg.cca_time1 * (h + hk) * dh * dh,
        "attn_core": layers * 2.0 * pairs * 2 * dh,
        "router": layers * 2.0 * n * (d * r + 2 * r * r + r * cfg.num_experts),
        "experts": layers * 2.0 * assignments * 3 * d * cfg.moe_intermediate_size,
        "head": 2.0 * batch * (length - 1) * d * cfg.vocab_size,
    }


def band_pairs(length: int, window) -> float:
    """(query, key) pairs a sequence and head under the causal mask: each
    query and the keys at or before it, at most `window` of them (None:
    all). length (length + 1) / 2 without a window."""
    w = length if window is None else min(window, length)
    return w * (w + 1) / 2.0 + (length - w) * w


def mellum_fwd_op_flops(cfg, batch: int, length: int, assignments=None) -> dict:
    """The same for the `mellum` family (`cfg`: any object with
    MellumConfig's fields): the four projections at the query and key
    heads' widths, the core of the window layers over the BAND
    (`attn_core_window`) and of the full layers over the triangle
    (`attn_core`), both at the QUERY heads' count, the router, the
    assignments HELD, the untied head's L - 1 rows a sequence."""
    n = batch * length
    d, h, hk, dh = (cfg.hidden_size, cfg.num_attention_heads,
                    cfg.num_key_value_heads, cfg.head_dim)
    layers = cfg.num_hidden_layers
    kinds = list(cfg.layer_types)[:layers]
    n_window = sum(kind == "sliding_attention" for kind in kinds)
    lo, hi = cfg.experts_held or (0, cfg.num_experts)
    if assignments is None:
        assignments = n * cfg.num_experts_per_tok * (hi - lo) / cfg.num_experts
    pair_flops = 2.0 * batch * h * 2 * dh
    return {
        "gqa_proj": layers * 2.0 * n * d * (2 * h * dh + 2 * hk * dh),
        "attn_core_window": n_window * pair_flops * band_pairs(length, cfg.sliding_window),
        "attn_core": (layers - n_window) * pair_flops * band_pairs(length, None),
        "router": layers * 2.0 * n * d * cfg.num_experts,
        "experts": layers * 2.0 * assignments * 3 * d * cfg.moe_intermediate_size,
        "head": 2.0 * batch * (length - 1) * d * cfg.vocab_size,
    }


def ouro_fwd_op_flops(cfg, batch: int, length: int, assignments=None) -> dict:
    """The same for the `ouro` family (`cfg`: any object with OuroConfig's
    fields), counting EVERY pass: `total_ut_steps` x `layers` layer
    applications (the four projections, the causal half of the logits at
    the query heads' count, the SwiGLU) and, a pass, the exit gate's one
    output and the untied head's L - 1 rows a sequence. Every parameter of
    the stack is used T times a step, so 6 x parameters x tokens reads
    about T times low. `assignments` is unused (no expert layer)."""
    del assignments
    n = batch * length
    d, h, hk, dh = (cfg.hidden_size, cfg.num_attention_heads,
                    cfg.num_key_value_heads, cfg.head_dim)
    passes = cfg.total_ut_steps
    applications = passes * cfg.layers
    pairs = batch * h * length * (length + 1) / 2.0
    return {
        "attn_proj": applications * 2.0 * n * d * (2 * h * dh + 2 * hk * dh),
        "attn_core": applications * 2.0 * pairs * 2 * dh,
        "dense_mlp": applications * 2.0 * n * 3 * d * cfg.intermediate_size,
        "exit_gate": passes * 2.0 * n * d,
        "head": passes * 2.0 * batch * (length - 1) * d * cfg.vocab_size,
    }


def decoder_fwd_op_flops(cfg, batch: int, length: int, assignments=None) -> dict:
    """The family's count, by the configuration's `model_type`."""
    count = {"deepseek_v3": deepseek_fwd_op_flops, "zaya": zaya_fwd_op_flops,
             "mellum": mellum_fwd_op_flops, "ouro": ouro_fwd_op_flops}
    return count[cfg.model_type](cfg, batch, length, assignments)


def decoder_fwd_flops(cfg, batch: int, length: int, assignments=None) -> float:
    return sum(decoder_fwd_op_flops(cfg, batch, length, assignments).values())


def decoder_required_train_flops(cfg, batch: int, length: int,
                                 assignments=None) -> float:
    """Operations one optimizer step REQUIRES: forward once, backward at
    twice the forward; what `jax.checkpoint` computes again is not
    counted."""
    return 3.0 * decoder_fwd_flops(cfg, batch, length, assignments)
