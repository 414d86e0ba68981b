"""Analytic model-FLOP accounting for the Alphafold2 trunk workload.

The benchmark's own copy of `alphafold2_tpu/utils/flops.py` (copied for PR
24; `tests/test_flops_copy.py` holds it equal to the original on the day of
the copy). A later PR may change the program, not the yardstick. `cfg` is
any object with dim, heads, dim_head, depth, num_buckets, reversible,
remat, cross_attn_mode and cross_attn_compress_ratio.


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
    cfg, n: int, r: int, c: int
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


def trunk_layer_flops(cfg, n: int, r: int, c: int) -> float:
    """Matmul FLOPs of ONE trunk layer (sum of trunk_layer_op_flops)."""
    return sum(trunk_layer_op_flops(cfg, n, r, c).values())


def model_fwd_flops(cfg, n: int, r: int, c: int) -> float:
    """Whole alphafold2_apply forward: trunk + distogram head (the
    front's embedding lookups and outer-sum are matmul-free)."""
    head = 2.0 * n * n * cfg.dim * cfg.num_buckets
    return cfg.depth * trunk_layer_flops(cfg, n, r, c) + head


def train_step_flops(
    cfg,
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


def required_train_flops(cfg, n: int, r: int, c: int) -> float:
    """Operations one optimizer step REQUIRES: forward once, backward at
    twice the forward. What a reversible or rematerialised trunk computes
    again is not counted, so this is the count for a number called mfu."""
    return 3.0 * model_fwd_flops(cfg, n, r, c)


# --- what the pair axial core's roofline reads --------------------------------
#
# As `flops_lm.attn_core_train_*` count the decoder's core: the work ASKED of
# the scope `seq_attn/attn_core` in one optimizer step, from the shapes alone
# and whatever arm or kernel does it. A step REQUIRES 3 x the forward (forward
# once, backward at twice); what the reversible trunk or a checkpoint computes
# again is not counted, nor the lanes a kernel pads `dim_head` to.

def attn_core_train_flops(cfg, n: int, r: int = 0, c: int = 0) -> float:
    """QK^T + AV of the pair stream's two axial passes (rows, then columns:
    n^2 queries, n keys each, at heads x dim_head), every layer, 3 x forward.
    The MSA's and the crosses' cores run under scopes of their own."""
    inner = cfg.heads * cfg.dim_head
    return 3.0 * cfg.depth * 2 * 4.0 * n * n * n * inner


def attn_core_train_bytes(cfg, n: int, r: int = 0, c: int = 0,
                          itemsize: int = 2) -> float:
    """The least a step moves through HBM for it at `itemsize` bytes an
    element: q, k, v read and the output written once a pass, three passes;
    logits and probabilities never need to leave the chip's fast memory."""
    once = 4 * n * n * cfg.heads * cfg.dim_head * itemsize
    return 3.0 * cfg.depth * 2 * once
