"""Sequence / context parallelism: ring attention, Ulysses all_to_all
attention, and sequence-parallel axial transposes.

The reference has no comm-based sequence parallelism (SURVEY.md §2.2: its
long-context story is architectural — axial factorization, block-sparse
attention, KV compression). A TPU-native framework at multi-chip scale needs
the communication-based complement, and these are its three primitives, all
designed to run inside `shard_map` over a mesh axis so XLA lowers the
communication onto ICI:

  * `ring_attention` — exact blockwise attention for sequences longer than
    one chip's HBM: K/V shards rotate around the ring via `ppermute` while
    each chip streams flash-style log-sum-exp softmax accumulation over its
    resident Q shard. Communication overlaps compute block by block;
    memory per chip is O(n/P) in sequence.
  * `ulysses_attention` — all_to_all (DeepSpeed-Ulysses-style) sequence
    parallelism: resharding flips (sequence-sharded, all heads) into
    (head-sharded, full sequence) so each chip runs a plain dense attention
    over its head group, then flips back. Two all_to_alls per attention;
    best when heads >= chips and the sequence fits per-chip after the flip.
  * `axial_alltoall_transpose` — for the axial (row/column) attention
    pattern: swaps which grid axis is sharded between the row pass and the
    column pass. Each axial pass is embarrassingly parallel over its
    folded-into-batch axis (reference alphafold2.py:276-283 semantics); the
    transpose is the only communication.

All softmax statistics accumulate in float32 with -inf masking handled the
same way as the Pallas block-sparse kernel (ops/sparse_kernel.py): masked
logits never contribute, fully-masked queries return zeros.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from alphafold2_tpu import compat
from alphafold2_tpu.ops import dispatch as _dispatch
from alphafold2_tpu.ops.flash import (
    flash_attention as _flash_attention,
    hop_attention_lse as _hop_attention_lse,
    merge_lse as _merge_lse,
    stream_block as _stream_block,
)
from alphafold2_tpu.parallel.overlap import overlap_enabled

_NEG_INF = float("-inf")


def _hop(k_blk, v_blk, bias_blk, axis_name, perm):
    """One ring hop: the neighbor copy of the K/V shard and its bias."""
    return (
        jax.lax.ppermute(k_blk, axis_name, perm),
        jax.lax.ppermute(v_blk, axis_name, perm),
        jax.lax.ppermute(bias_blk, axis_name, perm),
    )


def ring_attention(q, k, v, axis_name: str, mask=None, use_kernel="auto",
                   overlap=None):
    """Exact ring attention over a sharded sequence axis.

    Call inside `shard_map` with the sequence axis sharded over `axis_name`.

    Args:
      q, k, v: (b, n_local, h, d) — this chip's sequence shard.
      mask: (b, n_local) bool key-validity for the local shard (key-side
        masking, matching the reference's key_padding semantics,
        alphafold2.py:156-161 / DeepSpeed attn_mask_mode='add').
      use_kernel: per-hop compute path. "auto" uses the Pallas flash
        kernel on TPU for supported shapes whose PER-HOP key length
        nk_local reaches the flash family's measured crossover
        (ops/dispatch.py; each hop emits (out, lse) and hops combine in
        log space — ops/flash_kernel.flash_attention_lse); below it the
        hop runs the XLA stream_block recurrence — the crossover was
        measured on single-device e2e shapes (PERF.md section 5), not on
        ring hops, so force with True (interpret mode off-TPU, for tests)
        or AF2_KERNEL_BACKEND_MERGE_LSE=pallas_tpu to get the kernel on
        short shards.
      overlap: schedule selection. True = double-buffered (issue hop
        i+1's ppermute BEFORE computing hop i's block, so the ICI
        transfer hides under the current block's compute); False = the
        synchronous rotate-then-compute schedule; None (default) reads
        `AF2_COMM_OVERLAP` (parallel/overlap.py, default on). Both
        schedules visit the blocks in the same order with the same
        arithmetic — exact parity (tests/test_overlap.py), verified
        structurally by analysis/overlap_lint.py.

    Returns: (b, n_local, h, d) attention output for the local Q shard.
    """
    b, n_local, h, d = q.shape
    nk_local = k.shape[1]  # may differ from n_local for cross-attention
    scale = d ** -0.5
    num_shards = jax.lax.psum(1, axis_name)
    overlap = overlap_enabled(overlap)

    # mark constant-built carries as device-varying over the ring axis so
    # the fori_loop carry types match after the first ppermute
    def varying(x):
        return compat.pcast(x, (axis_name,), to="varying")

    bias = (
        varying(jnp.zeros((b, nk_local), jnp.float32))
        if mask is None
        else jnp.where(mask, 0.0, _NEG_INF).astype(jnp.float32)
    )
    perm = [(i, (i + 1) % num_shards) for i in range(num_shards)]

    # the SHARED resolution point (ops/dispatch.py, op "merge_lse" — the
    # ring hop's registered name): honors the
    # AF2_KERNEL_BACKEND[_MERGE_LSE] overrides, and raises loudly
    # when forcing an unsupported shape
    if _dispatch.resolve(
        "merge_lse", request=use_kernel, i=n_local, j=nk_local, dh=d
    ) == _dispatch.ARM_PALLAS_TPU:
        return _ring_attention_kernel(
            q, k, v, bias, axis_name, scale, num_shards, perm, overlap
        )

    m0 = varying(jnp.full((b, h, n_local), _NEG_INF, jnp.float32))
    l0 = varying(jnp.zeros((b, h, n_local), jnp.float32))
    acc0 = varying(jnp.zeros((b, h, n_local, d), jnp.float32))

    if overlap and num_shards > 1:
        # DOUBLE-BUFFERED schedule: hop 1's ppermute is issued before the
        # resident block's compute, and each loop body issues hop i+1's
        # ppermute before computing hop i's (already-arrived) block — the
        # neighbor copy rides the ICI while the MXU runs the current
        # block, instead of fencing it. Still exactly P-1 copies: the
        # loop runs hops 1..P-2 and the last arrival computes outside.
        k_nxt, v_nxt, b_nxt = _hop(k, v, bias, axis_name, perm)
        m, l, acc = _stream_block(q, k, v, bias, m0, l0, acc0, scale)

        def body(_, carry):
            m, l, acc, k_blk, v_blk, bias_blk = carry
            k_n, v_n, b_n = _hop(k_blk, v_blk, bias_blk, axis_name, perm)
            m, l, acc = _stream_block(
                q, k_blk, v_blk, bias_blk, m, l, acc, scale
            )
            return m, l, acc, k_n, v_n, b_n

        m, l, acc, k_last, v_last, b_last = jax.lax.fori_loop(
            1, num_shards - 1, body, (m, l, acc, k_nxt, v_nxt, b_nxt)
        )
        m, l, acc = _stream_block(q, k_last, v_last, b_last, m, l, acc, scale)
    else:
        # SYNCHRONOUS schedule: resident block first, then
        # rotate-before-compute for the remaining num_shards-1 blocks —
        # exactly P-1 neighbor copies, each fencing its block's compute.
        # Kept as the overlap-off reference arm (A/B legs, overlap-lint
        # fixtures) and the num_shards == 1 degenerate case.
        m, l, acc = _stream_block(q, k, v, bias, m0, l0, acc0, scale)

        def body(_, carry):
            m, l, acc, k_blk, v_blk, bias_blk = carry
            k_blk, v_blk, bias_blk = _hop(
                k_blk, v_blk, bias_blk, axis_name, perm
            )
            m, l, acc = _stream_block(
                q, k_blk, v_blk, bias_blk, m, l, acc, scale
            )
            return m, l, acc, k_blk, v_blk, bias_blk

        m, l, acc, _, _, _ = jax.lax.fori_loop(
            1, num_shards, body, (m, l, acc, k, v, bias)
        )
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]  # zeros for fully-masked q
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def _ring_attention_kernel(q, k, v, bias, axis_name, scale, num_shards, perm,
                           overlap=False):
    """Ring hops through the Pallas flash kernel: each hop produces its
    local (out, lse) fused in VMEM (ops/flash_kernel.flash_attention_lse),
    and hops merge in log space (ops/flash.py merge_lse — the shared hop
    interface). The communication pattern is identical to the XLA path
    (P-1 neighbor ppermutes, double-buffered when `overlap`), only the
    per-hop compute is fused. The kernel entry is ops/flash.py
    `hop_attention_lse` (zero-mass lse sign flip included) — this module
    never imports a kernel module directly (the dispatch lint's import
    monopoly)."""
    b, n_local, h, d = q.shape

    def fold(t):
        return t.transpose(0, 2, 1, 3).reshape(b * h, t.shape[1], d)

    qf = fold(q)

    def hop_compute(kf, vf, bias_blk):
        return _hop_attention_lse(
            qf, kf, vf, jnp.repeat(bias_blk, h, axis=0), scale
        )

    kf0, vf0 = fold(k), fold(v)

    if overlap and num_shards > 1:
        # double-buffered: hop i+1's ppermute issues before hop i's
        # kernel launch (see the XLA-path schedule above)
        k_nxt, v_nxt, b_nxt = _hop(kf0, vf0, bias, axis_name, perm)
        out, lse = hop_compute(kf0, vf0, bias)

        def body(_, carry):
            out, lse, k_blk, v_blk, bias_blk = carry
            k_n, v_n, b_n = _hop(k_blk, v_blk, bias_blk, axis_name, perm)
            out_h, lse_h = hop_compute(k_blk, v_blk, bias_blk)
            out, lse = _merge_lse(out, lse, out_h, lse_h)
            return out, lse, k_n, v_n, b_n

        out, lse, k_last, v_last, b_last = jax.lax.fori_loop(
            1, num_shards - 1, body, (out, lse, k_nxt, v_nxt, b_nxt)
        )
        out_h, lse_h = hop_compute(k_last, v_last, b_last)
        out, _ = _merge_lse(out, lse, out_h, lse_h)
    else:
        out, lse = hop_compute(kf0, vf0, bias)

        def body(_, carry):
            out, lse, k_blk, v_blk, bias_blk = carry
            k_blk, v_blk, bias_blk = _hop(
                k_blk, v_blk, bias_blk, axis_name, perm
            )
            out_h, lse_h = hop_compute(k_blk, v_blk, bias_blk)
            out, lse = _merge_lse(out, lse, out_h, lse_h)
            return out, lse, k_blk, v_blk, bias_blk

        out, lse, _, _, _ = jax.lax.fori_loop(
            1, num_shards, body, (out, lse, kf0, vf0, bias)
        )
    return out.reshape(b, h, n_local, d).transpose(0, 2, 1, 3).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str, mask=None):
    """All_to_all (Ulysses-style) sequence-parallel attention.

    Call inside `shard_map`; sequence axis sharded over `axis_name`, heads
    divisible by the axis size. Reshards to (full sequence, heads/P) per
    chip, runs dense flash-style attention locally, reshards back.

    Args/returns as `ring_attention`.
    """
    b, n_local, h, d = q.shape
    num_shards = jax.lax.psum(1, axis_name)
    if h % num_shards != 0:
        raise ValueError(f"heads ({h}) must divide by the sp axis ({num_shards})")

    # (b, n_local, h, d) -> (b, n, h_local, d): split heads, concat sequence
    def flip(t):
        return jax.lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1, tiled=True)

    qg, kg, vg = flip(q), flip(k), flip(v)
    if mask is None:
        bias = jnp.zeros((b, n_local * num_shards), jnp.float32)
    else:
        gathered = jax.lax.all_gather(mask, axis_name, tiled=True)  # (b*P, n_local)?
        # all_gather(tiled) concatenates over axis 0; reshape back to (b, n)
        bias = jnp.where(
            gathered.reshape(num_shards, b, n_local).transpose(1, 0, 2).reshape(b, -1),
            0.0,
            _NEG_INF,
        ).astype(jnp.float32)

    # fused/blockwise attention over the gathered sequence via the standard
    # dispatch (ops/flash.py): Pallas kernel on TPU, XLA K/V streaming
    # elsewhere — the full (n, n) logit tensor never materializes either
    # way, which is the point of sequence parallelism at long n
    out = _flash_attention(qg, kg, vg, bias, scale=d ** -0.5, kv_block=2048)

    # (b, n, h_local, d) -> (b, n_local, h, d)
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


def sequence_parallel_axial_attention(params, cfg, x, axis_name: str, mask=None, rng=None):
    """The trunk's axial attention, sequence-parallel over the grid's row
    axis (SURVEY.md §2.2: 'shard the folded-into-batch axis').

    Call inside `shard_map` with x (b, rows_local, cols, d) row-sharded over
    `axis_name` (and mask (b, rows_local, cols)). Semantics match
    ops.attention.axial_attention_apply for self-attention: the row pass is
    embarrassingly parallel (rows are the folded batch), the column pass
    runs after an `all_to_all` grid transpose, and the two results sum in
    the row-sharded layout. One all_to_all pair per call — the only
    communication.

    Tied-row attention needs a cross-shard logit psum and is not supported
    here; keep tied-row layers on the replicated path.

    Dropout: `rng` is folded with the shard index so masks are independent
    across shards (the exact single-device mask pattern is not reproduced —
    documented divergence; rng=None is bit-identical).
    """
    from alphafold2_tpu.ops.attention import attention_apply

    b, h_local, w, d = x.shape

    if rng is not None:
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axis_name))
        rng_col, rng_row = jax.random.split(rng)
    else:
        rng_col, rng_row = None, None

    # row pass: fold (sharded) rows into batch, attend along the full width
    row_x = x.reshape(b * h_local, w, d)
    row_mask = mask.reshape(b * h_local, w) if mask is not None else None
    row_out = attention_apply(
        params["attn_height"], cfg, row_x, mask=row_mask, rng=rng_row
    ).reshape(b, h_local, w, d)

    # column pass: transpose shard axis rows->cols, fold cols into batch
    xc = axial_alltoall_transpose(x, axis_name, row_sharded=True)  # (b, H, w/P, d)
    h_full, w_local = xc.shape[1], xc.shape[2]
    if mask is not None:
        mc = axial_alltoall_transpose(mask[..., None], axis_name, row_sharded=True)[..., 0]
        col_mask = jnp.swapaxes(mc, 1, 2).reshape(b * w_local, h_full)
    else:
        col_mask = None
    col_x = jnp.swapaxes(xc, 1, 2).reshape(b * w_local, h_full, d)
    col_out = attention_apply(
        params["attn_width"], cfg, col_x, mask=col_mask, rng=rng_col
    )
    col_out = jnp.swapaxes(col_out.reshape(b, w_local, h_full, d), 1, 2)
    col_out = axial_alltoall_transpose(col_out, axis_name, row_sharded=False)

    return row_out + col_out


def tied_row_attention_sharded(params, cfg, x, axis_name: str, mask=None, rng=None):
    """MSA tied-row attention with the ROW axis sharded over the mesh.

    Tied-row attention shares one logit matrix across all MSA rows
    (reference alphafold2.py:142-150; ops/attention.py tie_dim). When rows
    are sharded, each chip holds a partial logit sum over its resident
    rows; one `psum` over `axis_name` completes the contraction
    (SURVEY.md §2.2: 'if rows are sharded, logits need a psum over the
    row-shard axis'). Everything else — softmax, per-row value mixing,
    output projection — stays local.

    Call inside `shard_map`: x (b, r_local, n, dim) with the row axis
    sharded; mask (b, r_local, n). Exactly matches
    `attention_apply(..., tie_dim=r_total)` on the gathered rows (dropout
    included: the shared logits mean every shard must draw the same mask
    from the same key — do NOT fold in the shard index).

    Returns (b, r_local, n, dim).
    """
    from alphafold2_tpu.ops.core import dropout as _dropout, linear as _linear

    dtype = cfg.dtype
    b, r_local, n, _ = x.shape
    h, dh = cfg.heads, cfg.dim_head
    num_shards = jax.lax.psum(1, axis_name)
    r_total = r_local * num_shards

    q = _linear(params["to_q"], x, dtype=dtype)
    kv = _linear(params["to_kv"], x, dtype=dtype)
    k, v = jnp.split(kv, 2, axis=-1)
    q, k, v = (t.reshape(b, r_local, n, h, dh) for t in (q, k, v))

    # partial logit sum over resident rows, completed by ONE psum over ICI
    scale = dh ** -0.5 * r_total ** -0.5
    logits = jnp.einsum("brihd,brjhd->bhij", q, k).astype(jnp.float32) * scale
    logits = jax.lax.psum(logits, axis_name)

    if mask is not None:
        # a position is valid only if valid in EVERY row, across all shards
        # (ops/attention.py tie_dim mask collapse, generalized)
        local_all = jnp.all(mask, axis=1)  # (b, n)
        global_all = jax.lax.psum(local_all.astype(jnp.int32), axis_name) == num_shards
        pair = global_all[:, None, :, None] & global_all[:, None, None, :]
        logits = jnp.where(pair, logits, jnp.finfo(jnp.float32).min)

    attn = jax.nn.softmax(logits, axis=-1).astype(dtype)
    attn = _dropout(rng, attn, cfg.dropout)

    out = jnp.einsum("bhij,brjhd->brihd", attn, v).reshape(b, r_local, n, h * dh)
    if cfg.gate:
        # per-row output gate from the resident rows' own queries — the
        # sharded twin of attention_apply's epilogue (ops/flash.py
        # apply_output_gate), elementwise so no extra collective.
        # Direct attribute access on purpose: cfg is an AttentionConfig
        # (the caller passes self_attn_config()), and a wrong config
        # type must raise rather than silently skip the gate while
        # params["to_gate"] trains nowhere
        from alphafold2_tpu.ops.flash import apply_output_gate

        out = apply_output_gate(
            out, _linear(params["to_gate"], x, dtype=dtype)
        )
    return _linear(params["to_out"], out, dtype=dtype)


def axial_alltoall_transpose(x, axis_name: str, row_sharded: bool = True):
    """Swap the sharded grid axis of a pair-representation shard.

    x: (b, rows_local, cols, d) when `row_sharded` (-> (b, rows, cols_local, d)),
    or the mirror when not. One all_to_all on ICI; this is the only
    communication between the row pass and the column pass of sequence-
    parallel axial attention (SURVEY.md §2.2 'Ulysses-style transpose').
    """
    if row_sharded:
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)
    return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)
