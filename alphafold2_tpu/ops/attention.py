"""Dense, tied-row, KV-compressed, and axial attention.

TPU-native re-design of the reference attention stack
(reference alphafold2_pytorch/alphafold2.py:77-286):

  * `attention_apply` — multi-head attention with the reference's three fused
    modes: self/cross (optional `context`), memory-compressed KV (grouped
    strided conv over keys/values + sum-pooled mask,
    reference alphafold2.py:99-101,116-136), and tied-row attention (logits
    contracted over MSA rows with an extra r^-0.5 scale,
    reference alphafold2.py:142-150).
  * `axial_attention_apply` — factorized 2D attention over a (b, h, w, d)
    grid: one pass along each axis with the other folded into batch, results
    summed (reference alphafold2.py:240-286). The fold-into-batch axis is the
    natural sharding axis for sequence parallelism (see parallel/).

Everything is expressed as einsums over static shapes so XLA can tile the
contractions onto the MXU; softmax runs in float32 regardless of the compute
dtype.

Deliberate divergences from the reference (documented, not accidental):
  * KV compression always applies when compress_ratio > 1. The reference
    skips it entirely when the key length is an exact multiple of the ratio
    (`padding < ratio` guard, reference alphafold2.py:122) — a bug we do not
    reproduce.
  * Tied-row attention accepts a mask: columns masked in *any* row are
    masked for the shared logits (the reference hard-errors on any padding,
    reference alphafold2.py:147).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp

from alphafold2_tpu.ops.core import _uniform, linear, linear_init, dropout
from alphafold2_tpu.ops.flash import core_checkpoint_policy, flash_attention
from alphafold2_tpu.telemetry.profiling import scope

# switch to the blockwise path when the full logit tensor (B*h*i*j) would
# exceed this many elements (2^27 f32 = 512 MB)
_FLASH_AUTO_THRESHOLD = 1 << 27


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    """Static attention hyper-parameters (hashable; safe as a jit static arg)."""

    dim: int
    heads: int = 8
    dim_head: int = 64
    dropout: float = 0.0
    compress_ratio: int = 1  # KV compression for cross-attention, 1 = off
    dtype: Any = jnp.float32  # compute dtype (use bfloat16 on TPU)
    # blockwise (flash-style) streaming instead of materializing the full
    # logit tensor: True / False / "auto" (stream only when the logits would
    # exceed _FLASH_AUTO_THRESHOLD elements). Streaming is exact but skips
    # attention-probability dropout, so it is bypassed while attn dropout is
    # active. Not used for tied-row attention (its logits are already
    # row-contracted and small).
    flash: Union[bool, str] = "auto"
    # XLA streaming-path tile knobs (ignored by the Pallas kernel, which
    # picks its form and blocks from the shape): target logit-tile
    # elements and K/V streaming block. Bigger tiles = better MXU
    # utilization, more live memory — tune per chip generation
    flash_tile_elems: int = 1 << 25
    flash_kv_block: int = 2048
    # process the (folded) batch axis in chunks of this many elements under
    # jax.checkpoint (0 = off). Flash tiling bounds the LOGITS, but the
    # QKV/output projections still materialize over the whole folded batch —
    # at crop 384 the pair stream is 1.3M tokens, whose (tokens, 512)
    # projections are 1.3 GB each, and the reversible backward holds several
    # at once. Chunking the whole op (proj -> attend -> out-proj per chunk)
    # bounds all of them. Skipped for tied-row attention (chunks would split
    # tie groups) and while attention dropout is active (per-chunk keys
    # would change the mask pattern).
    batch_chunk: int = 0
    # sigmoid output gating (the AF2-style gate): out = sigmoid(W_g x + b_g)
    # * attention(x) before the output projection, gate weights initialized
    # (w=0, b=1) so a fresh gate starts nearly open. On the TPU kernel path
    # the gate is fused into the Pallas flash kernel's finish step
    # (ops/flash_kernel.py); elsewhere it is an exact epilogue.
    gate: bool = False

    @property
    def inner_dim(self) -> int:
        return self.heads * self.dim_head


# --- init -------------------------------------------------------------------


def attention_init(key, cfg: AttentionConfig):
    inner = cfg.inner_dim
    kq, kkv, ko, kc = jax.random.split(key, 4)
    params = {
        "to_q": linear_init(kq, cfg.dim, inner, bias=False),
        "to_kv": linear_init(kkv, cfg.dim, 2 * inner, bias=False),
        "to_out": linear_init(ko, inner, cfg.dim),
    }
    if cfg.gate:
        # near-open init (w=0, b=1 -> sigmoid(1) ~ 0.73): a freshly gated
        # model starts close to its ungated twin, so enabling the gate is
        # a benign fine-tune, not a re-initialization
        params["to_gate"] = {
            "w": jnp.zeros((cfg.dim, inner)),
            "b": jnp.ones((inner,)),
        }
    if cfg.compress_ratio > 1:
        # grouped strided conv over the key/value sequence, one group per head
        # (torch Conv1d(inner, inner, ratio, stride=ratio, groups=heads),
        # reference alphafold2.py:101). Kernel layout WIO for lax.conv.
        in_per_group = inner // cfg.heads
        bound = 1.0 / math.sqrt(in_per_group * cfg.compress_ratio)
        kw, kb = jax.random.split(kc)
        params["compress"] = {
            "w": _uniform(kw, (cfg.compress_ratio, in_per_group, inner), bound),
            "b": _uniform(kb, (inner,), bound),
        }
    return params


def axial_attention_init(key, cfg: AttentionConfig):
    k1, k2 = jax.random.split(key)
    return {
        "attn_width": attention_init(k1, cfg),
        "attn_height": attention_init(k2, cfg),
    }


# --- apply ------------------------------------------------------------------


def _compress_conv(params, cfg: AttentionConfig, t):
    """The grouped strided conv the compression paths share: stride-`ratio`
    windows, one feature group per head (torch Conv1d(inner, inner, ratio,
    stride=ratio, groups=heads), reference alphafold2.py:101). Also used by
    the sequence-parallel halo-exchange compression
    (parallel/sp_trunk.py `_compress_kv_sharded`) — the two paths must
    convolve identically or SP parity breaks."""
    w = params["compress"]["w"].astype(t.dtype)
    b = params["compress"]["b"].astype(t.dtype)
    out = jax.lax.conv_general_dilated(
        t,
        w,
        window_strides=(cfg.compress_ratio,),
        padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=cfg.heads,
    )
    return out + b


def _compress_kv(params, cfg: AttentionConfig, k, v, context_mask):
    """Downsample keys/values along the sequence with a grouped strided conv.

    k, v: (b, j, inner). Pads j up to a multiple of the ratio, then applies a
    stride-`ratio` conv with one feature group per head. The key mask is
    sum-pooled: a compressed position is valid if any source position was
    (reference alphafold2.py:116-136).
    """
    ratio = cfg.compress_ratio
    j = k.shape[-2]
    pad = (-j) % ratio
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        if context_mask is not None:
            context_mask = jnp.pad(context_mask, ((0, 0), (0, pad)))

    k = _compress_conv(params, cfg, k)
    v = _compress_conv(params, cfg, v)
    if context_mask is not None:
        pooled = jnp.sum(
            context_mask.astype(jnp.float32).reshape(context_mask.shape[0], -1, ratio),
            axis=-1,
        )
        context_mask = pooled > 0
    return k, v, context_mask


def attention_apply(
    params,
    cfg: AttentionConfig,
    x,
    *,
    context=None,
    mask=None,
    context_mask=None,
    tie_dim: Optional[int] = None,
    rng=None,
):
    """Multi-head attention.

    Args:
      x: queries, (b, i, dim).
      context: keys/values source, (b, j, dim); self-attention when None.
      mask: (b, i) bool query validity.
      context_mask: (b, j) bool key validity (defaults to `mask` for
        self-attention, all-valid for cross-attention —
        reference alphafold2.py:156-158).
      tie_dim: if given, x is (b*tie_dim, i, dim) and attention logits are
        shared across the tie_dim groups (MSA tied-row attention).
      rng: dropout key (None = deterministic).

    Returns: (b, i, dim) in cfg.dtype.
    """
    has_context = context is not None
    dropout_live = rng is not None and cfg.dropout > 0.0
    if (
        cfg.batch_chunk
        and x.shape[0] > cfg.batch_chunk
        and tie_dim is None
        and not dropout_live
    ):
        return _batch_chunked_attention(
            params, cfg, x, context=context, mask=mask, context_mask=context_mask
        )
    ctx = context if has_context else x
    dtype = cfg.dtype

    h, dh = cfg.heads, cfg.dim_head
    scale = dh ** -0.5
    compress = cfg.compress_ratio > 1 and has_context

    def streams(j):
        """Whether the core streams (ops/flash.py) instead of
        materializing the full logit tensor, at key length j."""
        return tie_dim is None and not dropout_live and (
            cfg.flash is True or (
                cfg.flash == "auto"
                and x.shape[0] * h * x.shape[1] * j > _FLASH_AUTO_THRESHOLD))

    with scope("qkv_proj"):
        q = linear(params["to_q"], x, dtype=dtype)
        if not compress and streams(ctx.shape[1]):
            # K and V as two projections from the halves of the weight:
            # the streaming core's kernel arm takes them as two arrays, and
            # two slices of one fused output would each be copied through
            # HBM on the way in (qkv_proj +20% at the pair stream's shape)
            k, v = (
                linear({name: jnp.split(t, 2, axis=-1)[half]
                        for name, t in params["to_kv"].items()},
                       ctx, dtype=dtype)
                for half in (0, 1)
            )
        else:
            kv = linear(params["to_kv"], ctx, dtype=dtype)
            k, v = jnp.split(kv, 2, axis=-1)

    if compress:
        with scope("kv_compress"):
            k, v, context_mask = _compress_kv(params, cfg, k, v, context_mask)

    def split_heads(t):
        b, n, _ = t.shape
        return t.reshape(b, n, h, dh)

    q, k, v = split_heads(q), split_heads(k), split_heads(v)
    i, j = q.shape[1], k.shape[1]
    # pre-sigmoid output-gate logits from the QUERY stream (cfg.gate):
    # fused into the Pallas kernel on the flash path, exact epilogue on
    # the dense/tied paths — both multiply sigmoid(gate) into the head
    # outputs before to_out
    gate_logits = None
    if cfg.gate:
        with scope("qkv_proj"):
            gate_logits = linear(params["to_gate"], x, dtype=dtype)

    # blockwise streaming path: same math, bounded memory (see ops/flash.py).
    # Key-side masking only — masked query rows yield finite garbage masked
    # downstream, exactly like the dense path's uniform-attention rows.
    if streams(j):
        if context_mask is None and mask is not None and not has_context:
            context_mask = mask
        key_bias = (
            None
            if context_mask is None
            else jnp.where(
                jnp.broadcast_to(context_mask, (k.shape[0], j)),
                0.0,
                float("-inf"),
            ).astype(jnp.float32)
        )
        # Pallas fused kernel on TPU (supported shapes), XLA streaming
        # otherwise (ops/dispatch.py decides; ops/flash.py names it
        # `attn_core`)
        out = flash_attention(
            q, k, v, key_bias, scale=scale,
            gate=(
                gate_logits.reshape(gate_logits.shape[0], i, h, dh)
                if gate_logits is not None else None
            ),
            tile_elems=cfg.flash_tile_elems, kv_block=cfg.flash_kv_block,
        )
        out = out.reshape(out.shape[0], i, h * dh)
    else:
        with scope("attn_core"):
            out = _dense_attention(cfg, q, k, v, mask, context_mask, tie_dim,
                                   has_context, rng, gate_logits)
    with scope("out_proj"):
        return linear(params["to_out"], out, dtype=dtype)


def _dense_attention(cfg, q, k, v, mask, context_mask, tie_dim, has_context,
                     rng, gate_logits):
    """The materialized-logits arm of `attention_apply`: q, k, v split by
    head, (b, n, h, dh); returns the head outputs, (b, i, h * dh)."""
    h, dh = cfg.heads, cfg.dim_head
    scale = dh ** -0.5
    i, j = q.shape[1], k.shape[1]
    dtype = cfg.dtype
    if tie_dim is not None:
        # (b*r, n, h, dh) -> (b, r, n, h, dh); share logits across rows r with
        # the extra r^-0.5 scale (reference alphafold2.py:142-150).
        r = tie_dim
        q, k, v = (t.reshape(-1, r, t.shape[1], h, dh) for t in (q, k, v))
        logits = jnp.einsum("brihd,brjhd->bhij", q, k) * (scale * r ** -0.5)
        # collapse per-row masks to the tied batch: a position is valid only
        # if valid in every row (generalizes the reference's all-valid
        # requirement, reference alphafold2.py:147).
        if mask is not None:
            mask = jnp.all(mask.reshape(-1, r, mask.shape[-1]), axis=1)
        if context_mask is not None and context_mask.shape[0] == r * logits.shape[0]:
            context_mask = jnp.all(
                context_mask.reshape(-1, r, context_mask.shape[-1]), axis=1
            )
    else:
        logits = jnp.einsum("bihd,bjhd->bhij", q, k) * scale

    if mask is not None or context_mask is not None:
        if mask is None:
            mask = jnp.ones((1, i), dtype=bool)
        if context_mask is None:
            context_mask = mask if not has_context else jnp.ones((1, j), dtype=bool)
        pair_mask = mask[:, None, :, None] & context_mask[:, None, None, :]
        logits = jnp.where(pair_mask, logits, jnp.finfo(jnp.float32).min)

    attn = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(dtype)
    attn = dropout(rng, attn, cfg.dropout)

    if tie_dim is not None:
        out = jnp.einsum("bhij,brjhd->brihd", attn, v)
        out = out.reshape(-1, i, h * dh)
    else:
        out = jnp.einsum("bhij,bjhd->bihd", attn, v)
        out = out.reshape(out.shape[0], i, h * dh)

    if gate_logits is not None:
        from alphafold2_tpu.ops.flash import apply_output_gate

        out = apply_output_gate(out, gate_logits)
    return out


def _checkpointed_chunk(body):
    """One batch chunk's whole op under its `jax.checkpoint`. What it keeps
    for the backward pass is the attention kernel's `out` and `lse`, where
    the chunk's core is the whole-row kernel (one more activation's width
    over the folded batch, and the kernel's forward is not run again); q, k,
    v are three times that and are built again, as is everything else. A
    chunk whose core is the XLA streaming arm or the materialized-logits
    arm carries no such name and is recomputed whole."""
    return jax.checkpoint(body, policy=core_checkpoint_policy())


def _batch_chunked_attention(params, cfg: AttentionConfig, x, *, context, mask, context_mask):
    """Run attention_apply in chunks over the (folded) batch axis.

    Each chunk re-runs the full op (QKV projection, attention, output
    projection) under jax.checkpoint (`_checkpointed_chunk`), so no
    projection ever materializes over the whole folded batch — the memory
    bound that lets the crop-384 pair stream (1.3M tokens) run on one chip.
    Deterministic (no-dropout) path only; the caller gates on that.
    """
    B = x.shape[0]
    chunk = cfg.batch_chunk
    inner_cfg = dataclasses.replace(cfg, batch_chunk=0)

    pad = (-B) % chunk
    arrays = {"x": x, "context": context, "mask": mask, "context_mask": context_mask}
    padded = {}
    for name, t in arrays.items():
        if t is None:
            padded[name] = None
            continue
        if t.shape[0] == 1 and B > 1:  # broadcast batch: share across chunks
            padded[name] = t
            continue
        if pad:
            t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        padded[name] = t.reshape((-1, chunk) + t.shape[1:])

    def body(i):
        def pick(name):
            t = padded[name]
            if t is None or t.shape[0] != (B + pad) // chunk:
                return t  # None or broadcast
            return t[i]

        return attention_apply(
            params,
            inner_cfg,
            pick("x"),
            context=pick("context"),
            mask=pick("mask"),
            context_mask=pick("context_mask"),
        )

    nb = (B + pad) // chunk
    out = jax.lax.map(_checkpointed_chunk(body), jnp.arange(nb))
    out = out.reshape((nb * chunk,) + out.shape[2:])
    return out[:B] if pad else out


def axial_attention_apply(
    params,
    cfg: AttentionConfig,
    x,
    *,
    mask=None,
    context=None,
    context_mask=None,
    tie_row: bool = False,
    rng=None,
    attention_fn=None,
):
    """Factorized 2D attention over a grid.

    Args:
      x: (b, h, w, d) grid — the pair representation (i, j) or MSA
        (rows, cols).
      mask: (b, h, w) bool.
      context / context_mask: optional cross-attention source (b, n, d) /
        (b, n), broadcast to every folded row/column
        (reference alphafold2.py:269-273).
      tie_row: tie attention across the h axis on the width pass (MSA
        tied-row attention; reference alphafold2.py:280-282).
      attention_fn: override the inner attention (e.g. block-sparse); called
        as `attention_fn(axis_params, x, *, axis, mask, tie_dim, rng,
        [context, context_mask])` where `axis` is "width" (column pass) or
        "height" (row pass) and `axis_params` is that pass's parameter
        subtree.

    Two passes, summed:
      * column pass — attend along h, w folded into batch;
      * row pass — attend along w, h folded into batch (tied over h if
        tie_row).
    """
    inner = attention_fn
    b, hh, ww, d = x.shape

    rng_col, rng_row = (jax.random.split(rng) if rng is not None else (None, None))

    def run(p, t, m, cm_ctx, tie_dim, r, axis):
        if inner is not None:
            return inner(p, t, axis=axis, mask=m, tie_dim=tie_dim, rng=r, **cm_ctx)
        return attention_apply(p, cfg, t, mask=m, tie_dim=tie_dim, rng=r, **cm_ctx)

    # column pass: fold w into batch, attend along h
    col_x = jnp.swapaxes(x, 1, 2).reshape(b * ww, hh, d)
    col_mask = (
        jnp.swapaxes(mask, 1, 2).reshape(b * ww, hh) if mask is not None else None
    )
    ctx_kwargs_col = {}
    if context is not None:
        ctx_kwargs_col = {
            "context": jnp.repeat(context, ww, axis=0),
            "context_mask": (
                jnp.repeat(context_mask, ww, axis=0) if context_mask is not None else None
            ),
        }
    col_out = run(
        params["attn_width"], col_x, col_mask, ctx_kwargs_col, None, rng_col, "width"
    )
    col_out = jnp.swapaxes(col_out.reshape(b, ww, hh, d), 1, 2)

    # row pass: fold h into batch, attend along w (optionally tied across h)
    row_x = x.reshape(b * hh, ww, d)
    row_mask = mask.reshape(b * hh, ww) if mask is not None else None
    ctx_kwargs_row = {}
    if context is not None:
        ctx_kwargs_row = {
            "context": jnp.repeat(context, hh, axis=0),
            "context_mask": (
                jnp.repeat(context_mask, hh, axis=0) if context_mask is not None else None
            ),
        }
    tie_dim = hh if tie_row else None
    row_out = run(
        params["attn_height"], row_x, row_mask, ctx_kwargs_row, tie_dim, rng_row, "height"
    )
    row_out = row_out.reshape(b, hh, ww, d)

    return col_out + row_out
