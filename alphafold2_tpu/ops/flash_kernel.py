"""Pallas TPU kernel for DENSE flash attention (forward + backward).

The fused fast path under ops/flash.py's blockwise streaming: QK^T ->
streaming softmax -> AV runs entirely in VMEM per (query-block, key-block)
tile, so logits never round-trip HBM — the traffic the XLA-level
`stream_block` scan pays between accumulation steps. Sibling of the
block-sparse kernel (ops/sparse_kernel.py), without the index table, and
supporting CROSS attention (query and key lengths differ) — the shape the
aligned cross-attention mode produces (models/trunk.py).

Two forms, chosen from the shape by `flash_attention_bnhd`: the
WHOLE-ROW form (second half of this file) where every key of a
(batch, head group) fits one grid step — the pair stream's axial passes,
i = j = 1152 — and the STREAMING form below everywhere else.

Streaming layout: each kernel runs a 3-D grid whose LAST dimension walks
the contraction blocks sequentially (dimension_semantics "arbitrary") with
running statistics in VMEM scratch, while Mosaic's pipeline double-buffers
the K/V (or Q/G) block fetches. Nothing is ever fully VMEM-resident per
grid row — unlike the previous design (whole K/V held per (batch*head)
row), the supported length is bounded only by the f32 row vectors (bias,
lse, delta) at 4 bytes per position, so the kernel also covers the long-j
flat cross-attention shapes that previously fell back to XLA streaming.

Layout and numerics follow ops/sparse_kernel.py: (b*h, n, dh) flattened
heads, float32 streaming statistics, finite running-max sentinel (_M0) so
masked logits (-inf bias) underflow to exact 0 with no nan-guard passes,
key-side additive bias only (ops/flash.py contract; fully-masked rows
return zeros, +inf lse makes the backward's recomputed p vanish). Dots
take operands in the INPUT dtype with f32 accumulation
(preferred_element_type): bf16 operands keep the MXU at its bf16 peak.
Backward recomputes tile logits from the saved lse: a dq kernel streams
key blocks per query block; a dk/dv kernel streams query blocks per key
block. On non-TPU backends the kernels run in interpreter mode (tests),
keeping one code path.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from alphafold2_tpu import compat
from alphafold2_tpu.compat import pallas as pl, pallas_tpu as pltpu
from alphafold2_tpu.ops.core import pallas_interpret as _interpret

_NEG = float("-inf")
# finite running-max sentinel: keeps the streaming-softmax recurrence free
# of (-inf) - (-inf) = nan without per-tile isneginf/where passes. Logits
# below this are treated as fully masked (the standard flash-kernel trade).
_M0 = -1e30

# VMEM budget for the per-grid-row RESIDENT operands: the f32 row vectors
# only (key bias at 4 B/key; lse + delta at 8 B/query in the backward).
# Blocks stream; ~12 MB leaves headroom under the ~16 MB/core VMEM for the
# double-buffered tiles and scratch.
_VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def supported(i: int, j: int, dh: int) -> bool:
    """Shapes the kernel handles; everything else streams via XLA.

    Only the f32 row vectors are VMEM-resident per (batch*head) grid row
    (bias: 4j bytes; lse + delta: 8i bytes in the backward) — K/V and Q/G
    blocks stream through the grid's sequential dimension.
    """
    resident = 4 * j + 8 * i
    return resident <= _VMEM_BUDGET_BYTES and dh % 8 == 0 and dh <= 512


def supported_fused(i: int, j: int, dh: int) -> bool:
    """Shapes the FUSED-epilogue kernel handles (`flash_attention_fused`:
    2-D pair-bias tiles and/or in-kernel sigmoid output gating).

    The 2-D bias streams block-by-block like K/V (never row-resident) and
    the gate streams with the query block, so the VMEM residency bound is
    the same row-vector budget as the plain kernel — kept identical so
    one `supported` story covers both dispatch gates."""
    return supported(i, j, dh)


def pick_block(n: int, target: int = 512, mult: int = 128, tol: float = 0.15) -> int:
    """Pick a Pallas block size for a length-n axis.

    Among multiples of `mult` (MXU-friendly) up to `target`, take the
    LARGEST block whose padded length is within `tol` of the minimum
    achievable — large blocks amortize grid/loop overhead, but gross
    padding waste is real FLOPs: n=1152 picks 384 (zero padding) where a
    fixed 512 pads to 1536 (+33%), while n=896 keeps 512 (+14% padding
    beats 7x the grid steps of 128). The tol knob is a heuristic pending
    on-chip measurement (PERF.md)."""
    if n <= mult:
        return mult
    padded = {b: ((n + b - 1) // b) * b for b in range(mult, target + 1, mult)}
    best = min(padded.values())
    return max(b for b, p in padded.items() if p <= best * (1 + tol))


def _block_target(dh: int) -> int:
    """Cap block size so per-grid-step tiles fit VMEM: the worst kernel
    step holds ~6 f32 tiles of (block, dh) plus a (qb, kb) logit tile,
    double-buffered. dh=64 (the framework's head dim) keeps the full 512;
    dh=512 drops to 256."""
    return max(128, min(512, (4 << 20) // (24 * dh) // 128 * 128))


# vma-aware ShapeDtypeStruct (union of the operands' varying-across-mesh-
# axes sets) — required for pallas_call under shard_map with vma checking
# (e.g. the ring-attention hops); plain struct on pre-vma JAX.
_out_struct = compat.out_struct


def _pad_args(q, k, v, bias, qb, kb):
    """Pad query/key lengths to block multiples (-inf bias on padded keys)."""
    BH, i, dh = q.shape
    j = k.shape[1]
    pad_i = (-i) % qb
    pad_j = (-j) % kb
    if pad_i:
        q = jnp.pad(q, ((0, 0), (0, pad_i), (0, 0)))
    if pad_j:
        k = jnp.pad(k, ((0, 0), (0, pad_j), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_j), (0, 0)))
        bias = jnp.pad(bias, ((0, 0), (0, pad_j)), constant_values=_NEG)
    return q, k, v, bias, i + pad_i, j + pad_j


# Backward kernels: first two grid dims parallel (their output windows are
# private per (b, block) pair), streamed contraction dim sequential.
_BWD_PARAMS = compat.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)
# Forward: the lse output window (1, nqb, qb) is SHARED across qi, so qi
# must not be split across megacore TPU cores (each core's private copy of
# the whole window would clobber the other's rows on write-back) — qi runs
# sequentially; the (batch*head) dim carries all the parallelism.
_FWD_PARAMS = compat.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary")
)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, out_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, nkb, scale):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _M0, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[0]          # (qb, dh), input dtype
    k = k_ref[0]          # (kb, dh)
    v = v_ref[0]
    b = bias_ref[0, ki]   # (kb,) f32, resident row vector
    s = jax.lax.dot_general(
        q, k,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale + b[None, :]

    m = m_scr[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(ki == nkb - 1)
    def _finish():
        l = l_scr[...]
        safe = jnp.where(l > 0, l, 1.0)
        out_ref[0] = jnp.where(l > 0, acc_scr[...] / safe, 0.0).astype(
            out_ref.dtype
        )
        # +inf for rows with no active mass: exp(s - inf) = 0 zeroes every
        # recomputed p in the backward (lse rides as a resident
        # (1, nQB, qb) block — Mosaic rejects (1, qb) row blocks)
        lse = jnp.where(l > 0, m_scr[...] + jnp.log(safe), jnp.inf)
        lse_ref[0, qi] = lse[:, 0]


def _forward(q, k, v, bias, scale, qb, kb):
    """q: (BH, i, dh); k, v: (BH, j, dh); bias: (BH, j) additive f32."""
    BH, i0, dh = q.shape
    j0 = k.shape[1]
    q, k, v, bias, i, j = _pad_args(q, k, v, bias, qb, kb)
    nqb, nkb = i // qb, j // kb
    bias3 = bias.reshape(BH, nkb, kb)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, nkb=nkb, scale=scale),
        out_shape=[
            _out_struct((BH, i, dh), q.dtype, q, k, v, bias3),
            _out_struct((BH, nqb, qb), jnp.float32, q, k, v, bias3),
        ],
        grid=(BH, nqb, nkb),
        in_specs=[
            pl.BlockSpec((1, qb, dh), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, kb, dh), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, kb, dh), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, nkb, kb), lambda b, qi, ki: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, qb, dh), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, nqb, qb), lambda b, qi, ki: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, dh), jnp.float32),
        ],
        compiler_params=_FWD_PARAMS,
        interpret=_interpret(),
    )(q, k, v, bias3)
    return out[:, :i0], (q, k, v, bias3, lse, i0, j0)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, bias_ref, g_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, *, nkb, scale):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    q = q_ref[0]
    g = g_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    b = bias_ref[0, ki]
    lse = lse_ref[0, qi][:, None]
    delta = delta_ref[0, qi][:, None]

    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale + b[None, :]
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        g, v, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # ds in the operand dtype: bf16 ds @ k on the MXU bf16 path — the
    # standard flash-backward precision trade (f32 accumulate)
    ds = (p * (dp - delta)).astype(k.dtype)
    dq_scr[...] = dq_scr[...] + jnp.dot(
        ds, k, preferred_element_type=jnp.float32
    )

    @pl.when(ki == nkb - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, bias_ref, g_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, nqb, scale):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    k = k_ref[0]                      # (kb, dh)
    v = v_ref[0]
    q = q_ref[0]                      # (qb, dh)
    g = g_ref[0]
    b = bias_ref[0, ki]               # (kb,)
    lse = lse_ref[0, qi][:, None]
    delta = delta_ref[0, qi][:, None]

    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale + b[None, :]
    p = jnp.exp(s - lse)              # (qb, kb) f32
    dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
        p.astype(g.dtype), g, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dp = jax.lax.dot_general(
        g, v, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = (p * (dp - delta)).astype(q.dtype)
    dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
        ds, q, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(qi == nqb - 1)
    def _finish():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_core(q, k, v, key_bias, scale, qb, kb):
    out, _ = _forward(q, k, v, key_bias, scale, qb, kb)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_core_lse(q, k, v, key_bias, scale, qb, kb):
    out, (_, _, _, _, lse, i0, _) = _forward(q, k, v, key_bias, scale, qb, kb)
    return out, lse.reshape(lse.shape[0], -1)[:, :i0]


def flash_attention_lse(q, k, v, key_bias, scale, qb=None, kb=None):
    """`flash_attention_tpu` that ALSO returns the per-row log-sum-exp.

    Returns (out (BH, i, dh), lse (BH, i) f32). lse is +inf for rows with
    no unmasked keys (zero attention mass — note the INVERTED convention
    vs the usual -inf-for-empty: +inf makes the backward's recomputed
    p = exp(s - lse) vanish). Differentiable in q/k/v including through
    lse — the lse cotangent folds into the softmax-jacobian diagonal
    (delta_eff = delta - g_lse), so the backward kernels are shared with
    the plain path. This is the building block for cross-chip softmax
    combination (ring attention, parallel/sequence.py).
    """
    dh = q.shape[-1]
    qb = pick_block(q.shape[1], target=_block_target(dh)) if qb is None else qb
    kb = pick_block(k.shape[1], target=_block_target(dh)) if kb is None else kb
    return _flash_core_lse(q, k, v, key_bias, scale, qb, kb)


def flash_attention_tpu(q, k, v, key_bias, scale, qb=None, kb=None):
    """Fused dense flash attention. q: (BH, i, dh); k, v: (BH, j, dh);
    key_bias: (BH, j) additive f32 (0 valid / -inf masked). Returns
    (BH, i, dh). The bias cotangent is not computed (masks are data, not
    parameters). qb/kb: query/key block sizes (None = padding-aware pick)."""
    dh = q.shape[-1]
    qb = pick_block(q.shape[1], target=_block_target(dh)) if qb is None else qb
    kb = pick_block(k.shape[1], target=_block_target(dh)) if kb is None else kb
    return _flash_core(q, k, v, key_bias, scale, qb, kb)


def _fwd(q, k, v, key_bias, scale, qb, kb):
    out, (qp, kp, vp, bias3, lse, i0, j0) = _forward(q, k, v, key_bias, scale, qb, kb)
    return out, (qp, kp, vp, bias3, lse, out, i0, j0)


def _bwd_impl(scale, qb, kb, res, g, g_lse=None):
    qp, kp, vp, bias3, lse, out, i0, j0 = res
    BH, i, dh = qp.shape
    j = kp.shape[1]
    nqb, nkb = i // qb, j // kb

    pad_i = i - i0
    if pad_i:
        g = jnp.pad(g, ((0, 0), (0, pad_i), (0, 0)))
        out = jnp.pad(out, ((0, 0), (0, pad_i), (0, 0)))

    # delta_i = rowsum(dO_i * O_i), the softmax-jacobian diagonal term.
    # An lse cotangent folds in here: d lse_i / d s_ij = p_ij, so
    # ds_ij = p_ij * (dp_ij - (delta_i - glse_i)) — same kernels, shifted
    # diagonal
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )
    if g_lse is not None:
        glse = g_lse.astype(jnp.float32)
        if pad_i:
            glse = jnp.pad(glse, ((0, 0), (0, pad_i)))
        delta = delta - glse
    delta = delta.reshape(BH, nqb, qb)

    blk_q = pl.BlockSpec((1, qb, dh), lambda b, x, y: (b, x, 0))
    blk_q_inner = pl.BlockSpec((1, qb, dh), lambda b, x, y: (b, y, 0))
    blk_k = pl.BlockSpec((1, kb, dh), lambda b, x, y: (b, x, 0))
    blk_k_inner = pl.BlockSpec((1, kb, dh), lambda b, x, y: (b, y, 0))
    rows_q = pl.BlockSpec((1, nqb, qb), lambda b, x, y: (b, 0, 0))
    rows_k = pl.BlockSpec((1, nkb, kb), lambda b, x, y: (b, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nkb=nkb, scale=scale),
        out_shape=_out_struct((BH, i, dh), qp.dtype, qp, kp, vp, g),
        grid=(BH, nqb, nkb),
        in_specs=[blk_q, blk_k_inner, blk_k_inner, rows_k, blk_q,
                  rows_q, rows_q],
        out_specs=blk_q,
        scratch_shapes=[pltpu.VMEM((qb, dh), jnp.float32)],
        compiler_params=_BWD_PARAMS,
        interpret=_interpret(),
    )(qp, kp, vp, bias3, g, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nqb=nqb, scale=scale),
        out_shape=[
            _out_struct((BH, j, dh), kp.dtype, qp, kp, vp, g),
            _out_struct((BH, j, dh), vp.dtype, qp, kp, vp, g),
        ],
        grid=(BH, nkb, nqb),
        in_specs=[blk_q_inner, blk_k, blk_k, rows_k, blk_q_inner,
                  rows_q, rows_q],
        out_specs=[blk_k, blk_k],
        scratch_shapes=[
            pltpu.VMEM((kb, dh), jnp.float32),
            pltpu.VMEM((kb, dh), jnp.float32),
        ],
        compiler_params=_BWD_PARAMS,
        interpret=_interpret(),
    )(qp, kp, vp, bias3, g, lse, delta)

    # cotangents must match the ORIGINAL (unpadded) primal shapes; the bias
    # is a mask, not a parameter — its cotangent is declared zero
    return (
        dq[:, :i0],
        dk[:, :j0],
        dv[:, :j0],
        jnp.zeros((qp.shape[0], j0), jnp.float32),
    )


def _bwd(scale, qb, kb, res, g):
    return _bwd_impl(scale, qb, kb, res, g)


_flash_core.defvjp(_fwd, _bwd)


def _fwd_lse(q, k, v, key_bias, scale, qb, kb):
    out, (qp, kp, vp, bias3, lse, i0, j0) = _forward(q, k, v, key_bias, scale, qb, kb)
    lse_flat = lse.reshape(lse.shape[0], -1)[:, :i0]
    return (out, lse_flat), (qp, kp, vp, bias3, lse, out, i0, j0)


def _bwd_lse(scale, qb, kb, res, gs):
    g, g_lse = gs
    return _bwd_impl(scale, qb, kb, res, g, g_lse=g_lse)


_flash_core_lse.defvjp(_fwd_lse, _bwd_lse)


# ---------------------------------------------------------------------------
# whole-row form: one grid step holds every key of a (batch, head group)
# ---------------------------------------------------------------------------
#
# At the pair stream's axial shape (i = j = 1152, dh = 64) a whole
# (1152, 1152) f32 logit tile is 5.3 MB: it fits VMEM, so nothing streams.
# One grid step takes a (batch, head GROUP) row straight out of the
# model's (B, n, h*dh) layout — the group is the heads that fill 128
# lanes (two at dh = 64), picked by the BlockSpec's index, so q/k/v/out
# never transpose through HBM — and walks the queries in chunks of
# `rows` with K and V resident:
#
#   * no running max, no alpha, no accumulator read-modify-write: max,
#     exp, row sum and the AV dot see every key at once;
#   * a head's logits come from the 128-lane q block against K with the
#     OTHER heads' lanes zeroed. The MXU pads a 64-deep contraction to
#     128 anyway, so the zeros cost nothing and no lane is ever sliced;
#     p @ (V with the other lanes zeroed) lands each head's output in its
#     own lanes of one lane-dense (rows, 128) tile;
#   * K and V are transposed ONCE a grid step, so every dot in the
#     chunk loop is a plain (M, K) @ (K, N);
#   * the backward is ONE kernel: s and p are recomputed once and feed
#     dq (written a chunk at a time) and the dk / dv accumulators;
#     delta = rowsum(dO * O) is taken in the kernel from the O block.
#
# Numerics are the streaming form's: f32 logits from the dot's
# accumulator, finite max sentinel, p -> operand dtype for AV and dv,
# ds -> operand dtype for dq / dk, +inf lse on zero-mass rows.

# whole-row VMEM ceiling: what one grid step may plan (double-buffered
# blocks + resident K / K^T / V^T + the chunk's logit tiles). A shape
# over it streams through the 3-D grid above instead.
_ROWS_VMEM_CAP = 48 * 1024 * 1024
# largest f32 (rows, j) logit tile inside a grid step: the whole
# 1152 x 1152 tile (5.1 MiB) is one chunk — measured 5% faster than three
# chunks of 384 (5.9 against 6.2 us a row forward, PERF.md section 5)
_ROWS_TILE_BYTES = 6 * 1024 * 1024
# past this many keys K and V stream: the form measured for j >= 4096
_ROWS_MAX_KEYS = 2048


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _rows_vmem_bytes(ip, jp, g, lanes, rows, itemsize):
    """Planned VMEM of the backward step (the larger of the two
    kernels): q, o, dO, dq and k, v, dk, dv blocks double-buffered, the
    f32 dk / dv accumulators, K / K^T / V^T per head of the group, and
    six live (rows, j) f32 tiles."""
    blocks = 2 * 4 * (ip + jp) * lanes * itemsize
    resident = 2 * jp * lanes * 4 + 3 * g * jp * lanes * itemsize
    return blocks + resident + 6 * rows * jp * 4


def rows_plan(i: int, j: int, h: int, dh: int, itemsize: int = 2):
    """(heads a grid step, query rows a chunk) when the whole-row form
    takes (i, j, h, dh), else None (the shape streams).

    Heads group to 128 lanes (dh = 64 -> 2, dh = 32 -> 4; dh a multiple
    of 128 -> 1), so h must divide by the group. `rows` is the largest
    128-multiple divisor of the padded i whose f32 logit tile stays
    within _ROWS_TILE_BYTES (1152 x 1152 -> all 1152 rows)."""
    if dh % 128 == 0:
        g = 1
    elif 128 % dh == 0:
        g = 128 // dh
    else:
        return None
    ip, jp = _round_up(i, 128), _round_up(j, 128)
    if h % g or jp > _ROWS_MAX_KEYS:
        return None
    n = ip // 128
    rows = 128 * max(
        d for d in range(1, n + 1)
        if n % d == 0 and 128 * d * jp * 4 <= _ROWS_TILE_BYTES
    )
    if _rows_vmem_bytes(ip, jp, g, g * dh, rows, itemsize) > _ROWS_VMEM_CAP:
        return None
    return g, rows


def _rows_params(ip, jp, g, lanes, rows, itemsize):
    est = _rows_vmem_bytes(ip, jp, g, lanes, rows, itemsize)
    return compat.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        # from the shape: twice the plan (Mosaic's own temporaries), never
        # under its 16 MiB default
        vmem_limit_bytes=max(16 << 20, 2 * est),
    )


def _head_sels(g, dh, lanes):
    """Per head of the group, the (1, lanes) mask of ITS lanes; [None]
    when the group is one head."""
    if g == 1:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    return [(lane >= hh * dh) & (lane < (hh + 1) * dh) for hh in range(g)]


def _keep(sel, x):
    return x if sel is None else jnp.where(sel, x, 0.0)


def _chunks(n_chunks, body):
    """Run body(c) for every query chunk: inline when there is one."""
    if n_chunks == 1:
        body(0)
    else:
        def step(c, carry):
            body(c)
            return carry

        jax.lax.fori_loop(0, n_chunks, step, 0)


def _chunk_start(c, rows):
    return c * rows if isinstance(c, int) else pl.multiple_of(c * rows, rows)


def _rows_fwd_kernel(q_ref, k_ref, v_ref, bias_ref, out_ref, lse_ref, *,
                     scale, g, dh, rows, n_chunks):
    dtype = k_ref.dtype
    lanes = k_ref.shape[-1]
    k32 = k_ref[0].astype(jnp.float32)        # (j, lanes)
    v32 = v_ref[0].astype(jnp.float32)
    b = bias_ref[0]                           # (1, j) f32
    sels = _head_sels(g, dh, lanes)
    # resident per head: K^T (lanes, j) and V (j, lanes), other heads zeroed
    kts = [_keep(sel, k32).T.astype(dtype) for sel in sels]
    vs = [_keep(sel, v32).astype(dtype) for sel in sels]

    def chunk(c):
        r0 = _chunk_start(c, rows)
        q = q_ref[0, pl.ds(r0, rows), :]      # (rows, lanes)
        out = jnp.zeros((rows, lanes), jnp.float32)
        for hh in range(g):
            s = jnp.dot(q, kts[hh], preferred_element_type=jnp.float32)
            s = s * scale + b                 # (rows, j) f32
            m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), _M0)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            o = jnp.dot(p.astype(dtype), vs[hh],
                        preferred_element_type=jnp.float32)
            safe = jnp.where(l > 0, l, 1.0)
            out = out + jnp.where(l > 0, o / safe, 0.0)
            # +inf on zero-mass rows, as the streaming form
            lse = jnp.where(l > 0, m + jnp.log(safe), jnp.inf)
            lse_ref[0, hh, c] = lse[:, 0]
        out_ref[0, pl.ds(r0, rows), :] = out.astype(out_ref.dtype)

    _chunks(n_chunks, chunk)


# contract dim 0 of both operands: (rows, j)^T @ (rows, lanes) -> (j, lanes)
_TN = (((0,), (0,)), ((), ()))


def _rows_bwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, do_ref, lse_ref,
                     dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                     scale, g, dh, rows, n_chunks):
    dtype = k_ref.dtype
    lanes = k_ref.shape[-1]
    k32 = k_ref[0].astype(jnp.float32)
    v32 = v_ref[0].astype(jnp.float32)
    b = bias_ref[0]
    sels = _head_sels(g, dh, lanes)
    # resident per head, other heads zeroed: K (j, lanes), K^T, V^T
    ks = [_keep(sel, k32) for sel in sels]
    kts = [t.T.astype(dtype) for t in ks]
    ks = [t.astype(dtype) for t in ks]
    vts = [_keep(sel, v32).T.astype(dtype) for sel in sels]
    dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
    dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def chunk(c):
        r0 = _chunk_start(c, rows)
        q = q_ref[0, pl.ds(r0, rows), :]
        do = do_ref[0, pl.ds(r0, rows), :]
        q32, do32 = q.astype(jnp.float32), do.astype(jnp.float32)
        # delta's summand; each head sums its own lanes below
        do_o = do32 * o_ref[0, pl.ds(r0, rows), :].astype(jnp.float32)
        dq = jnp.zeros((rows, lanes), jnp.float32)
        for hh in range(g):
            delta = jnp.sum(_keep(sels[hh], do_o), axis=-1, keepdims=True)
            lse = lse_ref[0, hh, c][:, None]
            s = jnp.dot(q, kts[hh], preferred_element_type=jnp.float32)
            p = jnp.exp(s * scale + b - lse)  # (rows, j) f32
            dp = jnp.dot(do, vts[hh], preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(dtype)
            dq = dq + jnp.dot(ds, ks[hh], preferred_element_type=jnp.float32)
            # the small operand carries the head's lanes, so each head's
            # dk / dv lands in its own lanes of the one accumulator
            dv_scr[...] += jax.lax.dot_general(
                p.astype(dtype), _keep(sels[hh], do32).astype(dtype), _TN,
                preferred_element_type=jnp.float32)
            dk_scr[...] += jax.lax.dot_general(
                ds, _keep(sels[hh], q32).astype(dtype), _TN,
                preferred_element_type=jnp.float32)
        dq_ref[0, pl.ds(r0, rows), :] = (dq * scale).astype(dq_ref.dtype)

    _chunks(n_chunks, chunk)
    dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _rows_specs(ip, jp, g, lanes, n_chunks, rows):
    blk_q = pl.BlockSpec((1, ip, lanes), lambda b, p: (b, 0, p))
    blk_k = pl.BlockSpec((1, jp, lanes), lambda b, p: (b, 0, p))
    blk_b = pl.BlockSpec((1, 1, jp), lambda b, p: (b, 0, 0))
    blk_lse = pl.BlockSpec((1, g, n_chunks, rows), lambda b, p: (b, p, 0, 0))
    return blk_q, blk_k, blk_b, blk_lse


def _rows_forward(q, k, v, bias, scale, g, dh, rows):
    """q: (B, i, h*dh); k, v: (B, j, h*dh); bias: (B, j) additive f32.
    Returns the output cut to i and the padded residuals."""
    B, i0, H = q.shape
    q, k, v, bias, ip, jp = _pad_args(q, k, v, bias, 128, 128)
    bias3 = bias[:, None, :]
    lanes, n_chunks = g * dh, ip // rows
    blk_q, blk_k, blk_b, blk_lse = _rows_specs(ip, jp, g, lanes, n_chunks, rows)
    out, lse = pl.pallas_call(
        functools.partial(_rows_fwd_kernel, scale=scale, g=g, dh=dh,
                          rows=rows, n_chunks=n_chunks),
        out_shape=[
            _out_struct((B, ip, H), q.dtype, q, k, v, bias3),
            _out_struct((B, H // dh, n_chunks, rows), jnp.float32,
                        q, k, v, bias3),
        ],
        grid=(B, H // lanes),
        in_specs=[blk_q, blk_k, blk_k, blk_b],
        out_specs=[blk_q, blk_lse],
        compiler_params=_rows_params(ip, jp, g, lanes, rows,
                                     q.dtype.itemsize),
        interpret=_interpret(),
    )(q, k, v, bias3)
    return out[:, :i0], (q, k, v, bias3, out, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _rows_core(q, k, v, key_bias, scale, g, dh, rows):
    return _rows_forward(q, k, v, key_bias, scale, g, dh, rows)[0]


def _rows_fwd(q, k, v, key_bias, scale, g, dh, rows):
    _, res = _rows_forward(q, k, v, key_bias, scale, g, dh, rows)
    return _rows_named(res + (q.shape[1], k.shape[1]))


def _rows_bwd(scale, g, dh, rows, res, do):
    qp, kp, vp, bias3, out, lse, i0, j0 = res
    B, ip, H = qp.shape
    jp = kp.shape[1]
    lanes, n_chunks = g * dh, ip // rows
    if ip != i0:
        do = jnp.pad(do, ((0, 0), (0, ip - i0), (0, 0)))
    blk_q, blk_k, blk_b, blk_lse = _rows_specs(ip, jp, g, lanes, n_chunks, rows)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_rows_bwd_kernel, scale=scale, g=g, dh=dh,
                          rows=rows, n_chunks=n_chunks),
        out_shape=[
            _out_struct((B, ip, H), qp.dtype, qp, kp, vp, do),
            _out_struct((B, jp, H), kp.dtype, qp, kp, vp, do),
            _out_struct((B, jp, H), vp.dtype, qp, kp, vp, do),
        ],
        grid=(B, H // lanes),
        in_specs=[blk_q, blk_k, blk_k, blk_b, blk_q, blk_q, blk_lse],
        out_specs=[blk_q, blk_k, blk_k],
        scratch_shapes=[
            pltpu.VMEM((jp, lanes), jnp.float32),
            pltpu.VMEM((jp, lanes), jnp.float32),
        ],
        compiler_params=_rows_params(ip, jp, g, lanes, rows,
                                     qp.dtype.itemsize),
        interpret=_interpret(),
    )(qp, kp, vp, bias3, out, do, lse)
    # the bias is a mask, not a parameter: its cotangent is declared zero
    return dq[:, :i0], dk[:, :j0], dv[:, :j0], jnp.zeros((B, j0), jnp.float32)


_rows_core.defvjp(_rows_fwd, _rows_bwd)


def flash_attention_bnhd(q, k, v, key_bias, scale, qb=None, kb=None):
    """Dense flash attention in the model's layout. q: (B, i, h, dh);
    k, v: (B, j, h, dh); key_bias: (B, j) additive f32. Returns
    (B, i, h, dh).

    The kernel chooses its form from (i, j, h, dh): the whole-row form
    where `rows_plan` takes the shape, else heads folded into the batch
    and the streaming form at `pick_block` blocks. qb / kb force the
    streaming form at those blocks (block tuning)."""
    B, i, h, dh = q.shape
    j = k.shape[1]
    plan = None
    if qb is None and kb is None:
        plan = rows_plan(i, j, h, dh, q.dtype.itemsize)
    if plan is not None:
        g, rows = plan
        out = _rows_core(
            q.reshape(B, i, h * dh), k.reshape(B, j, h * dh),
            v.reshape(B, j, h * dh), key_bias, scale, g, dh, rows,
        )
        return out.reshape(B, i, h, dh)

    def fold(t):
        return t.transpose(0, 2, 1, 3).reshape(B * h, t.shape[1], dh)

    out = flash_attention_tpu(
        fold(q), fold(k), fold(v), jnp.repeat(key_bias, h, axis=0), scale,
        qb=qb, kb=kb,
    )
    return out.reshape(B, h, i, dh).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# fused-epilogue kernel: full 2-D pair-bias tiles + sigmoid output gating
# ---------------------------------------------------------------------------
#
# The plain kernel above takes a key-side (BH, j) additive bias — a mask.
# The fused family generalizes the contract two ways (static flags, so
# each combination compiles its own minimal kernel):
#
#   * bias2d — the bias is a full (BH, i, j) f32 tile (pair bias + mask
#     folded together). It streams through the grid's sequential dimension
#     in (qb, kb) blocks exactly like K/V: the bias is never materialized
#     as a separate XLA add over an HBM logit tensor — one of the two HBM
#     round-trips the epilogue fusion removes. The bias cotangent is real
#     (pair biases are projections of learned state, not masks): the dq
#     kernel emits the per-tile ds as a d_bias output.
#   * gated — a (BH, i, dh) pre-sigmoid gate streams with the query block
#     and the finish step writes sigmoid(gate) * out directly, removing
#     the separate out-read/gate-multiply/out-write HBM pass. The gate
#     cotangent needs no kernel: d_gate = g * out_gated * (1 - sigmoid)
#     and the q/k/v backward sees g_eff = g * sigmoid(gate) — all
#     elementwise on tensors already in HBM (see _fused_bwd).
#
# The key-side-only contract stays the plain kernel's fast path; the
# (bias2d=False, gated=False) combination is the plain kernel and callers
# (ops/flash.py) dispatch it there.


def _make_fused_fwd_kernel(nkb, scale, bias2d, gated):
    def kernel(q_ref, k_ref, v_ref, bias_ref, *rest):
        if gated:
            gate_ref, out_ref, lse_ref, m_scr, l_scr, acc_scr = rest
        else:
            out_ref, lse_ref, m_scr, l_scr, acc_scr = rest
        qi = pl.program_id(1)
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            m_scr[...] = jnp.full(m_scr.shape, _M0, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if bias2d:
            s = s + bias_ref[0]             # (qb, kb) streamed tile
        else:
            s = s + bias_ref[0, ki][None, :]  # (kb,) resident row vector

        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

        @pl.when(ki == nkb - 1)
        def _finish():
            l = l_scr[...]
            safe = jnp.where(l > 0, l, 1.0)
            out = jnp.where(l > 0, acc_scr[...] / safe, 0.0)
            if gated:
                # sigmoid in f32 on the f32 accumulator: ONE cast at the
                # very end, matching the XLA epilogue's f32 math
                out = out * jax.nn.sigmoid(gate_ref[0].astype(jnp.float32))
            out_ref[0] = out.astype(out_ref.dtype)
            lse = jnp.where(l > 0, m_scr[...] + jnp.log(safe), jnp.inf)
            lse_ref[0, qi] = lse[:, 0]

    return kernel


def _pad_fused_args(q, k, v, bias, gate, qb, kb, bias2d, gated):
    """Pad to block multiples: -inf bias on padded keys AND padded query
    rows (2-D mode — padded rows become zero-mass, out 0 / lse +inf),
    zero gate rows (sigmoid of anything times a zero row is zero)."""
    BH, i, dh = q.shape
    j = k.shape[1]
    pad_i = (-i) % qb
    pad_j = (-j) % kb
    if pad_i:
        q = jnp.pad(q, ((0, 0), (0, pad_i), (0, 0)))
        if gated:
            gate = jnp.pad(gate, ((0, 0), (0, pad_i), (0, 0)))
    if pad_j:
        k = jnp.pad(k, ((0, 0), (0, pad_j), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_j), (0, 0)))
    if bias2d:
        if pad_i or pad_j:
            bias = jnp.pad(bias, ((0, 0), (0, pad_i), (0, pad_j)),
                           constant_values=_NEG)
    elif pad_j:
        bias = jnp.pad(bias, ((0, 0), (0, pad_j)), constant_values=_NEG)
    return q, k, v, bias, gate, i + pad_i, j + pad_j


def _forward_fused(q, k, v, bias, gate, scale, qb, kb, bias2d, gated):
    """q: (BH, i, dh); k, v: (BH, j, dh); bias: (BH, i, j) f32 when bias2d
    else (BH, j) f32; gate: (BH, i, dh) pre-sigmoid logits (gated only)."""
    BH, i0, dh = q.shape
    j0 = k.shape[1]
    q, k, v, bias, gate, i, j = _pad_fused_args(
        q, k, v, bias, gate, qb, kb, bias2d, gated
    )
    nqb, nkb = i // qb, j // kb
    biask = bias if bias2d else bias.reshape(BH, nkb, kb)

    in_specs = [
        pl.BlockSpec((1, qb, dh), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, kb, dh), lambda b, qi, ki: (b, ki, 0)),
        pl.BlockSpec((1, kb, dh), lambda b, qi, ki: (b, ki, 0)),
        pl.BlockSpec((1, qb, kb), lambda b, qi, ki: (b, qi, ki))
        if bias2d
        else pl.BlockSpec((1, nkb, kb), lambda b, qi, ki: (b, 0, 0)),
    ]
    operands = [q, k, v, biask]
    if gated:
        in_specs.append(pl.BlockSpec((1, qb, dh), lambda b, qi, ki: (b, qi, 0)))
        operands.append(gate)

    out, lse = pl.pallas_call(
        _make_fused_fwd_kernel(nkb, scale, bias2d, gated),
        out_shape=[
            _out_struct((BH, i, dh), q.dtype, *operands),
            _out_struct((BH, nqb, qb), jnp.float32, *operands),
        ],
        grid=(BH, nqb, nkb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, qb, dh), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, nqb, qb), lambda b, qi, ki: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, dh), jnp.float32),
        ],
        compiler_params=_FWD_PARAMS,
        interpret=_interpret(),
    )(*operands)
    return out[:, :i0], (q, k, v, biask, gate, lse, i0, j0)


def _make_fused_dq_kernel(nkb, scale, bias2d):
    def kernel(q_ref, k_ref, v_ref, bias_ref, g_ref, lse_ref, delta_ref,
               *rest):
        if bias2d:
            dq_ref, db_ref, dq_scr = rest
        else:
            dq_ref, dq_scr = rest
        qi = pl.program_id(1)
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

        q = q_ref[0]
        g = g_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        lse = lse_ref[0, qi][:, None]
        delta = delta_ref[0, qi][:, None]

        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = s + (bias_ref[0] if bias2d else bias_ref[0, ki][None, :])
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            g, v, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds_f32 = p * (dp - delta)
        if bias2d:
            # d s / d bias = 1: the unscaled ds tile IS the bias cotangent
            db_ref[0] = ds_f32
        ds = ds_f32.astype(k.dtype)
        dq_scr[...] = dq_scr[...] + jnp.dot(
            ds, k, preferred_element_type=jnp.float32
        )

        @pl.when(ki == nkb - 1)
        def _finish():
            dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)

    return kernel


def _make_fused_dkv_kernel(nqb, scale, bias2d):
    def kernel(q_ref, k_ref, v_ref, bias_ref, g_ref, lse_ref, delta_ref,
               dk_ref, dv_ref, dk_scr, dv_scr):
        ki = pl.program_id(1)
        qi = pl.program_id(2)

        @pl.when(qi == 0)
        def _init():
            dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
            dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

        k = k_ref[0]
        v = v_ref[0]
        q = q_ref[0]
        g = g_ref[0]
        lse = lse_ref[0, qi][:, None]
        delta = delta_ref[0, qi][:, None]

        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = s + (bias_ref[0] if bias2d else bias_ref[0, ki][None, :])
        p = jnp.exp(s - lse)
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p.astype(g.dtype), g, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            g, v, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds, q, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(qi == nqb - 1)
        def _finish():
            dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    return kernel


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _fused_core(q, k, v, bias, gate, scale, qb, kb, bias2d, gated):
    out, _ = _forward_fused(q, k, v, bias, gate, scale, qb, kb, bias2d, gated)
    return out


def _fused_fwd(q, k, v, bias, gate, scale, qb, kb, bias2d, gated):
    out, res = _forward_fused(q, k, v, bias, gate, scale, qb, kb, bias2d, gated)
    qp, kp, vp, biask, gatep, lse, i0, j0 = res
    return out, (qp, kp, vp, biask, gatep, lse, out, i0, j0)


def _fused_bwd(scale, qb, kb, bias2d, gated, res, g):
    qp, kp, vp, biask, gatep, lse, out, i0, j0 = res
    BH, i, dh = qp.shape
    j = kp.shape[1]
    nqb, nkb = i // qb, j // kb

    pad_i = i - i0
    if pad_i:
        g = jnp.pad(g, ((0, 0), (0, pad_i), (0, 0)))
        out = jnp.pad(out, ((0, 0), (0, pad_i), (0, 0)))

    g32 = g.astype(jnp.float32)
    out32 = out.astype(jnp.float32)
    # delta = rowsum(dO_eff * O_pre). With gating, dO_eff = g * sig and
    # O_pre = O_gated / sig, so the product collapses to g * O_gated —
    # delta computes from the SAVED gated output with the RAW cotangent
    delta = jnp.sum(g32 * out32, axis=-1).reshape(BH, nqb, qb)
    d_gate = None
    if gated:
        sig = jax.nn.sigmoid(gatep.astype(jnp.float32))
        # d gate = g * O_pre * sig' = g * O_gated * (1 - sig); elementwise
        # on tensors already in HBM, so no backward kernel change
        d_gate = (g32 * out32 * (1.0 - sig)).astype(gatep.dtype)[:, :i0]
        g = (g32 * sig).astype(g.dtype)

    blk_q = pl.BlockSpec((1, qb, dh), lambda b, x, y: (b, x, 0))
    blk_q_inner = pl.BlockSpec((1, qb, dh), lambda b, x, y: (b, y, 0))
    blk_k = pl.BlockSpec((1, kb, dh), lambda b, x, y: (b, x, 0))
    blk_k_inner = pl.BlockSpec((1, kb, dh), lambda b, x, y: (b, y, 0))
    rows_q = pl.BlockSpec((1, nqb, qb), lambda b, x, y: (b, 0, 0))
    rows_k = pl.BlockSpec((1, nkb, kb), lambda b, x, y: (b, 0, 0))
    bias_dq = (
        pl.BlockSpec((1, qb, kb), lambda b, x, y: (b, x, y))
        if bias2d else rows_k
    )
    bias_dkv = (
        pl.BlockSpec((1, qb, kb), lambda b, x, y: (b, y, x))
        if bias2d else rows_k
    )

    dq_outs = [_out_struct((BH, i, dh), qp.dtype, qp, kp, vp, g)]
    dq_specs = [blk_q]
    scratch = [pltpu.VMEM((qb, dh), jnp.float32)]
    if bias2d:
        dq_outs.append(_out_struct((BH, i, j), jnp.float32, qp, kp, vp, g))
        dq_specs.append(pl.BlockSpec((1, qb, kb), lambda b, x, y: (b, x, y)))
    dq_res = pl.pallas_call(
        _make_fused_dq_kernel(nkb, scale, bias2d),
        out_shape=dq_outs,
        grid=(BH, nqb, nkb),
        in_specs=[blk_q, blk_k_inner, blk_k_inner, bias_dq, blk_q,
                  rows_q, rows_q],
        out_specs=dq_specs,
        scratch_shapes=scratch,
        compiler_params=_BWD_PARAMS,
        interpret=_interpret(),
    )(qp, kp, vp, biask, g, lse, delta)
    if bias2d:
        dq, db = dq_res
        d_bias = db[:, :i0, :j0]
    else:
        dq = dq_res[0] if isinstance(dq_res, (list, tuple)) else dq_res
        # key-side bias is a mask, not a parameter: cotangent declared zero
        d_bias = jnp.zeros((BH, j0), jnp.float32)

    dk, dv = pl.pallas_call(
        _make_fused_dkv_kernel(nqb, scale, bias2d),
        out_shape=[
            _out_struct((BH, j, dh), kp.dtype, qp, kp, vp, g),
            _out_struct((BH, j, dh), vp.dtype, qp, kp, vp, g),
        ],
        grid=(BH, nkb, nqb),
        in_specs=[blk_q_inner, blk_k, blk_k, bias_dkv, blk_q_inner,
                  rows_q, rows_q],
        out_specs=[blk_k, blk_k],
        scratch_shapes=[
            pltpu.VMEM((kb, dh), jnp.float32),
            pltpu.VMEM((kb, dh), jnp.float32),
        ],
        compiler_params=_BWD_PARAMS,
        interpret=_interpret(),
    )(qp, kp, vp, biask, g, lse, delta)

    if d_gate is None:
        d_gate = jnp.zeros(
            (BH, 1, dh), gatep.dtype if hasattr(gatep, "dtype") else jnp.float32
        )
    return (dq[:, :i0], dk[:, :j0], dv[:, :j0], d_bias, d_gate)


_fused_core.defvjp(_fused_fwd, _fused_bwd)


def flash_attention_fused(q, k, v, bias, scale, *, gate=None, qb=None,
                          kb=None):
    """Fused-epilogue dense flash attention.

    q: (BH, i, dh); k, v: (BH, j, dh). bias: additive f32, either the
    plain key-side (BH, j) contract or a full 2-D (BH, i, j) pair-bias
    tile (masks folded in as -inf) — the 2-D tiles stream through the
    kernel in (qb, kb) blocks, so the bias-add never costs a separate
    HBM logit pass. gate: optional (BH, i, dh) pre-sigmoid output-gate
    logits applied INSIDE the kernel's finish step
    (out = sigmoid(gate) * softmax(s) V). Returns (BH, i, dh).

    Differentiable in q/k/v, the 2-D bias (real cotangent — pair biases
    are learned projections), and the gate; the key-side bias cotangent
    stays declared-zero (masks are data). Shape support:
    `supported_fused`."""
    dh = q.shape[-1]
    bias2d = bias.ndim == 3
    gated = gate is not None
    # the 2-D bias adds a streamed (qb, kb) f32 tile plus the backward's
    # d_bias tile to each grid step's VMEM footprint: cap the block target
    # so the double-buffered working set keeps headroom
    target = min(256, _block_target(dh)) if bias2d else _block_target(dh)
    qb = pick_block(q.shape[1], target=target) if qb is None else qb
    kb = pick_block(k.shape[1], target=target) if kb is None else kb
    if not gated:
        gate = jnp.zeros((q.shape[0], 1, dh), q.dtype)
    return _fused_core(q, k, v, bias, gate, scale, qb, kb, bias2d, gated)


# ---------------------------------------------------------------------------
# causal form: self-attention under the lower-triangular mask, with a value
# head size of its own, in the model's layout
# ---------------------------------------------------------------------------
#
# The decoder's latent attention (models/decoder.py) attends i = j tokens
# under the causal mask with q / k heads of 192 and v heads of 128. One
# forward and ONE backward kernel, both on a grid (B, head groups, tiles):
#
#   * the last grid axis walks only the (query block, key block) pairs on
#     or below the diagonal, `causal_schedule`'s tables handed to the
#     BlockSpecs by scalar prefetch: nb (nb + 1) / 2 steps a row, none
#     empty. The forward walks query blocks outermost (its statistics and
#     its accumulator belong to a query block), the backward key blocks
#     outermost (dk, dv belong to a key block) with dq for the WHOLE row
#     resident in VMEM scratch, written once a row;
#   * a step works a (qb, qb) tile in sub-tiles of kb keys (the backward:
#     kb queries) in an unrolled loop, so that one sub-tile's dots can
#     issue under another's softmax; on a diagonal step a sub-tile takes
#     only the queries (keys) that see it, and only there is the iota mask
#     built;
#   * a sub-tile's logits are held TRANSPOSED, keys down the sublanes and
#     queries across the lanes: a query's statistics (running max, sum,
#     lse, delta) are then (1, qb) lane vectors that broadcast down a tile
#     for nothing, where a (qb, 1) column costs a vector register for
#     every eight queries in every pass. The forward's accumulator is
#     (dv, qb) and is transposed once a query block;
#   * q, k: (B, n, h * dh) and v, out: (B, n, h * dv), as the projections
#     hand them over, `g` heads a grid step (`causal_plan`: whole 128-lane
#     blocks). A head's lanes are never sliced off the tiling: its dots
#     run over the 128-aligned window that holds them, with the OTHER
#     head's lanes zeroed in one operand (q once a query block in the
#     forward, k once a key block in the backward). For dh = 192 the
#     window is 256 lanes, which is what the 128-wide array makes of a
#     contraction of 192 anyway;
#   * the backward builds s, p, dp, ds once a tile for all three
#     gradients: five dots and one exp;
#   * with a static `window` (a query sees the `window` keys that end with
#     its own: sliding-window layers) the walk is the BAND: the tiles a
#     query block reaches, `causal_schedule`'s `window_blocks` under the
#     diagonal and no further (8192 positions, blocks and window of 1024:
#     15 tiles a row where the triangle has 36). A tile that the band's
#     lower edge crosses takes a second iota mask, built only there as the
#     diagonal's is built only on the diagonal, and a sub-tile there takes
#     only the queries (keys) that still reach it. `window=None` is the
#     triangle: the same tables and the same kernels as before there was a
#     window.
#
# Numerics are the streaming form's: operands in the input dtype, f32
# logits from the dot's accumulator, finite max sentinel, p and ds cast to
# the operand dtype for their dots. There is no key bias (the mask is the
# causal one, or the causal band of a window) and every row sees its own
# key, so no row is without mass and lse stays finite. What each part
# bought on the chip: PERF.md section 5, the micro-measurement of PR 28.

# what a causal kernel's grid step may plan in VMEM (v5e: 128 MiB); a row
# whose resident dq needs more takes the XLA arm
_CAUSAL_VMEM_CAP = 96 * 1024 * 1024
# the block and the sub-tile the plan starts from (PERF.md section 5: the
# micro-measurement that chose them)
_CAUSAL_QB = 1024
_CAUSAL_KB = 256


class CausalPlan(NamedTuple):
    g: int        # heads a grid step
    qb: int       # query block = the grid's key block
    kb: int       # sub-tile inside a step
    tiles: int    # grid steps a (batch, head group) row
    vmem: int     # planned bytes of the backward step, the larger kernel
    window: int | None = None  # keys a query sees, its own the last; None: all before it


def _lane_group(dh: int, dv: int) -> int:
    """The fewest heads whose q / k and v lanes are whole 128-lane blocks."""
    g = 1
    while (g * dh) % 128 or (g * dv) % 128:
        g += 1
    return g


def _causal_group(h: int, dh: int, dv: int) -> int:
    """Heads a grid step: `_lane_group`'s where it divides h, else all of
    them (a block as wide as the array)."""
    g = _lane_group(dh, dv)
    return h if h % g else g


def _causal_vmem_bytes(n, g, dh, dv, qb, kb, itemsize):
    """The backward step: dq for the row (f32 scratch + its output block,
    double-buffered), the q, k, v, dO, dk, dv blocks double-buffered, the
    dk / dv accumulators and the masked k, and six live (kb, qb) f32
    tiles."""
    lanes = g * (dh + dv)
    resident = n * g * dh * (4 + 2 * itemsize)
    blocks = 2 * 3 * qb * lanes * itemsize
    scratch = qb * lanes * 4 + 2 * qb * _round_up(g * dh, 128) * (4 + itemsize)
    return resident + blocks + scratch + 6 * kb * qb * 4


def causal_plan(n: int, h: int, dh: int, dv: int, itemsize: int = 2,
                qb: int | None = None, kb: int | None = None,
                window: int | None = None):
    """The causal form's plan for self-attention over n positions with h
    heads of dh (q, k) and dv (v), or None where the row's resident dq
    passes _CAUSAL_VMEM_CAP (the call then takes the XLA arm). qb / kb
    force the blocks (tests, block tuning); kb must divide qb.

    `window` (a query sees the `window` keys that end with its own) changes
    the TILES a row walks (`causal_schedule`: the band's, not the
    triangle's) and nothing else of the plan: the blocks, the head group
    and the backward's resident dq, so the length the kernel takes
    (`supported_causal`) is the same with and without one. A window of n
    or more is the plain triangle and comes back as None."""
    if qb is None:
        qb = pick_block(n, target=_CAUSAL_QB)
    if kb is None:
        kb = _CAUSAL_KB
        while qb % kb:
            kb //= 2
    if qb % kb:
        raise ValueError(f"causal kernel: the sub-tile {kb} must divide "
                         f"the block {qb}")
    g = _causal_group(h, dh, dv)
    nb = _round_up(n, qb) // qb
    vmem = _causal_vmem_bytes(nb * qb, g, dh, dv, qb, kb, itemsize)
    if vmem > _CAUSAL_VMEM_CAP:
        return None
    if window is not None and window >= n:
        window = None
    tiles = causal_schedule(nb, window_blocks=window_blocks(window, qb)).shape[1]
    return CausalPlan(g, qb, kb, tiles, vmem, window)


def supported_causal(i: int, j: int, dh: int, dv: int) -> bool:
    """Shapes the causal form takes: self-attention (i = j), both head
    sizes sublane-aligned, and a row whose resident dq fits the plan (at
    the head group the head sizes ask for, float32 operands). A window
    does not enter: `causal_plan` says why."""
    return (i == j and dh % 8 == 0 and dh <= 512 and dv % 8 == 0
            and dv <= 512
            and causal_plan(i, _lane_group(dh, dv), dh, dv, 4) is not None)


def window_blocks(window: int | None, qb: int) -> int | None:
    """How many blocks under the diagonal a query block of qb still
    reaches with `window`: the tile d blocks under it holds a pair in the
    band where its nearest pair does, d qb - (qb - 1) < window."""
    return None if window is None else (window + qb - 2) // qb


def masked_distances(window: int | None, qb: int) -> tuple:
    """The distances d under the diagonal whose tile needs a mask: the
    diagonal's own, and with a window those the band's lower edge crosses
    (the tile's farthest pair, d qb + qb - 1 apart, lies outside)."""
    if window is None:
        return (0,)
    back = window_blocks(window, qb)
    return (0,) + tuple(d for d in range(1, back + 1) if (d + 1) * qb - 1 >= window)


def causal_schedule(nb: int, key_major: bool = False,
                    window_blocks: int | None = None):
    """The walk over nb x nb blocks as a (4, tiles) int32 table: rows
    `query block`, `key block`, `first`, `last`. Every pair with key <=
    query appears once: nb (nb + 1) / 2 tiles; with `window_blocks` only
    the pairs at most that many blocks under the diagonal (the band).
    Query-major (the forward): a query block's keys ascend, `first` /
    `last` flag its first and last tile. Key-major (the backward): a key
    block's queries ascend from the diagonal, the flags are the key
    block's."""
    import numpy as np

    back = nb if window_blocks is None else window_blocks
    if key_major:
        pairs = [(qi, ki) for ki in range(nb)
                 for qi in range(ki, min(nb - 1, ki + back) + 1)]
        flags = [(qi == ki, qi == min(nb - 1, ki + back)) for qi, ki in pairs]
    else:
        pairs = [(qi, ki) for qi in range(nb)
                 for ki in range(max(0, qi - back), qi + 1)]
        flags = [(ki == max(0, qi - back), ki == qi) for qi, ki in pairs]
    return np.array([[q for q, _ in pairs], [k for _, k in pairs],
                     [f for f, _ in flags], [l for _, l in flags]], np.int32)


def _window_bounds(g, d):
    """Per head of the group, the 128-aligned lane window [w0, w1) of a
    (.., g * d) block that holds the head's d lanes. The windows of one
    group may differ in width."""
    return [(hh * d // 128 * 128, min(g * d, _round_up((hh + 1) * d, 128)))
            for hh in range(g)]


def _head_windows(g, d):
    """(w0, w1, sel) per head, inside a kernel: its window and the
    (1, w1 - w0) mask of ITS lanes there (None where the window is the
    head's own)."""
    wins = []
    for hh, (w0, w1) in enumerate(_window_bounds(g, d)):
        lo, hi = hh * d, (hh + 1) * d
        sel = None
        if (w0, w1) != (lo, hi):
            lane = w0 + jax.lax.broadcasted_iota(jnp.int32, (1, w1 - w0), 1)
            sel = (lane >= lo) & (lane < hi)
        wins.append((w0, w1, sel))
    return wins


def _own_lanes(sel, x):
    """x with the other heads' lanes of its window zeroed."""
    return x if sel is None else _keep(sel, x.astype(jnp.float32)).astype(x.dtype)


def _causal_mask_t(st, k0, q0, window=None, diagonal=True):
    """Transposed logits (keys down, queries across) under the mask: the
    causal one on a `diagonal` tile, the band's lower edge with a
    `window`. k0, q0: the first key's and the first query's position,
    from any common origin."""
    keys = k0 + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
    queries = q0 + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
    if window is None:
        return jnp.where(keys <= queries, st, _M0)
    seen = keys > queries - window
    return jnp.where((keys <= queries) & seen if diagonal else seen, st, _M0)


_NT = (((1,), (1,)), ((), ()))  # (m, d) x (n, d) -> (m, n)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _by_distance(sched_ref, t, tile, window, qb):
    """tile(d) where step t's tile lies d blocks under the diagonal, for
    each d of `masked_distances` (static: the tiles that take a mask, the
    diagonal's among them), and tile(None) on every other step, where the
    walk has any (the triangle always; a band only if it reaches past
    its masked tiles)."""
    qi, ki = sched_ref[0, t], sched_ref[1, t]
    masked = masked_distances(window, qb)
    hits = [qi == ki + d if d else qi == ki for d in masked]
    for d, hit in zip(masked, hits):
        pl.when(hit)(functools.partial(tile, d))
    if window is None or window_blocks(window, qb) >= len(masked):
        pl.when(jnp.logical_not(functools.reduce(jnp.logical_or, hits)))(
            lambda: tile(None))


def _seen_span(d, c, qb, kb, window, keys_of_queries):
    """[lo, hi) of a tile's other side that sub-tile c works with, in whole
    sub-tiles: the forward's queries that see key sub-tile c, or (with
    `keys_of_queries`) the backward's keys that query sub-tile c sees. d:
    the tile's distance under the diagonal, None where it takes no mask.
    On the diagonal a key sub-tile is seen from its own queries on; where
    the band's lower edge crosses, up to the last query that reaches it."""
    lo, hi = 0, qb
    if keys_of_queries:
        if d == 0:
            hi = (c + 1) * kb
        if d is not None and window is not None:
            lo = max(0, (c * kb + d * qb - window + 1) // kb * kb)
    else:
        if d == 0:
            lo = c * kb
        if d is not None and window is not None:
            hi = min(qb, _round_up(max(0, (c + 1) * kb - 1 + window - d * qb), kb))
    return lo, hi


def _causal_fwd_kernel(sched_ref, q_ref, k_ref, v_ref, out_ref, lse_ref,
                       qm_scr, m_scr, l_scr, acc_scr, *, scale, g, dh, dv, kb,
                       window=None):
    t = pl.program_id(2)
    qi = sched_ref[0, t]
    qb = q_ref.shape[1]
    wins = _head_windows(g, dh)

    @pl.when(sched_ref[2, t] == 1)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _M0, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
        for hh, (w0, w1, sel) in enumerate(wins):
            qm_scr[hh, :, :w1 - w0] = _own_lanes(sel, q_ref[0, :, w0:w1])

    def tile(d):
        for hh, (w0, w1, _) in enumerate(wins):
            for c in range(qb // kb):
                # the queries that see key sub-tile c (`_seen_span`)
                lo, hi = _seen_span(d, c, qb, kb, window, False)
                if lo >= hi:
                    continue
                keys = slice(c * kb, (c + 1) * kb)
                st = _dot(k_ref[0, keys, w0:w1], qm_scr[hh, lo:hi, :w1 - w0],
                          _NT) * scale
                if d is not None:
                    st = _causal_mask_t(st, 0, d * qb + lo - c * kb, window,
                                        d == 0)
                v = v_ref[0, keys, hh * dv:(hh + 1) * dv]
                m = m_scr[hh, :, lo:hi]                     # (1, hi - lo)
                m_new = jnp.maximum(m, jnp.max(st, axis=0, keepdims=True))
                alpha = jnp.exp(m - m_new)
                pt = jnp.exp(st - m_new)
                l_scr[hh, :, lo:hi] = l_scr[hh, :, lo:hi] * alpha + jnp.sum(
                    pt, axis=0, keepdims=True)
                acc_scr[hh, :, lo:hi] = acc_scr[hh, :, lo:hi] * alpha + _dot(
                    v, pt.astype(v.dtype), _TN)             # (dv, hi - lo)
                m_scr[hh, :, lo:hi] = m_new

    _by_distance(sched_ref, t, tile, window, qb)

    @pl.when(sched_ref[3, t] == 1)
    def _finish():
        for hh in range(g):
            l = l_scr[hh]
            out_ref[0, :, hh * dv:(hh + 1) * dv] = (acc_scr[hh] / l).T.astype(
                out_ref.dtype)
            lse_ref[0, hh, qi] = (m_scr[hh] + jnp.log(l))[0]


def _causal_bwd_kernel(sched_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, dq_ref, dk_ref, dv_ref,
                       km_scr, dq_scr, dk_scr, dv_scr, *,
                       scale, g, dh, dv, kb, tiles, window=None):
    t = pl.program_id(2)
    qi = sched_ref[0, t]
    qb = k_ref.shape[1]
    wins = _head_windows(g, dh)

    @pl.when(t == 0)
    def _row():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    @pl.when(sched_ref[2, t] == 1)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)
        for hh, (w0, w1, sel) in enumerate(wins):
            km_scr[hh, :, :w1 - w0] = _own_lanes(sel, k_ref[0, :, w0:w1])

    def tile(d):
        r0 = pl.multiple_of(qi * qb, qb)
        for hh, (w0, w1, _) in enumerate(wins):
            lanes = slice(hh * dv, (hh + 1) * dv)
            for c in range(qb // kb):
                # the keys that query chunk c sees (`_seen_span`)
                lo, hi = _seen_span(d, c, qb, kb, window, True)
                if lo >= hi:
                    continue
                rows = slice(c * kb, (c + 1) * kb)
                q = q_ref[0, rows, w0:w1]
                do = do_ref[0, rows, lanes]
                k = km_scr[hh, lo:hi, :w1 - w0]
                st = _dot(k, q, _NT) * scale                # (hi - lo, kb)
                if d is not None:
                    st = _causal_mask_t(st, lo, d * qb + c * kb, window, d == 0)
                pt = jnp.exp(st - lse_ref[0, hh, qi, rows][None, :])
                dpt = _dot(v_ref[0, lo:hi, lanes], do, _NT)
                dst = (pt * (dpt - delta_ref[0, hh, qi, rows][None, :])).astype(
                    q.dtype)
                dv_scr[lo:hi, lanes] += _dot(pt.astype(do.dtype), do)
                dk_scr[hh, lo:hi, :w1 - w0] += _dot(dst, q)
                dq_scr[pl.ds(r0 + c * kb, kb), w0:w1] += _dot(dst, k, _TN)

    _by_distance(sched_ref, t, tile, window, qb)

    @pl.when(sched_ref[3, t] == 1)
    def _finish():
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)
        # q's windows carried the other heads' lanes into dk: each head
        # adds its own to a zeroed block (every lane gets one addend, so
        # the round trip through the block's dtype is exact)
        shared = any(sel is not None for _, _, sel in wins)
        if shared:
            dk_ref[0] = jnp.zeros(dk_ref.shape[1:], dk_ref.dtype)
        for hh, (w0, w1, sel) in enumerate(wins):
            own = dk_scr[hh, :, :w1 - w0] * scale
            if shared:
                own = dk_ref[0, :, w0:w1].astype(jnp.float32) + (
                    own if sel is None else jnp.where(sel, own, 0.0))
            dk_ref[0, :, w0:w1] = own.astype(dk_ref.dtype)

    @pl.when(t == tiles - 1)
    def _row_done():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _causal_params(plan):
    return compat.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        # from the shape, as `_rows_params`: twice the plan (Mosaic's own
        # temporaries), never under 32 MiB nor over what a v5e core has
        vmem_limit_bytes=min(max(32 << 20, 2 * plan.vmem), 120 << 20),
    )


def _causal_specs(plan, n, dh, dv):
    """Blocks of the (B, n, h * d) operands at a step's query block (row 0
    of the schedule) and key block (row 1), the per-row vectors, and the
    widest head window."""
    g, qb = plan.g, plan.qb
    ww = max(w1 - w0 for w0, w1 in _window_bounds(g, dh))
    at_q = lambda b, p, t, sched: (b, sched[0, t], p)  # noqa: E731
    at_k = lambda b, p, t, sched: (b, sched[1, t], p)  # noqa: E731
    rows = pl.BlockSpec((1, g, n // qb, qb), lambda b, p, t, sched: (b, p, 0, 0))
    return (pl.BlockSpec((1, qb, g * dh), at_q), pl.BlockSpec((1, qb, g * dv), at_q),
            pl.BlockSpec((1, qb, g * dh), at_k), pl.BlockSpec((1, qb, g * dv), at_k),
            rows, ww)


def _causal_forward(q, k, v, scale, dh, plan):
    """q, k: (B, n, h * dh); v: (B, n, h * dv), n a multiple of plan.qb.
    Returns out (B, n, h * dv) and lse (B, h, n / qb, qb)."""
    B, n, H = q.shape
    h = H // dh
    dv = v.shape[-1] // h
    g, qb, kb = plan.g, plan.qb, plan.kb
    q_at_q, v_at_q, k_at_k, v_at_k, rows, ww = _causal_specs(plan, n, dh, dv)
    return pl.pallas_call(
        functools.partial(_causal_fwd_kernel, scale=scale, g=g, dh=dh, dv=dv,
                          kb=kb, window=plan.window),
        out_shape=[
            _out_struct((B, n, h * dv), q.dtype, q, k, v),
            _out_struct((B, h, n // qb, qb), jnp.float32, q, k, v),
        ],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, h // g, plan.tiles),
            in_specs=[q_at_q, k_at_k, v_at_k],
            out_specs=[v_at_q, rows],
            scratch_shapes=[
                pltpu.VMEM((g, qb, ww), q.dtype),
                pltpu.VMEM((g, 1, qb), jnp.float32),
                pltpu.VMEM((g, 1, qb), jnp.float32),
                pltpu.VMEM((g, dv, qb), jnp.float32),
            ],
        ),
        compiler_params=_causal_params(plan),
        interpret=_interpret(),
    )(jnp.asarray(causal_schedule(
        n // qb, window_blocks=window_blocks(plan.window, qb))), q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _causal_core(q, k, v, scale, dh, plan):
    return _causal_forward(q, k, v, scale, dh, plan)[0]


# The names of a forward kernel's two results where they become the
# backward's residuals, in this form and in the whole-row form alike. Under
# a `jax.checkpoint` whose policy saves them (`ops/flash.py`) the backward
# reads the stored values and the recomputation holds no forward call; in
# no policy a name is the identity. Imported here and not at the top so that
# no line above moves: a Mosaic kernel's serialized body carries its call
# sites' line numbers (PERF.md section 6, PR 28; `_rows_named` ends the file).
from jax.ad_checkpoint import checkpoint_name  # noqa: E402

SAVED_NAMES = ("attn_core_out", "attn_core_lse")


def _causal_fwd(q, k, v, scale, dh, plan):
    out, lse = map(checkpoint_name,
                   _causal_forward(q, k, v, scale, dh, plan), SAVED_NAMES)
    return out, (q, k, v, out, lse)


def _causal_bwd(scale, dh, plan, res, do):
    q, k, v, out, lse = res
    # `out` may come from a checkpoint's saved residuals, whose layout no
    # call pins: left free, XLA lays it out for W_o's gradient and copies
    # `do`, which `delta`'s fusion then emits the same way, back for the
    # kernel (two passes of 134 MB a layer at the decoder's widths)
    out = compat.row_major(out)
    B, n, H = q.shape
    h = H // dh
    dv = v.shape[-1] // h
    g, qb, kb = plan.g, plan.qb, plan.kb
    delta = jnp.sum(
        (do.astype(jnp.float32) * out.astype(jnp.float32)).reshape(B, n, h, dv),
        axis=-1).transpose(0, 2, 1).reshape(B, h, n // qb, qb)
    q_at_q, v_at_q, k_at_k, v_at_k, rows, ww = _causal_specs(plan, n, dh, dv)
    whole = pl.BlockSpec((1, n, g * dh), lambda b, p, t, sched: (b, 0, p))
    return tuple(pl.pallas_call(
        functools.partial(_causal_bwd_kernel, scale=scale, g=g, dh=dh, dv=dv,
                          kb=kb, tiles=plan.tiles, window=plan.window),
        out_shape=[
            _out_struct(q.shape, q.dtype, q, k, v, do),
            _out_struct(k.shape, k.dtype, q, k, v, do),
            _out_struct(v.shape, v.dtype, q, k, v, do),
        ],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, h // g, plan.tiles),
            in_specs=[q_at_q, k_at_k, v_at_k, v_at_q, rows, rows],
            out_specs=[whole, k_at_k, v_at_k],
            scratch_shapes=[
                pltpu.VMEM((g, qb, ww), k.dtype),
                pltpu.VMEM((n, g * dh), jnp.float32),
                pltpu.VMEM((g, qb, ww), jnp.float32),
                pltpu.VMEM((qb, g * dv), jnp.float32),
            ],
        ),
        compiler_params=_causal_params(plan),
        interpret=_interpret(),
    )(jnp.asarray(causal_schedule(
        n // qb, key_major=True,
        window_blocks=window_blocks(plan.window, qb))), q, k, v, do, lse, delta))


_causal_core.defvjp(_causal_fwd, _causal_bwd)


def flash_attention_causal_bnhd(q, k, v, scale, qb=None, kb=None, window=None):
    """Causal self-attention in the model's layout. q, k: (B, n, h, dh);
    v: (B, n, h, dv). Returns (B, n, h, dv). The kernels read the operands
    as (B, n, h * d), `causal_plan`'s heads a grid step; n pads to the
    block (a padded key lies past every real query, a padded query row is
    cut away). qb / kb force the block and the sub-tile. `window` (static):
    query i sees keys j with i - window < j <= i, any whole number of keys
    from 1 up; None, or n and more, is the plain triangle."""
    B, n, h, dh = q.shape
    dv = v.shape[-1]
    if window is not None and window < 1:
        raise ValueError(f"causal kernel: a window of {window} keys holds no "
                         "key; every query sees its own")
    plan = causal_plan(n, h, dh, dv, q.dtype.itemsize, qb, kb, window)
    if plan is None:
        raise ValueError(
            f"causal kernel: a row of n={n} at h={h} dh={dh} dv={dv} does "
            "not fit VMEM (flash_kernel.causal_plan); use_kernel=False "
            "streams it through XLA")
    pad = (-n) % plan.qb

    def flat(t):
        t = t.reshape(B, n, h * t.shape[-1])
        return jnp.pad(t, ((0, 0), (0, pad), (0, 0))) if pad else t

    out = _causal_core(flat(q), flat(k), flat(v), scale, dh, plan)
    return out[:, :n].reshape(B, n, h, dv)


def _rows_named(res):
    """(output, residuals) of the whole-row forward rule `_rows_fwd`, the
    kernel's padded `out` and its `lse` under SAVED_NAMES as `_causal_fwd`
    has them. The output is cut from the NAMED `out`, so a checkpoint that
    keeps the two names leaves the forward call nothing live to compute.
    It stands here, after every causal call site, for the reason above."""
    qp, kp, vp, bias3, out, lse, i0, j0 = res
    out, lse = map(checkpoint_name, (out, lse), SAVED_NAMES)
    return out[:, :i0], (qp, kp, vp, bias3, out, lse, i0, j0)
