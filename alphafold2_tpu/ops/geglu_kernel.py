"""Pallas TPU kernels for the GEGLU feed-forward block (forward + backward).

The whole block of ops/feedforward.py — `x @ W_in + b_in` -> split ->
`value * gelu(gate)` -> `@ W_out + b_out` — over a tile of rows at a time,
with the weights resident in VMEM. The (rows, 2 * hidden) projection and
the gated (rows, hidden) product never leave VMEM: the XLA arm writes both
to HBM and reads them back (at the pair stream's 1.3M rows that is most of
the block's traffic), and its backward does so several times over.

Layout: x is (rows, dim) in the compute dtype; W_in (dim, 2 * hidden),
value in lanes [0, hidden) and gate in [hidden, 2 * hidden), as
`jnp.split` cuts the XLA arm's projection; W_out (hidden, dim). The
value/gate pair is walked in blocks of `lanes` lanes, the output
accumulated across them in float32, so that a step's temporaries stay
(tile, lanes) wide. The projection, the gate and the product are float32;
the product is rounded to the compute dtype only as the second matmul's
operand. The GELU is the exact (erf) one: Mosaic has no lowering for
`lax.erf`, so `erf` below is XLA's own float32 rational form, which
equals `lax.erf` on the CPU to the bit.

Backward: ONE kernel over the same row tiles recomputes the projection
from x (nothing but x and the weights is saved), forms dvalue and dgate,
dx, and accumulates dW_in, db_in, dW_out and db_out in float32 in its
resident output blocks across a sequential ("arbitrary") row axis, written
once at the end. The weights' gradients come back in float32, whatever the
compute dtype. Off the TPU the kernels run in interpret mode (the parity
tests), one code path.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from alphafold2_tpu import compat
from alphafold2_tpu.compat import pallas as pl
from alphafold2_tpu.ops.core import pallas_interpret as _interpret

_SQRT_HALF = 0.7071067811865476
_INV_SQRT_2PI = 0.3989422804014327

# XLA's float32 erf: x * P(x^2) / Q(x^2) on x clamped to +-erfinv(1 - 2^-23),
# past which erf rounds to +-1 (xla/service/llvm_ir/math_ops.cc EmitErfF32)
_ERF_CLAMP = 3.7439211627767994
_ERF_P = (0.00022905065861350646, 0.0034082910107109506,
          0.050955695062380861, 0.18520832239976145, 1.128379143519084)
_ERF_Q = (-1.1791602954361697e-7, 0.000023547966471313185,
          0.0010179625278914885, 0.014070470171167667,
          0.11098505178285362, 0.49746925110067538, 1.0)

# rows a grid step and lanes of the value/gate pair a block, chosen on the
# chip (benchmarks/records/micro_geglu_*.jsonl)
_TILE = 1024
_LANES = 512
# what a step may ask of VMEM (v5e has 128 MiB a core)
_VMEM_CAP = 100 << 20

_TN = (((0,), (0,)), ((), ()))  # contract the rows: x^T @ d


def erf(x):
    """float32 erf, as XLA computes `lax.erf` (Horner in x^2)."""
    x = jnp.clip(x, -_ERF_CLAMP, _ERF_CLAMP)
    x2 = x * x
    p = x2 * _ERF_P[0] + _ERF_P[1]
    for c in _ERF_P[2:]:
        p = p * x2 + c
    q = x2 * _ERF_Q[0] + _ERF_Q[1]
    for c in _ERF_Q[2:]:
        q = q * x2 + c
    return x * p / q


def _gelu_parts(z):
    """(gelu(z), Phi(z)): exact GELU and the normal CDF it is made of."""
    cdf = 0.5 + 0.5 * erf(z * _SQRT_HALF)
    return z * cdf, cdf


class Plan(NamedTuple):
    tile: int    # rows a grid step
    lanes: int   # lanes of value (and of gate) a block
    vmem: int    # bytes the backward step asks for, the larger of the two


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_bytes(tile, lanes, dim, hidden, itemsize):
    """The backward step's VMEM (the forward's is smaller): x, dy, dx
    tiles double-buffered; W_in, W_in^T, W_out^T double-buffered; the
    float32 gradient blocks; ~8 float32 (tile, lanes) temporaries and the
    float32 dx accumulator."""
    tiles = 3 * 2 * tile * dim * itemsize
    weights = 2 * (2 * dim * 2 * hidden + dim * hidden) * itemsize
    grads = 2 * 4 * (dim * 2 * hidden + hidden * dim + 2 * hidden + dim)
    temps = 4 * (8 * tile * lanes + tile * dim)
    return tiles + weights + grads + temps


def plan(rows: int, dim: int, hidden: int, itemsize: int):
    """The kernels' plan for a shape and the compute dtype's itemsize, or
    None where they do not go: `dim` and `hidden` on whole lane tiles
    (128), and a step under the VMEM cap. A tile is never longer than the
    rows rounded up to 16."""
    if dim % 128 or hidden % 128:
        return None
    lanes = max(c for c in range(128, min(_LANES, hidden) + 1, 128)
                if hidden % c == 0)
    tile = min(_TILE, _round_up(max(rows, 1), 16))
    vmem = _vmem_bytes(tile, lanes, dim, hidden, itemsize)
    if vmem > _VMEM_CAP:
        return None
    return Plan(tile, lanes, vmem)


def _params(p: Plan, semantics):
    return compat.CompilerParams(
        dimension_semantics=(semantics,),
        vmem_limit_bytes=min(max(32 << 20, 3 * p.vmem // 2), _VMEM_CAP),
    )


def _fwd_kernel(x_ref, wi_ref, bi_ref, wo_ref, bo_ref, o_ref, *, hidden,
                lanes):
    x = x_ref[...]
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for c in range(hidden // lanes):
        v, g = pl.ds(c * lanes, lanes), pl.ds(hidden + c * lanes, lanes)
        hv = jnp.dot(x, wi_ref[:, v], preferred_element_type=jnp.float32)
        hg = jnp.dot(x, wi_ref[:, g], preferred_element_type=jnp.float32)
        gel, _ = _gelu_parts(hg + bi_ref[:, g])
        prod = (hv + bi_ref[:, v]) * gel
        acc = acc + jnp.dot(prod.astype(wo_ref.dtype), wo_ref[v, :],
                            preferred_element_type=jnp.float32)
    o_ref[...] = (acc + bo_ref[...]).astype(o_ref.dtype)


def _bwd_kernel(x_ref, dy_ref, wi_ref, bi_ref, wit_ref, wot_ref,
                dx_ref, dwi_ref, dbi_ref, dwo_ref, dbo_ref, *, hidden, lanes):
    @pl.when(pl.program_id(0) == 0)
    def _zero():
        for ref in (dwi_ref, dbi_ref, dwo_ref, dbo_ref):
            ref[...] = jnp.zeros(ref.shape, ref.dtype)

    x, dy = x_ref[...], dy_ref[...]
    cdt = x.dtype
    dx = jnp.zeros(dx_ref.shape, jnp.float32)
    for c in range(hidden // lanes):
        v, g = pl.ds(c * lanes, lanes), pl.ds(hidden + c * lanes, lanes)
        hv = jnp.dot(x, wi_ref[:, v],
                     preferred_element_type=jnp.float32) + bi_ref[:, v]
        hg = jnp.dot(x, wi_ref[:, g],
                     preferred_element_type=jnp.float32) + bi_ref[:, g]
        gel, cdf = _gelu_parts(hg)
        # gelu'(z) = Phi(z) + z phi(z)
        dgel = cdf + hg * (_INV_SQRT_2PI * jnp.exp(-0.5 * hg * hg))
        dprod = jnp.dot(dy, wot_ref[:, v], preferred_element_type=jnp.float32)
        dval = dprod * gel
        dgate = dprod * hv * dgel
        dwo_ref[v, :] += jax.lax.dot_general(
            (hv * gel).astype(cdt), dy, _TN,
            preferred_element_type=jnp.float32)
        dval_c, dgate_c = dval.astype(cdt), dgate.astype(cdt)
        dwi_ref[:, v] += jax.lax.dot_general(
            x, dval_c, _TN, preferred_element_type=jnp.float32)
        dwi_ref[:, g] += jax.lax.dot_general(
            x, dgate_c, _TN, preferred_element_type=jnp.float32)
        dbi_ref[:, v] += jnp.sum(dval, axis=0, keepdims=True)
        dbi_ref[:, g] += jnp.sum(dgate, axis=0, keepdims=True)
        dx = dx + jnp.dot(dval_c, wit_ref[v, :],
                          preferred_element_type=jnp.float32)
        dx = dx + jnp.dot(dgate_c, wit_ref[g, :],
                          preferred_element_type=jnp.float32)
    dbo_ref[...] += jnp.sum(dy.astype(jnp.float32), axis=0, keepdims=True)
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _pad_rows(t, rows_p):
    pad = rows_p - t.shape[0]
    return jnp.pad(t, ((0, pad), (0, 0))) if pad else t


def _whole(shape):
    """A block that is the whole array at every grid step: fetched once,
    resident (an output one is written back once, at the end)."""
    return pl.BlockSpec(shape, lambda i: (0, 0))


def _forward(x, w_in, b_in, w_out, b_out, p: Plan):
    rows, dim = x.shape
    hidden = w_out.shape[0]
    cdt = x.dtype
    rows_p = _round_up(rows, p.tile)
    xp = _pad_rows(x, rows_p)
    wi, wo = w_in.astype(cdt), w_out.astype(cdt)
    bi = b_in.astype(jnp.float32).reshape(1, -1)
    bo = b_out.astype(jnp.float32).reshape(1, -1)
    row_blk = pl.BlockSpec((p.tile, dim), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, hidden=hidden, lanes=p.lanes),
        out_shape=compat.out_struct((rows_p, dim), cdt, xp, wi, wo),
        grid=(rows_p // p.tile,),
        in_specs=[row_blk, _whole(wi.shape), _whole(bi.shape),
                  _whole(wo.shape), _whole(bo.shape)],
        out_specs=row_blk,
        compiler_params=_params(p, "parallel"),
        interpret=_interpret(),
    )(xp, wi, bi, wo, bo)
    return out[:rows] if rows_p != rows else out


def _backward(x, w_in, b_in, w_out, dy, p: Plan):
    rows, dim = x.shape
    hidden = w_out.shape[0]
    cdt = x.dtype
    rows_p = _round_up(rows, p.tile)
    xp, dyp = _pad_rows(x, rows_p), _pad_rows(dy.astype(cdt), rows_p)
    wi = w_in.astype(cdt)
    wit, wot = wi.T, w_out.astype(cdt).T
    bi = b_in.astype(jnp.float32).reshape(1, -1)
    row_blk = pl.BlockSpec((p.tile, dim), lambda i: (i, 0))
    ops = (xp, dyp, wi, wit, wot)
    dx, dwi, dbi, dwo, dbo = pl.pallas_call(
        functools.partial(_bwd_kernel, hidden=hidden, lanes=p.lanes),
        out_shape=[
            compat.out_struct((rows_p, dim), cdt, *ops),
            compat.out_struct((dim, 2 * hidden), jnp.float32, *ops),
            compat.out_struct((1, 2 * hidden), jnp.float32, *ops),
            compat.out_struct((hidden, dim), jnp.float32, *ops),
            compat.out_struct((1, dim), jnp.float32, *ops),
        ],
        grid=(rows_p // p.tile,),
        in_specs=[row_blk, row_blk, _whole(wi.shape), _whole(bi.shape),
                  _whole(wit.shape), _whole(wot.shape)],
        out_specs=[row_blk, _whole((dim, 2 * hidden)),
                   _whole((1, 2 * hidden)), _whole((hidden, dim)),
                   _whole((1, dim))],
        compiler_params=_params(p, "arbitrary"),
        interpret=_interpret(),
    )(xp, dyp, wi, bi, wit, wot)
    if rows_p != rows:
        dx = dx[:rows]
    return dx, dwi, dbi[0], dwo, dbo[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _geglu(x, w_in, b_in, w_out, b_out, p):
    return _forward(x, w_in, b_in, w_out, b_out, p)


def _geglu_fwd(x, w_in, b_in, w_out, b_out, p):
    # residuals: the input and the weights, nothing the block computes
    return _forward(x, w_in, b_in, w_out, b_out, p), (x, w_in, b_in, w_out,
                                                       b_out)


def _geglu_bwd(p, res, dy):
    x, w_in, b_in, w_out, b_out = res
    dx, dwi, dbi, dwo, dbo = _backward(x, w_in, b_in, w_out, dy, p)
    return (dx, dwi.astype(w_in.dtype), dbi.astype(b_in.dtype),
            dwo.astype(w_out.dtype), dbo.astype(b_out.dtype))


_geglu.defvjp(_geglu_fwd, _geglu_bwd)


def geglu_ff(params, x, dtype=None):
    """The GEGLU block of `feed_forward_init`'s params on x (rows, dim):
    (rows, dim) in the compute dtype (`dtype`, else x's and the weights'
    promoted), as ops/feedforward.py's XLA arm returns it."""
    w_in, b_in = params["proj_in"]["w"], params["proj_in"]["b"]
    w_out, b_out = params["proj_out"]["w"], params["proj_out"]["b"]
    cdt = jnp.dtype(dtype) if dtype is not None else jnp.result_type(
        x.dtype, w_in.dtype)
    x = x.astype(cdt)
    p = plan(x.shape[0], x.shape[1], w_out.shape[0], cdt.itemsize)
    if p is None:
        raise ValueError(
            f"geglu kernel does not support rows={x.shape[0]}, "
            f"dim={x.shape[1]}, hidden={w_out.shape[0]} (see plan)")
    return _geglu(x, w_in, b_in, w_out, b_out, p)
