"""GEGLU feed-forward block (reference alphafold2_pytorch/alphafold2.py:52-73).

Linear(d -> 2*mult*d) -> GEGLU (value * gelu(gate)) -> dropout ->
Linear(mult*d -> d). Uses exact (erf) GELU to match torch.nn.functional.gelu.
The two matmuls dominate; XLA fuses the gating elementwise into them.

Two arms (ops/dispatch.py op `geglu_ff`): on the TPU, from
`_GEGLU_KERNEL_MIN_ROWS` rows up and without dropout or int8 weights, the
whole flattened token axis goes through ONE Pallas kernel pair
(ops/geglu_kernel.py) that keeps the 2*mult*d intermediate in VMEM forward
and backward and saves only x. Everywhere else the XLA arm, `_ff_core`.

`chunk` (XLA arm only): when set, the token axes are flattened and
processed in blocks of that many tokens under `jax.checkpoint`, bounding
the 8*dim GEGLU intermediate — at crop 384 the pair stream has 1.3M
tokens, whose 2048-wide intermediate would otherwise be the largest single
activation in the trunk. Chunked dropout draws an independent key per
block (fold_in of the block index); the unchunked mask pattern is not
reproduced — set chunk=0 for bit-identical dropout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from alphafold2_tpu.ops import dispatch
from alphafold2_tpu.ops.core import dropout, linear, linear_init
from alphafold2_tpu.telemetry.profiling import scope


def feed_forward_init(key, dim: int, mult: int = 4):
    k_in, k_out = jax.random.split(key)
    return {
        "proj_in": linear_init(k_in, dim, dim * mult * 2),
        "proj_out": linear_init(k_out, dim * mult, dim),
    }


def _ff_core(params, x, dropout_rate, rng, dtype):
    with scope("geglu"):
        y = linear(params["proj_in"], x, dtype=dtype)
        value, gate = jnp.split(y, 2, axis=-1)
        y = value * jax.nn.gelu(gate, approximate=False)
        y = dropout(rng, y, dropout_rate)
        return linear(params["proj_out"], y, dtype=dtype)


def feed_forward_apply(
    params, x, *, dropout_rate: float = 0.0, rng=None, dtype=None,
    chunk: int = 0, use_kernel="auto",
):
    d = x.shape[-1]
    tokens = 1
    for s in x.shape[:-1]:
        tokens *= s
    w_in, w_out = params["proj_in"], params["proj_out"]
    quantized = "qw" in w_in or "qw" in w_out
    # the kernel's compute dtype, which sizes its VMEM plan
    cdt = jnp.dtype(dtype) if dtype is not None else (
        x.dtype if quantized else jnp.result_type(x.dtype, w_in["w"].dtype))
    arm = dispatch.resolve(
        "geglu_ff", use_kernel, rows=tokens, dim=d,
        hidden=(w_out["w"] if "w" in w_out else w_out["qw"]).shape[0],
        itemsize=cdt.itemsize, dropout=bool(dropout_rate) and rng is not None,
        quantized=quantized,
    )
    if arm == dispatch.ARM_PALLAS_TPU:
        from alphafold2_tpu.ops.geglu_kernel import geglu_ff

        with scope("geglu"):
            out = geglu_ff(params, x.reshape(tokens, d), cdt)
        return out.reshape(x.shape[:-1] + (out.shape[-1],))
    if not chunk or tokens <= chunk:
        return _ff_core(params, x, dropout_rate, rng, dtype)

    xf = x.reshape(tokens, d)
    pad = (-tokens) % chunk
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    nb = (tokens + pad) // chunk

    def body(args):
        xi, idx = args
        r = jax.random.fold_in(rng, idx) if rng is not None else None
        return _ff_core(params, xi, dropout_rate, r, dtype)

    out = jax.lax.map(
        jax.checkpoint(body), (xf.reshape(nb, chunk, d), jnp.arange(nb))
    )
    out = out.reshape(nb * chunk, -1)[:tokens]
    return out.reshape(x.shape[:-1] + (out.shape[-1],))
