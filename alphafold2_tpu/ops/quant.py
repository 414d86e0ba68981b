"""Post-training int8 weight quantization for the inference arm.

Serving inference (serving/engine.py) runs the same fp32 weights as
training, so every replica pays full HBM for weight residency and full
memory bandwidth on the trunk's dense layers. The efficiency-
implementation line of work (HelixFold, arxiv 2207.05477; FastFold,
arxiv 2203.00854) shows AlphaFold2's trunk tolerates reduced-precision
arms when parity is pinned per-op; this module arms the int8 lever:

  * **Per-channel symmetric PTQ** — `quantize_weight` maps an fp32
    (d_in, d_out) dense weight to (int8 values, f32 per-output-channel
    scale): scale_c = max|w[:, c]| / 127, q = round(w / scale). Symmetric
    (no zero point), so dequant is one multiply; per-channel, so one
    saturated channel cannot flatten the rest of the layer's resolution.
  * **Tree transforms** — `quantize_tree` / `dequantize_tree` walk a
    model parameter pytree by NAMED path and rewrite selected linear
    weights `{"w": ...}` to `{"qw": int8, "scale": f32}` (bias and every
    unselected leaf untouched). The fp32 master tree is never mutated —
    PTQ produces a NEW inference tree; training keeps the master.
    The default selection (`default_quant_select`) is the trunk's dense/
    projection weights: every 2-D (or reversible-trunk depth-stacked
    3-D) "w" under a "trunk" path. Embedding tables (gather, not
    matmul), LayerNorm, the KV-compress conv (a real 3-D conv kernel,
    excluded by name), and the distogram head stay fp32.
  * **Mixed-precision matmul dispatch** — `quant_matmul` runs activations
    (f32/bf16) against int8 weights: the Pallas fused-dequant kernel
    (ops/quant_kernel.py — int8 tiles cross HBM, per-channel scale in
    the kernel epilogue) on TPU for supported shapes, the pure-XLA
    dequant reference arm (`quant_matmul_xla` — materializes the
    dequantized weight, the baseline the kernel exists to beat)
    elsewhere. The arm comes from ops/dispatch.py `resolve` (op
    "quant_matmul"): tri-state use_kernel, loud error on
    forced-unsupported, AF2_KERNEL_BACKEND[_QUANT_MATMUL] override.

Quantized weights are INFERENCE-ONLY: `quant_matmul` installs a
custom-vjp backward that raises, and the training entry points
(training/harness.py, training/e2e.py) reject `weight_dtype="int8"`
configs before any tracing via `reject_quant_training` — a silently
straight-through-estimated training run would be a wrong-numbers
generator, not a feature.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "quantize_weight",
    "dequantize_weight",
    "quantize_tree",
    "dequantize_tree",
    "default_quant_select",
    "is_quantized_linear",
    "quant_matmul",
    "quant_matmul_xla",
    "tree_weight_bytes",
    "quantized_path_bytes",
    "reject_quant_training",
]

_QMAX = 127.0  # symmetric int8 range; -128 is never produced


# ---------------------------------------------------------------------------
# per-channel symmetric PTQ
# ---------------------------------------------------------------------------


def quantize_weight(w, *, per_channel: bool = True):
    """fp32 (..., d_in, d_out) -> (int8 same shape, f32 scale).

    scale is (..., d_out) per output channel (the matmul's N axis, so the
    dequant commutes past the contraction and can apply in the kernel
    epilogue), or (...,) when per_channel=False — a scalar for a plain
    2-D weight. Leading axes are a STACK (the reversible trunk stores
    every layer's weights stacked (depth, d_in, d_out), lax.scan-sliced
    back to 2-D inside the layer body): each stacked slice quantizes
    independently, so scan slicing a quantized tree hands `linear` the
    exact (d_in, d_out)/(d_out,) pair `quant_matmul` takes. All-zero
    channels get scale 0 and values 0 — dequant reproduces exact zeros
    (the near-open gate init `w=0` round-trips bit-exactly)."""
    wf = jnp.asarray(w, jnp.float32)
    if wf.ndim < 2:
        raise ValueError(
            f"quantize_weight expects a (stacked) 2-D dense weight, "
            f"got {wf.shape}"
        )
    amax = (
        jnp.max(jnp.abs(wf), axis=-2) if per_channel
        else jnp.max(jnp.abs(wf), axis=(-2, -1))
    )
    scale = amax / _QMAX
    safe = jnp.where(scale > 0, scale, 1.0)
    safe = safe[..., None, :] if per_channel else safe[..., None, None]
    q = jnp.clip(jnp.round(wf / safe), -_QMAX, _QMAX).astype(jnp.int8)
    return q, scale


def dequantize_weight(qw, scale):
    """(int8, scale) -> f32 weight. Exact inverse of the rounding grid:
    |w_deq - w| <= scale/2 per element. Accepts per-channel scales
    (qw.ndim - 1 dims) and per-tensor scales (qw.ndim - 2 dims),
    stacked or plain."""
    s = jnp.asarray(scale, jnp.float32)
    if s.ndim == qw.ndim - 1:        # per output channel
        s = s[..., None, :]
    elif s.ndim == qw.ndim - 2:      # per tensor (per stacked slice)
        s = s[..., None, None]
    else:
        raise ValueError(
            f"scale shape {s.shape} does not match weight shape {qw.shape}"
        )
    return qw.astype(jnp.float32) * s


def is_quantized_linear(d) -> bool:
    """True for a linear-param dict rewritten by `quantize_tree`."""
    return isinstance(d, dict) and "qw" in d and "scale" in d


def default_quant_select(path: str, w) -> bool:
    """The trunk's dense/projection weights: every 2-D linear weight (or
    depth-STACKED 3-D weight — the reversible trunk's layout) on a path
    through the trunk layer stack. Embeddings/LayerNorm never reach here
    (no "w" leaf of rank >= 2); the KV-compress conv is excluded BY NAME
    (its "w" is a genuine 3-D (ratio, in_per_group, inner) conv kernel
    that `linear` never sees, ops/attention.py:158 reads it directly);
    the distogram head (`head_out`) and front-end projections are
    deliberately excluded — output quality-sensitive, and a
    rounding-error share of total bytes."""
    parts = path.split("/")
    return (
        "trunk" in parts
        and "compress" not in parts
        and getattr(w, "ndim", 0) in (2, 3)
    )


def _walk(tree, path, fn):
    """Rebuild a dict/list/tuple pytree, giving `fn(path, subtree)` first
    right of refusal at every dict node (return None = recurse)."""
    if isinstance(tree, dict):
        replaced = fn(path, tree)
        if replaced is not None:
            return replaced
        return {
            k: _walk(v, f"{path}/{k}" if path else str(k), fn)
            for k, v in tree.items()
        }
    if isinstance(tree, (list, tuple)):
        seq = [
            _walk(v, f"{path}/{i}" if path else str(i), fn)
            for i, v in enumerate(tree)
        ]
        return type(tree)(seq) if isinstance(tree, tuple) else seq
    return tree


def quantize_tree(
    params,
    select: Optional[Callable[[str, object], bool]] = None,
    *,
    per_channel: bool = True,
):
    """PTQ a parameter pytree: rewrite every selected linear-param dict
    `{"w": (d_in, d_out), ...}` to `{"qw": int8, "scale": f32, ...}`.

    `select(path, w) -> bool` picks weights by named path (default:
    `default_quant_select` — the trunk's dense/projection weights).
    Returns a NEW tree; the fp32 master is untouched. Pure jnp — safe
    under `jax.eval_shape` for chip-free residency accounting."""
    select = default_quant_select if select is None else select

    def visit(path, d):
        w = d.get("w")
        if w is None or getattr(w, "ndim", 0) < 2:
            return None
        if not select(path, w):
            return None
        qw, scale = quantize_weight(w, per_channel=per_channel)
        out = {k: v for k, v in d.items() if k != "w"}
        out["qw"], out["scale"] = qw, scale
        return out

    return _walk(params, "", visit)


def dequantize_tree(params):
    """Inverse structure transform: every `{"qw", "scale", ...}` dict back
    to `{"w": dequantized fp32, ...}` — the pure-XLA reference arm's tree
    (and the restore path for tooling that expects fp32 weights)."""

    def visit(path, d):
        if not is_quantized_linear(d):
            return None
        out = {k: v for k, v in d.items() if k not in ("qw", "scale")}
        out["w"] = dequantize_weight(d["qw"], d["scale"])
        return out

    return _walk(params, "", visit)


# ---------------------------------------------------------------------------
# residency accounting (chip-free: works on ShapeDtypeStructs)
# ---------------------------------------------------------------------------


def tree_weight_bytes(params) -> int:
    """Total resident bytes of a parameter pytree — the weight side of the
    HBM budget a serving replica pays per config tag. Works on concrete
    arrays AND abstract ShapeDtypeStructs (`jax.eval_shape` trees), so
    bench legs can record it with the TPU unreachable."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        size = 1
        for s in leaf.shape:
            size *= int(s)
        total += size * jnp.dtype(leaf.dtype).itemsize
    return total


def quantized_path_bytes(params) -> Tuple[int, int]:
    """(fp32 bytes of the quantizable weights, bytes after PTQ) over the
    DEFAULT selection — the per-tensor residency ratio the acceptance
    gate pins (>= 3.5x on the north-star preset: 4x from int8 minus the
    per-channel scale overhead of 4/d_in)."""
    before = after = 0
    for path, d in iter_linear_dicts(params):
        w = d.get("w")
        if w is not None and getattr(w, "ndim", 0) >= 2 \
                and default_quant_select(path, w):
            n = 1
            for s in w.shape:
                n *= int(s)
            stack = n // (int(w.shape[-2]) * int(w.shape[-1]))
            before += n * jnp.dtype(w.dtype).itemsize
            # int8 values + f32 per-(slice, out-channel) scales
            after += n + stack * int(w.shape[-1]) * 4
        elif is_quantized_linear(d):
            n = 1
            for s in d["qw"].shape:
                n *= int(s)
            before += n * 4
            after += tree_weight_bytes({"qw": d["qw"], "scale": d["scale"]})
    return before, after


def iter_linear_dicts(params, path: str = ""):
    """Yield (path, dict) for every dict node holding a "w" or "qw" leaf."""
    if isinstance(params, dict):
        if "w" in params or "qw" in params:
            yield path, params
            return
        for k, v in params.items():
            yield from iter_linear_dicts(v, f"{path}/{k}" if path else str(k))
    elif isinstance(params, (list, tuple)):
        for i, v in enumerate(params):
            yield from iter_linear_dicts(v, f"{path}/{i}" if path else str(i))


# ---------------------------------------------------------------------------
# mixed-precision matmul: dispatch + XLA reference arm
# ---------------------------------------------------------------------------


def quant_matmul_xla(x, qw, scale):
    """Pure-XLA dequant reference arm: materialize the dequantized f32
    weight, matmul with f32 accumulation, cast once at the end — the
    same epilogue math as the kernel (scale in f32 on the f32
    accumulator), paid for with a full fp32 weight copy in HBM. x is 2-D
    (m, k); qw (k, n) int8; scale (n,) f32."""
    w = dequantize_weight(qw, scale)
    y = jax.lax.dot_general(
        x, w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return y.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _quant_core(x, qw, scale, kernel: bool):
    if kernel:
        from alphafold2_tpu.ops.quant_kernel import quant_matmul_tpu

        return quant_matmul_tpu(x, qw, scale)
    return quant_matmul_xla(x, qw, scale)


def _quant_core_fwd(x, qw, scale, kernel):
    return _quant_core(x, qw, scale, kernel), None


def _quant_core_bwd(kernel, res, g):
    raise NotImplementedError(
        "int8 weight-quantized matmuls are inference-only: differentiating "
        "through quant_matmul would silently train on straight-through "
        "rounding noise. Train on the fp32 master weights "
        "(Alphafold2Config.weight_dtype='f32') and re-quantize post-training."
    )


_quant_core.defvjp(_quant_core_fwd, _quant_core_bwd)


def quant_matmul(x, qw, scale, *, use_kernel="auto", dtype=None):
    """y = x @ dequant(qw, scale), without dequantizing in HBM on the
    kernel path.

    x: (..., d_in) f32/bf16 activations (leading dims flattened for the
    kernel); qw: (d_in, d_out) int8; scale: per-output-channel (d_out,)
    f32, or a scalar per-tensor scale (broadcast). `dtype` casts the
    activations first (the `linear` compute-dtype contract); the output
    is in the activation compute dtype. use_kernel: True forces the
    kernel (ValueError on unsupported shapes/dtypes), False the XLA
    dequant arm, "auto" asks ops/dispatch.py `resolve`. Inference-only —
    the backward raises."""
    from alphafold2_tpu.ops import dispatch

    if dtype is not None:
        x = x.astype(dtype)
    if qw.ndim != 2:
        raise ValueError(
            f"quant_matmul takes one (d_in, d_out) weight slice, got "
            f"{qw.shape} — stacked (depth, ...) quantized trees are sliced "
            f"by the trunk's lax.scan before reaching the matmul"
        )
    d_in, d_out = qw.shape
    if x.shape[-1] != d_in:
        raise ValueError(
            f"activation feature dim {x.shape[-1]} != weight d_in {d_in}"
        )
    scale = jnp.broadcast_to(
        jnp.asarray(scale, jnp.float32).reshape(-1), (d_out,)
    )
    lead = x.shape[:-1]
    m = 1
    for s in lead:
        m *= int(s)
    x2 = x.reshape(m, d_in)
    arm = dispatch.resolve("quant_matmul", request=use_kernel,
                           m=m, k=d_in, n=d_out, x_dtype=x2.dtype)
    y = _quant_core(x2, qw, scale, arm == dispatch.ARM_PALLAS_TPU)
    return y.reshape(lead + (d_out,))


# ---------------------------------------------------------------------------
# training-side guard
# ---------------------------------------------------------------------------


def reject_quant_training(model_cfg, where: str) -> None:
    """Loudly refuse to build a training path over an int8-weight config.
    Called by every train-state/step constructor (training/harness.py,
    training/e2e.py) BEFORE any tracing, so the failure names the entry
    point instead of surfacing as a custom-vjp error deep in a scan.
    Accepts an Alphafold2Config OR a wrapper carrying one as `.model`
    (E2EConfig) — the harness builders take either."""
    model_cfg = getattr(model_cfg, "model", model_cfg)
    if getattr(model_cfg, "weight_dtype", "f32") == "int8":
        raise ValueError(
            f"{where}: weight_dtype='int8' is the inference-only serving "
            f"arm (per-channel PTQ over frozen weights, non-differentiable "
            f"by construction); train with weight_dtype='f32' and quantize "
            f"post-training (ops/quant.py quantize_tree)"
        )
