"""Mixture of experts for one chip's share of an expert-parallel layer.

The layer is TOLD which experts it holds (`held = (lo, hi)` of the
router's `n_experts`). The router scores every expert; the layer gathers
the token-assignments whose expert lies in `[lo, hi)`, sorts them by
expert, runs the three grouped products of a SwiGLU over the sorted rows
and scatters the results back, weighted. What the absent experts would
add is left out (on a deployment it arrives through the exchange, which
one chip runs without); nothing here stands in for other chips.

No capacity and no dropped token: the sorted rows are walked in chunks
up to the worst case (every token's every pick held here), and a chunk
past the last held assignment is skipped by `lax.cond`, so the work
follows the load the router gave, not the bound. A chunk holds
`CHUNK_OVER_EXPECTED` times the load the layer expects from its inputs
(`chunk_rows_for`).

The layer is also TOLD its routing: `moe_apply` takes (picks, weights,
load) from its caller, so that a model with a router of its own shares
everything after it. The routers here pick the top-k of `scores + b`
(`pick`: `b` a selection bias that carries no gradient and is moved after
each step by `bias_update`; a router without one hands None) and weigh by
the scores at the picks:

  * `route` (DeepSeek-V3's `noaux_tc`, as HF `deepseek_v3` computes it
    with `n_group` = `topk_group` = 1): `s = sigmoid(x W_g)` in float32;
    the weights are `s` at the picks, divided by their sum, times
    `routed_scaling_factor`;
  * `route_softmax` (ZAYA1): the caller's logits (models/decoder.py: an
    MLP over a state carried from layer to layer) through a softmax in
    float32; the weight is the picked probability itself;
  * `route_softmax` with `norm_topk` and no bias (Mellum 2, the Qwen3-MoE
    rule): `softmax(x W_g)` (`router_logits`) over all experts, the top-k,
    their probabilities divided by their sum.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from alphafold2_tpu.ops import dispatch
from alphafold2_tpu.ops.core import pallas_interpret
from alphafold2_tpu.telemetry.profiling import scope


#: rows of a chunk over the rows the layer expects to hold. A chunk past
#: the load is skipped, yet costs the backward a pass over the experts'
#: weight gradients, and a second LIVE chunk its gathers and scatters, so
#: one chunk should hold every load short of a collapsed router's; what
#: a chunk's rows cost whether they hold an assignment or not (the
#: gathers, the masks) grows with the factor
CHUNK_OVER_EXPECTED = 2
#: the grouped kernels' largest row tile: chunks are whole tiles
ROW_TILE = 512


def chunk_rows_for(n_tokens: int, top_k: int, n_held: int, n_experts: int) -> int:
    """Sorted token-assignments the expert layer takes at a time:
    `CHUNK_OVER_EXPECTED` times the expected load of a balanced router
    (n_tokens * top_k * n_held / n_experts), in whole row tiles, at most
    the worst case."""
    expected = n_tokens * top_k * n_held / n_experts
    tiles = -(-int(CHUNK_OVER_EXPECTED * expected) // ROW_TILE)
    return min(tiles * ROW_TILE, n_tokens * min(top_k, n_held))


def grouped_kernel_supported(m: int, k: int, n: int) -> bool:
    """Shapes the megablox kernels take here: whole row tiles, and lanes
    of 128 on both matrix sides."""
    return m % 128 == 0 and k % 128 == 0 and n % 128 == 0


def _grouped_tiling(m: int, k: int, n: int):
    tm = next(t for t in (512, 256, 128) if m % t == 0)
    return tm, min(k, 1024), min(n, 1024)


def grouped_matmul(x, w, group_sizes, use_kernel="auto"):
    """Rows of x (m, k), sorted by group, each times its group's matrix of
    w (g, k, n): row r of group e gives x[r] @ w[e], in x.dtype.
    `group_sizes` (g,) int32 may sum to less than m; the rows past the sum
    belong to no group: their result is UNSPECIFIED, and so is their row
    of x's gradient (on a TPU both arms leave them unwritten), so the
    caller masks both (`experts_apply`). One dispatched op: `pallas_tpu` is
    JAX's megablox kernels (`gmm`, with `tgmm` for w's gradient), whose
    grid ends at the last group's tile; `xla_ref` is
    `jax.lax.ragged_dot`."""
    m, k = x.shape
    n = w.shape[2]
    arm = dispatch.resolve("grouped_matmul", request=use_kernel,
                           m=m, k=k, n=n, groups=w.shape[0])
    if arm == dispatch.ARM_PALLAS_TPU:
        from alphafold2_tpu.compat import megablox

        return megablox.gmm(x, w, group_sizes, x.dtype, _grouped_tiling(m, k, n),
                            None, None, False, pallas_interpret())
    return jax.lax.ragged_dot(x, w, group_sizes)


def pick(scores, bias, top_k: int):
    """The top-k of `scores + bias` over ALL experts. scores: (N, E)
    float32. Returns (idx (N, top_k) int32, the scores at the picks
    (N, top_k), load (E,) float32: how many of the N * top_k assignments
    each expert received). The bias enters the choice and not the weight;
    None where the router has none."""
    chosen_by = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    _, idx = jax.lax.top_k(chosen_by, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    load = jnp.zeros((scores.shape[-1],), jnp.float32).at[idx.reshape(-1)].add(1.0)
    return idx, w, jax.lax.stop_gradient(load)


def router_logits(params, x):
    """x W_g over ALL experts, in float32. x: (N, d)."""
    return jnp.matmul(x.astype(jnp.float32), params["router"]["w"],
                      precision=jax.lax.Precision.HIGHEST)


def _over_their_sum(w):
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)


def route(params, x, *, top_k: int, scaling: float, norm_topk: bool):
    """Sigmoid scores over ALL experts and the picks. x: (N, d). Returns
    (idx, weights, load) as `pick` does, the weights normalised and
    scaled."""
    s = jax.nn.sigmoid(router_logits(params, x))
    idx, w, load = pick(s, params["bias"], top_k)
    if norm_topk:
        w = _over_their_sum(w)
    return idx, w * scaling, load


def route_softmax(logits, bias, top_k: int = 1, norm_topk: bool = False):
    """Softmax probabilities over ALL experts from the caller's `logits`
    (N, E), in float32, and the picks; the weight of a pick is its
    probability, with `norm_topk` over the sum of the token's picked ones.
    `bias` None: the router has no selection bias. Returns (idx, weights,
    load) as `pick` does."""
    idx, w, load = pick(jax.nn.softmax(logits.astype(jnp.float32), axis=-1),
                        bias, top_k)
    return idx, _over_their_sum(w) if norm_topk else w, load


def bias_update(bias, load, rate: float):
    """`b_e += rate * sign(mean load - load_e)`: an expert under the mean
    becomes likelier to be picked, one over it less (DeepSeek-V3 report,
    section 2.1.2). Outside the gradient."""
    mean = jnp.mean(load, axis=-1, keepdims=True)
    return bias + rate * jnp.sign(mean - load)


def swiglu(params, x, dtype):
    """down(silu(gate(x)) * up(x)), no biases."""
    w = {name: params[name]["w"].astype(dtype) for name in ("gate", "up", "down")}
    return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]


def experts_apply(params, x, idx, weights, *, held, chunk_rows: int):
    """The held experts' part of `sum_e w_e SwiGLU_e(x)`. x: (N, d) in the
    compute dtype; idx, weights: (N, top_k) from `route`; params: `gate`,
    `up` (E_held, d, f) and `down` (E_held, f, d). Returns (N, d)
    float32."""
    lo, hi = held
    n_held = hi - lo
    N, top_k = idx.shape
    dtype = x.dtype
    with scope("dispatch"):
        flat = idx.reshape(-1)
        local = jnp.where((flat >= lo) & (flat < hi), flat - lo, n_held)
        order = jnp.argsort(local, stable=True)  # held first, by expert
        counts = jnp.zeros((n_held + 1,), jnp.int32).at[local].add(1)[:n_held]
        ends = jnp.cumsum(counts)
        starts, total = ends - counts, ends[-1]
        # the worst case: every pick of every token held here
        rows = N * min(top_k, n_held)
        chunk_rows = min(chunk_rows, rows)
        n_chunks = -(-rows // chunk_rows)
        pad = n_chunks * chunk_rows - rows
        tok = jnp.pad(order[:rows] // top_k, (0, pad))
        w_sorted = jnp.pad(weights.reshape(-1)[order[:rows]], (0, pad))
    w = {name: params[name]["w"].astype(dtype) for name in ("gate", "up", "down")}

    def run(out, start):
        with scope("dispatch"):
            t = jax.lax.dynamic_slice(tok, (start,), (chunk_rows,))
            wc = jax.lax.dynamic_slice(w_sorted, (start,), (chunk_rows,))
            sizes = (jnp.clip(ends, start, start + chunk_rows)
                     - jnp.clip(starts, start, start + chunk_rows))
            live = (start + jnp.arange(chunk_rows) < total)[:, None]

            def keep(part):
                # the rows past the last held assignment belong to no
                # group: what a grouped product gives for them, forward
                # and backward, is unspecified and stops here both ways
                return jnp.where(live, part, 0)

            xc = keep(x[t])
        with scope("experts"):
            gate = keep(grouped_matmul(xc, w["gate"], sizes))
            up = keep(grouped_matmul(xc, w["up"], sizes))
            y = keep(grouped_matmul(keep(jax.nn.silu(gate) * up), w["down"], sizes))
        with scope("combine"):
            return out.at[t].add(y.astype(jnp.float32) * wc[:, None])

    def chunk(out, c):
        start = c * chunk_rows
        return jax.lax.cond(start < total, lambda o: run(o, start),
                            lambda o: o, out), None

    out = jnp.zeros(x.shape, jnp.float32)
    out, _ = jax.lax.scan(jax.checkpoint(chunk), out, jnp.arange(n_chunks))
    return out


def moe_apply(params, x, routing, *, held):
    """One MoE feed-forward on x (N, d) under the caller's `routing` =
    (idx, weights, load) of `route` or `route_softmax`: the held routed
    experts' part, plus the shared experts in full where the layer has any
    (without one, a token whose experts are all absent gets nothing).
    Returns (y (N, d) in x.dtype, {"load": (E,), "picks": (N, top_k)})."""
    idx, weights, load = routing
    y = experts_apply(
        params["experts"], x, idx, weights, held=held,
        chunk_rows=chunk_rows_for(x.shape[0], idx.shape[1], held[1] - held[0],
                                  load.shape[-1]))
    if "shared" in params:
        with scope("shared_expert"):
            y = y + swiglu(params["shared"], x, x.dtype).astype(jnp.float32)
    return y.astype(x.dtype), {"load": load, "picks": idx}
