"""Mixture of experts for one chip's share of an expert-parallel layer.

The layer is TOLD which experts it holds (`held = (lo, hi)` of the
router's `n_experts`). The router scores every expert; the layer gathers
the token-assignments whose expert lies in `[lo, hi)`, sorts them by
expert, runs the three grouped products of a SwiGLU over the sorted rows
and scatters the results back, weighted. What the absent experts would
add is left out (on a deployment it arrives through the exchange, which
one chip runs without); nothing here stands in for other chips.

No capacity and no dropped token: the sorted rows are walked in blocks
of `block_rows_for` rows by a loop whose bound is the number of LIVE
blocks (`ceil(held assignments / block rows)`, data: a while loop), so
the work follows the load the router gave, up to the worst case (every
token's every pick held here) and not beyond the load by more than a
block. The bound being data, autodiff cannot transpose the loop: `_walk`
is a `jax.custom_vjp` whose backward is a second loop over the same
blocks, written by hand across blocks (the float32 carries of the
experts' weight gradients, of x's and of the routing weights') and left
to autodiff within one (`jax.vjp` of `_block_rows_out` under
`jax.checkpoint`, so the grouped products keep their own pairing).
Nothing in a layer's backward reads the layer's output, so under the
layer's checkpoint (models/decoder.py) the second forward of the loop is
dead code and XLA removes it. `rows_walked` is the loop's own statement of
its work, a step metric (`training/lm.py`: `moe_rows_walked`).

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

import functools

import jax
import jax.numpy as jnp

from alphafold2_tpu.ops import dispatch
from alphafold2_tpu.ops.core import pallas_interpret
from alphafold2_tpu.telemetry.profiling import scope


#: the grouped kernels' largest row tile: blocks are whole tiles
ROW_TILE = 512
#: rows of a block over the load a balanced router gives. On the chip a
#: live block costs 2-6 ms a layer whatever it holds (a pass over the
#: float32 weight-gradient carries, and each grouped kernel's visit of
#: every expert's tiles) against 0.3-0.5 us a row walked beyond the load,
#: so one block should hold the load the layer usually gets, with the
#: least room above it that the load's swing allows: a quarter (a load
#: beyond it takes a second block; `benchmarks/records/moe_block_sweep_pr36.jsonl`)
BLOCK_OVER_EXPECTED = 1.25


def block_rows_for(n_tokens: int, top_k: int, n_held: int, n_experts: int) -> int:
    """Sorted token-assignments the expert loop takes at a time:
    `BLOCK_OVER_EXPECTED` times the load a balanced router gives
    (n_tokens * top_k * n_held / n_experts), in whole row tiles, at most
    the worst case."""
    expected = n_tokens * top_k * n_held / n_experts
    tiles = -(-int(BLOCK_OVER_EXPECTED * expected) // ROW_TILE)
    return min(tiles * ROW_TILE, n_tokens * min(top_k, n_held))


def rows_walked(assignments_held, block_rows: int):
    """Sorted rows the expert loop visits for `assignments_held` held
    assignments: its live blocks times a block's rows."""
    return jnp.ceil(assignments_held / block_rows) * block_rows


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


def _block_of(plan, i, block_rows: int):
    """Block `i` of the sorted rows: its tokens, routing weights, group
    sizes clipped to the block, and which of its rows hold an assignment."""
    tok, w_sorted, starts, ends, total = plan
    start = i * block_rows
    t = jax.lax.dynamic_slice(tok, (start,), (block_rows,))
    wc = jax.lax.dynamic_slice(w_sorted, (start,), (block_rows,))
    sizes = (jnp.clip(ends, start, start + block_rows)
             - jnp.clip(starts, start, start + block_rows))
    live = (start + jnp.arange(block_rows) < total)[:, None]
    return start, t, wc, sizes, live


def _block_rows_out(xc, wc, w, sizes, live):
    """What one block's rows add to their tokens, (block_rows, d) float32:
    the rows' experts' SwiGLU of `xc`, times the routing weights `wc`."""

    def keep(part):
        # the rows past the last held assignment belong to no group: what
        # a grouped product gives for them, forward and backward, is
        # unspecified and stops here both ways
        return jnp.where(live, part, 0)

    with scope("dispatch"):
        xc = keep(xc)
    with scope("experts"):
        gate = keep(grouped_matmul(xc, w["gate"], sizes))
        up = keep(grouped_matmul(xc, w["up"], sizes))
        y = keep(grouped_matmul(keep(jax.nn.silu(gate) * up), w["down"], sizes))
    with scope("combine"):
        return y.astype(jnp.float32) * wc[:, None]


def _live_blocks(total, block_rows: int):
    return (total + block_rows - 1) // block_rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _walk(block_rows, x, experts, w_sorted, tok, starts, ends, total):
    """sum over the LIVE blocks of the sorted rows of `_block_rows_out`,
    scattered to the rows' tokens: (N, d) float32. A loop whose bound is
    data, so the vjp is written by hand across blocks (`_walk_bwd`)."""
    return _walk_fwd(block_rows, x, experts, w_sorted, tok, starts, ends, total)[0]


def _walk_fwd(block_rows, x, experts, w_sorted, tok, starts, ends, total):
    w = {name: leaf.astype(x.dtype) for name, leaf in experts.items()}
    plan = (tok, w_sorted, starts, ends, total)

    def block(i, out):
        _, t, wc, sizes, live = _block_of(plan, i, block_rows)
        with scope("dispatch"):
            xc = x[t]
        y = _block_rows_out(xc, wc, w, sizes, live)
        with scope("combine"):
            return out.at[t].add(y)

    out = jax.lax.fori_loop(0, _live_blocks(total, block_rows), block,
                            jnp.zeros(x.shape, jnp.float32))
    return out, (x, experts, plan)


def _walk_bwd(block_rows, residuals, g):
    x, experts, plan = residuals
    w = {name: leaf.astype(x.dtype) for name, leaf in experts.items()}
    w_sorted, total = plan[1], plan[4]

    def block(i, carry):
        dx, dw, dw_sorted = carry
        start, t, wc, sizes, live = _block_of(plan, i, block_rows)
        with scope("dispatch"):
            xc = x[t]
        with scope("combine"):
            gy = g[t]
        # within a block the vjp is autodiff's (the grouped products keep
        # their own pairing); its forward runs again under jax.checkpoint
        _, pull = jax.vjp(
            jax.checkpoint(lambda xc, wc, w: _block_rows_out(xc, wc, w, sizes, live)),
            xc, wc, w)
        dxc, dwc, dwb = pull(gy)
        with scope("dispatch"):
            dx = dx.at[t].add(dxc.astype(jnp.float32))
        with scope("experts"):
            dw = {name: dw[name] + dwb[name].astype(jnp.float32) for name in dw}
        with scope("combine"):
            dw_sorted = jax.lax.dynamic_update_slice(dw_sorted, dwc, (start,))
        return dx, dw, dw_sorted

    dx, dw, dw_sorted = jax.lax.fori_loop(
        0, _live_blocks(total, block_rows), block,
        (jnp.zeros(x.shape, jnp.float32),
         {name: jnp.zeros(leaf.shape, jnp.float32) for name, leaf in w.items()},
         jnp.zeros_like(w_sorted)))
    dw = {name: dw[name].astype(experts[name].dtype) for name in dw}
    return dx.astype(x.dtype), dw, dw_sorted, None, None, None, None


_walk.defvjp(_walk_fwd, _walk_bwd)


def experts_apply(params, x, idx, weights, *, held, block_rows: int):
    """The held experts' part of `sum_e w_e SwiGLU_e(x)`. x: (N, d) in the
    compute dtype; idx, weights: (N, top_k) from `route`; params: `gate`,
    `up` (E_held, d, f) and `down` (E_held, f, d). Returns (N, d)
    float32."""
    lo, hi = held
    n_held = hi - lo
    N, top_k = idx.shape
    with scope("dispatch"):
        flat = idx.reshape(-1)
        local = jnp.where((flat >= lo) & (flat < hi), flat - lo, n_held)
        order = jnp.argsort(local, stable=True)  # held first, by expert
        counts = jnp.zeros((n_held + 1,), jnp.int32).at[local].add(1)[:n_held]
        ends = jnp.cumsum(counts)
        starts, total = ends - counts, ends[-1]
        # the worst case: every pick of every token held here
        rows = N * min(top_k, n_held)
        block_rows = min(block_rows, rows)
        pad = -rows % block_rows
        tok = jnp.pad(order[:rows] // top_k, (0, pad))
        w_sorted = jnp.pad(weights.reshape(-1)[order[:rows]], (0, pad))
    experts = {name: params[name]["w"] for name in ("gate", "up", "down")}
    return _walk(block_rows, x, experts, w_sorted, tok, starts, ends, total)


def moe_apply(params, x, routing, *, held):
    """One MoE feed-forward on x (N, d) under the caller's `routing` =
    (idx, weights, load) of `route` or `route_softmax`: the held routed
    experts' part, plus the shared experts in full where the layer has any
    (without one, a token whose experts are all absent gets nothing).
    Returns (y (N, d) in x.dtype, {"load": (E,), "picks": (N, top_k),
    "rows_walked": () float32, the sorted rows the expert loop visits})."""
    idx, weights, load = routing
    lo, hi = held
    block_rows = block_rows_for(x.shape[0], idx.shape[1], hi - lo, load.shape[-1])
    y = experts_apply(params["experts"], x, idx, weights, held=held,
                      block_rows=block_rows)
    if "shared" in params:
        with scope("shared_expert"):
            y = y + swiglu(params["shared"], x, x.dtype).astype(jnp.float32)
    return y.astype(x.dtype), {
        "load": load, "picks": idx,
        "rows_walked": rows_walked(jnp.sum(load[lo:hi]), block_rows)}
