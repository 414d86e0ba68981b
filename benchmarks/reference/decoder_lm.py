"""Plain reference of the `deepseek_v3` decoder's training step: forward,
next-token loss, gradients, Adam and the router's bias update.

Straightforward `jax.numpy` in float32 with every contraction at
`Precision.HIGHEST`; no kernels, no streaming softmax, no sorted
dispatch, no compute-dtype casts. It imports nothing of the program. It
reads a parameter tree of the layout `hp` describes (`embed`, `dense` and
`moe` stacks of layers, `final_norm`, `head`) and follows HF
`transformers` `deepseek_v3`:

  h += MLA(RMSNorm(h)); h += MLP(RMSNorm(h)); final RMSNorm; untied head
  MLA: q = x W_q (heads of nope | rope); [c | k_r] = x W_dkv; c =
       RMSNorm(c); [k_nope | v] = c W_ukv; RoPE (interleaved pairs) on q's
       rope part and on the one k_r; softmax(q [k_nope | k_r]^T /
       sqrt(nope + rope)) under the causal mask, materialised for a block
       of queries at a time; heads of v through W_o
  MoE: s = sigmoid(x W_g); top-k of s + b; weights s / sum * scaling;
       y = sum over the experts HELD of w_e SwiGLU_e(x), a dense loop over
       them with every token through every held expert, plus the shared
       SwiGLU. What the absent experts would add is left out, as in the
       program: the same share of the same deployment.

`hp`: heads, nope, rope, dv, lora, eps, theta, top_k, scaling, norm_topk,
held (lo, hi), lr, bias_rate, and the blocks (`attn_block` queries,
`ff_block` tokens of a feed-forward, `loss_block` rows of logits; 0 =
whole).

`q` is the operand rounding of the control (`lowprec.py`), applied to
both operands of every contraction. `None` is the reference itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _q(q, t):
    return t if q is None else q(t)


def mm(a, b, q=None):
    return jnp.matmul(_q(q, a), _q(q, b), precision=HIGHEST)


def ein(spec, a, b, q=None):
    return jnp.einsum(spec, _q(q, a), _q(q, b), precision=HIGHEST)


def rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x (B, L, ..., d): pairs (x_2i, x_2i+1) turned by position *
    theta^(-2i/d)."""
    L, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((1, L) + (1,) * (x.ndim - 3) + (d // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def blocked(fn, x_args, block):
    """fn(*x_args) with every x_arg cut along axis 0 into blocks of at
    most `block` rows (the largest divisor of the axis that fits), each
    block under checkpoint: where memory is spent, not one number."""
    n = x_args[0].shape[0]
    if not block or n <= block:
        return fn(*x_args)
    while n % block:
        block -= 1
    cut = [a.reshape((n // block, block) + a.shape[1:]) for a in x_args]
    out = jax.lax.map(jax.checkpoint(lambda args: fn(*args)), tuple(cut))
    return out.reshape((n,) + out.shape[2:])


def swiglu(p, x, q=None, block=0):
    """down(silu(gate(x)) * up(x)), no biases. x: (N, d)."""
    def core(t):
        return mm(jax.nn.silu(mm(t, p["gate"]["w"], q)) * mm(t, p["up"]["w"], q),
                  p["down"]["w"], q)

    return blocked(core, (x,), block)


def causal_attention(qh, k, v, scale, block, q=None):
    """softmax(qh k^T * scale) v under the causal mask; the logits of
    `block` queries against every key stand at a time. (B, L, h, d)."""
    B, L = qh.shape[:2]
    block = L if not block or block > L else block
    while L % block:
        block -= 1
    cols = jnp.arange(L)

    @jax.checkpoint
    def one(q_blk, row0):
        logits = ein("bihd,bjhd->bhij", q_blk, k, q) * scale
        rows = row0 + jnp.arange(block)
        logits = jnp.where(cols[None, :] <= rows[:, None], logits, -jnp.inf)
        return ein("bhij,bjhd->bihd", jax.nn.softmax(logits, axis=-1), v, q)

    blocks = qh.reshape(B, L // block, block, *qh.shape[2:]).swapaxes(0, 1)
    out = jax.lax.map(lambda a: one(*a), (blocks, jnp.arange(0, L, block)))
    return out.swapaxes(0, 1).reshape(B, L, *out.shape[3:])


def mla(p, x, hp, q=None):
    B, L, _ = x.shape
    h, nope, rd, dv = hp["heads"], hp["nope"], hp["rope"], hp["dv"]
    qh = mm(x, p["q"]["w"], q).reshape(B, L, h, nope + rd)
    ckr = mm(x, p["dkv"]["w"], q)
    c = rms_norm(p["kv_norm"]["scale"], ckr[..., :hp["lora"]], hp["eps"])
    k_r = rope(ckr[..., hp["lora"]:][:, :, None, :], hp["theta"])
    kv = mm(c, p["ukv"]["w"], q).reshape(B, L, h, nope + dv)
    qh = jnp.concatenate([qh[..., :nope], rope(qh[..., nope:], hp["theta"])], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (B, L, h, rd))], -1)
    out = causal_attention(qh, k, kv[..., nope:], (nope + rd) ** -0.5,
                           hp["attn_block"], q)
    return mm(out.reshape(B, L, h * dv), p["o"]["w"], q)


def route(p, x, hp, q=None):
    """(picks (N, top_k), weights (N, top_k), load (E,))."""
    s = jax.nn.sigmoid(mm(x, p["router"]["w"], q))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(p["bias"]), hp["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if hp["norm_topk"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    load = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32), axis=(0, 1))
    return idx, w * hp["scaling"], load


def moe(p, x, hp, q=None):
    """x (N, d) -> (y, picks, load): the held experts one after another,
    every token through each, plus the shared experts."""
    idx, w, load = route(p, x, hp, q)
    lo, hi = hp["held"]

    def routed(x_blk, idx_blk, w_blk):
        def one(y, e_and_params):
            e, pe = e_and_params
            # the expert's weight for each token: 0 where it was not picked
            w_e = jnp.sum(jnp.where(idx_blk == e, w_blk, 0.0), axis=-1)
            return y + w_e[:, None] * swiglu(pe, x_blk, q), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x_blk),
                            (jnp.arange(lo, hi), p["experts"]))
        return y

    y = blocked(routed, (x, idx, w), hp["ff_block"])
    return (y + swiglu(p["shared"], x, q, hp["ff_block"]), idx,
            jax.lax.stop_gradient(load))


def layer(lp, h, hp, is_moe, q=None):
    h = h + mla(lp["attn"], rms_norm(lp["attn_norm"]["scale"], h, hp["eps"]), hp, q)
    x = rms_norm(lp["mlp_norm"]["scale"], h, hp["eps"])
    B, L, d = x.shape
    x = x.reshape(B * L, d)
    if not is_moe:
        return h + swiglu(lp["mlp"], x, q, hp["ff_block"]).reshape(B, L, d), None
    y, idx, load = moe(lp["mlp"], x, hp, q)
    return h + y.reshape(B, L, d), (idx, load)


def layer_at(stack, i):
    return jax.tree_util.tree_map(lambda t: t[i], stack)


def cross_entropy(hidden, head_w, targets, weights, block, q=None):
    n = hidden.shape[0]
    block = n if not block or block > n else block
    while n % block:
        block -= 1

    @jax.checkpoint
    def one(h, t, w):
        logits = mm(h, head_w, q)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(w * (lse - jnp.take_along_axis(logits, t[:, None], -1)[:, 0]))

    cut = lambda a: a.reshape((n // block, block) + a.shape[1:])  # noqa: E731
    return jnp.sum(jax.lax.map(lambda a: one(*a), (cut(hidden), cut(targets), cut(weights))))


STACKS = (("dense", False), ("moe", True))


def unstack(params):
    """(everything outside the layers, [one layer's parameters], (is_moe of
    each layer)) from the tree whose `dense` and `moe` entries stack their
    layers."""
    outer = {k: v for k, v in params.items() if k not in dict(STACKS)}
    layers, kinds = [], []
    for name, is_moe in STACKS:
        for i in range(params[name]["attn_norm"]["scale"].shape[0] if name in params else 0):
            layers.append(layer_at(params[name], i))
            kinds.append(is_moe)
    return outer, layers, tuple(kinds)


def restack(outer, layers, kinds, stack=jnp.stack):
    """The stacked tree again; `stack` combines one leaf's per-layer
    values (`jnp.stack` for arrays, anything else for what was computed
    leaf by leaf: `stacked_norms`)."""
    out = dict(outer)
    for name, is_moe in STACKS:
        mine = [lp for lp, m in zip(layers, kinds) if m == is_moe]
        if mine:
            out[name] = jax.tree_util.tree_map(lambda *ts: stack(ts), *mine)
    return out


def stacked_norms(outer, layers, kinds):
    """Each leaf's L2 norm AS IF the layers were stacked (the root of the
    layers' squared norms), as a tree of the stacked layout."""
    @jax.jit
    def squares(tree):
        return jax.tree_util.tree_map(lambda t: jnp.sum(jnp.square(t)), tree)

    sq = restack(squares(outer), [squares(lp) for lp in layers], kinds,
                 stack=lambda ts: sum(ts))
    return jax.tree_util.tree_map(lambda v: float(jnp.sqrt(v)), sq)


def loss_of_layers(outer, layers, kinds, tokens, hp, q=None):
    """Mean over the L - 1 targets of each sequence, then over sequences.
    Returns (loss, (picks (n_moe, N, top_k), load (n_moe, E)))."""
    B, L = tokens.shape
    h = outer["embed"]["table"][tokens]
    picks, loads = [], []
    for lp, is_moe in zip(layers, kinds):
        h, aux = jax.checkpoint(
            functools.partial(layer, hp=hp, is_moe=is_moe, q=q))(lp, h)
        if aux is not None:
            picks.append(aux[0])
            loads.append(aux[1])
    h = rms_norm(outer["final_norm"]["scale"], h, hp["eps"])
    # every position gives a row, so that the rows divide into blocks; the
    # last of each sequence has no target and weighs nothing
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    weights = jnp.broadcast_to((jnp.arange(L) < L - 1).astype(jnp.float32), (B, L))
    total = cross_entropy(h.reshape(B * L, -1), outer["head"]["w"],
                          targets.reshape(-1), weights.reshape(-1),
                          hp["loss_block"], q)
    aux = (jnp.stack(picks), jnp.stack(loads)) if picks else (None, None)
    return total / (B * (L - 1)), aux


@functools.partial(jax.jit, static_argnums=(2, 4, 5))
def _value_and_grad_layers(outer, layers, kinds, tokens, hp_items, q):
    (loss, (picks, load)), grads = jax.value_and_grad(
        loss_of_layers, argnums=(0, 1), has_aux=True)(
            outer, layers, kinds, tokens, dict(hp_items), q)
    return loss, grads, picks, load


def value_and_grad_layers(outer, layers, kinds, tokens, hp, q=None):
    """(loss, (d outer, [d layer]), picks, load) of the UNSTACKED
    parameters: the form that fits at the cell's size (the gradient of a
    slice of a stack is a whole stack of zeros around it). The selection
    bias's gradient is 0."""
    return _value_and_grad_layers(outer, layers, kinds, tokens,
                                  tuple(sorted(hp.items())), q)


def value_and_grad(params, tokens, hp, q=None):
    """The same on the stacked tree: (loss, gradients, picks, load)."""
    outer, layers, kinds = unstack(params)
    loss, (d_outer, d_layers), picks, load = value_and_grad_layers(
        outer, layers, kinds, tokens, hp, q)
    return loss, restack(d_outer, d_layers, kinds), picks, load


# --- Adam as the trainer's optimizer is configured, and the bias update -----------

def adam_init(params):
    """Zero moments for any tree of parameters (stacked or unstacked)."""
    return {"m": jax.tree_util.tree_map(jnp.zeros_like, params),
            "v": jax.tree_util.tree_map(jnp.zeros_like, params), "t": 0}


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adam_apply(params, grads, m, v, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)

    def upd(p, a, b):
        return p - lr * (a / (1 - b1 ** t)) / (jnp.sqrt(b / (1 - b2 ** t)) + eps)

    return jax.tree_util.tree_map(upd, params, m, v), m, v


def _moved_bias(mlp, load, hp):
    bias = mlp["bias"] + hp["bias_rate"] * jnp.sign(
        jnp.mean(load, axis=-1, keepdims=True) - load)
    return {**mlp, "bias": bias}


def train_step_layers(outer, layers, kinds, opt, grads, load, hp):
    """Adam on the unstacked tree, then `b_e += bias_rate * sign(mean load
    - load_e)` in each MoE layer. The parameters and the moments are
    consumed. Returns ((outer, layers), opt)."""
    t = opt["t"] + 1
    (outer, layers), m, v = _adam_apply((outer, layers), grads, opt["m"],
                                        opt["v"], t, hp["lr"])
    moe_at = [i for i, is_moe in enumerate(kinds) if is_moe]
    for row, i in enumerate(moe_at):
        layers[i] = {**layers[i], "mlp": _moved_bias(layers[i]["mlp"], load[row], hp)}
    return (outer, layers), {"m": m, "v": v, "t": t}


def train_step(params, opt, grads, load, hp):
    """The same on the stacked tree."""
    t = opt["t"] + 1
    params, m, v = _adam_apply(params, grads, opt["m"], opt["v"], t, hp["lr"])
    if load is not None:
        params = {**params, "moe": {**params["moe"], "mlp": _moved_bias(
            params["moe"]["mlp"], load, hp)}}
    return params, {"m": m, "v": v, "t": t}
