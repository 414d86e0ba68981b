"""Plain reference of the `ouro` decoder's training step (Ouro, a looped
language model): forward through every pass, the loss weighted by the exit
distribution, gradients and Adam.

Straightforward `jax.numpy` in float32 with every contraction at
`Precision.HIGHEST`; no kernels, no streaming softmax, no scan over layers
or passes (a Python loop over the T passes, and in each a Python loop over
the unstacked layers, each under checkpoint; the backward through the
passes is the chain rule written out, a pass's vjp at a time, so that each
program holds one pass: `value_and_grad_layers`), no compute-dtype casts. It
imports nothing of the program; from `decoder_lm.py` it takes only what is
not MLA's (`mm`, `rms_norm`, `rope`, `blocked`, `swiglu`,
`causal_attention`, the unstacking, Adam). The equations are the published
config.json's keys (`model_type: ouro`) read with the Ouro paper (Zhu et
al., arXiv:2510.25741). d the hidden size, h query and h key heads of dh
lanes, T = `total_ut_steps`:

  layer   a = Attn(RMSNorm_in(h)); h = h + RMSNorm_in2(a);
          m = SwiGLU(RMSNorm_post(h)); h = h + RMSNorm_post2(m)
  Attn    q, k, v = x W_q, x W_k, x W_v; RoPE (interleaved pairs) over all
          dh lanes at theta; softmax(q k^T / sqrt(dh)) under the causal
          mask, an explicit boolean on the materialised logits of a block
          of queries at a time; W_o. SwiGLU(x) = (silu(x W_gate) * x W_up)
          W_down, a block of rows at a time
  passes  h(0) = Embed(x); h(t) = RMSNorm_f(Layers(h(t-1))), t = 1..T, the
          same layers every pass; logits(t) = h(t) W_head a block of rows
          at a time; lambda_t = sigmoid(h(t) w_g + b_g)
  exit    p(t) = lambda_t prod_{j<t} (1 - lambda_j), t < T;
          p(T) = prod_{j<T} (1 - lambda_j)
  loss    CE_t(i) = logsumexp(logits(t)_i) - logits(t)_i[y_i];
          mean over the L - 1 targets of each sequence, then over
          sequences, of sum_t p(t|i) CE_t(i) - beta H(p(.|i)),
          H(p) = -sum_t p(t) log p(t)

Departures from the published description, each argued in the
configuration file's `assumed` (benchmarks/configs/ouro_2p6b_l5.json): the
sandwich norms, the gate's input and bias come from the paper and the
family's modelling code, not from a key of config.json; beta has no key
(0.1 is chosen); the paper's stage-II gate training and early exit are
left out; interleaved RoPE pairs (a fixed permutation of lanes against
`transformers`' rotate-half); no cross-document mask; `layers` of the
published depth are built (one pipeline stage's).

`hp`: heads, kv_heads, dh, eps, theta, passes (T), beta, lr, and the
blocks (`attn_block` queries, `ff_block` rows of a feed-forward,
`loss_block` rows of logits; 0 = whole).

`q` is the operand rounding of the control (`lowprec.py`), applied to
both operands of every contraction. `None` is the reference itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference.decoder_lm import (  # noqa: F401  (the kind reads them here)
    _adam_apply, adam_init, blocked, causal_attention, mm, restack, rms_norm, rope,
    stacked_norms, swiglu, unstack)


def attention(p, x, hp, q=None):
    B, L, _ = x.shape
    h, hk, dh = hp["heads"], hp["kv_heads"], hp["dh"]
    if h != hk:
        raise ValueError("the reference computes one key head a query head")
    qh = rope(mm(x, p["q"]["w"], q).reshape(B, L, h, dh), hp["theta"])
    k = rope(mm(x, p["k"]["w"], q).reshape(B, L, h, dh), hp["theta"])
    v = mm(x, p["v"]["w"], q).reshape(B, L, h, dh)
    out = causal_attention(qh, k, v, dh ** -0.5, hp["attn_block"], q)
    return mm(out.reshape(B, L, h * dh), p["o"]["w"], q)


def layer(lp, h, hp, q=None):
    """One sandwich layer: h (B, L, d) -> h."""
    eps = hp["eps"]
    a = attention(lp["attn"], rms_norm(lp["attn_norm"]["scale"], h, eps), hp, q)
    h = h + rms_norm(lp["attn_out_norm"]["scale"], a, eps)
    B, L, d = h.shape
    x = rms_norm(lp["mlp_norm"]["scale"], h, eps).reshape(B * L, d)
    m = swiglu(lp["mlp"], x, q, hp["ff_block"]).reshape(B, L, d)
    return h + rms_norm(lp["mlp_out_norm"]["scale"], m, eps)


def one_pass(final_scale, layers, h, hp, q=None):
    """The same layers in order, each under checkpoint, then the final
    norm: h(t-1) -> h(t)."""
    for lp in layers:
        h = jax.checkpoint(functools.partial(layer, hp=hp, q=q))(lp, h)
    return rms_norm(final_scale, h, hp["eps"])


def pass_states(outer, layers, tokens, hp, q=None):
    """[h(1), ..., h(T)]: the normed state of each pass is the next pass's
    input."""
    h = outer["embed"]["table"][tokens]
    states = []
    for _ in range(hp["passes"]):
        h = one_pass(outer["final_norm"]["scale"], layers, h, hp, q)
        states.append(h)
    return states


def exit_distribution(lam):
    """p (T, ...) from the gates' lambda (T, ...), as the equations write
    it: p(t) = lambda_t prod_{j<t} (1 - lambda_j) for t < T, p(T) =
    prod_{j<T} (1 - lambda_j)."""
    T = lam.shape[0]
    p, stayed = [], jnp.ones_like(lam[0])
    for t in range(T):
        p.append(stayed if t == T - 1 else lam[t] * stayed)
        stayed = stayed * (1.0 - lam[t])
    return jnp.stack(p)


def entropy(p):
    """H(p) = -sum_t p(t) log p(t) over the leading axis."""
    return -jnp.sum(p * jnp.log(p), axis=0)


def token_losses(hidden, head_w, targets, block, q=None):
    """CE of every row, `block` rows of logits at a time. hidden (N, d)."""
    def ce(h, t):
        logits = mm(h, head_w, q)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, t[:, None], -1)[:, 0]

    return blocked(ce, (hidden, targets), block)


def loss_of_states(top, states, tokens, hp, q=None):
    """The loss from the T passes' normed states, with `top` the head and
    the exit gate. Returns (loss, {"pass_loss": (T,) each pass's mean CE
    over the targets, "exit_step_mean": the mean over the targets of
    sum_t t p(t)})."""
    B, L = tokens.shape
    gate = top["exit_gate"]
    lam = jnp.stack([jax.nn.sigmoid(mm(h, gate["w"], q)[..., 0] + gate["b"][0])
                     for h in states])
    p = exit_distribution(lam).reshape(len(states), B * L)
    # every position gives a row; the last of each sequence has no target
    # and weighs nothing
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1).reshape(-1)
    mask = jnp.broadcast_to((jnp.arange(L) < L - 1).astype(jnp.float32), (B, L)).reshape(-1)
    ce = jnp.stack([token_losses(h.reshape(B * L, -1), top["head"]["w"], targets,
                                 hp["loss_block"], q) for h in states])
    per_token = jnp.sum(p * ce, axis=0) - hp["beta"] * entropy(p)
    n = B * (L - 1)
    steps = jnp.arange(1, len(states) + 1, dtype=jnp.float32)[:, None]
    aux = {"pass_loss": jnp.sum(mask * ce, axis=1) / n,
           "exit_step_mean": jnp.sum(mask * jnp.sum(steps * p, axis=0)) / n}
    return jnp.sum(mask * per_token) / n, aux


def loss_of_layers(outer, layers, kinds, tokens, hp, q=None):
    """`loss_of_states` of `pass_states`, as one function of the unstacked
    parameters (for the tests' autodiff)."""
    del kinds  # `unstack`'s: every layer is of one kind
    top = {"head": outer["head"], "exit_gate": outer["exit_gate"]}
    return loss_of_states(top, pass_states(outer, layers, tokens, hp, q), tokens, hp, q)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _pass(final_scale, layers, h, hp_items, q):
    return one_pass(final_scale, layers, h, dict(hp_items), q)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _pass_vjp(final_scale, layers, h, g, hp_items, q):
    _, back = jax.vjp(lambda s, ls, x: one_pass(s, ls, x, dict(hp_items), q),
                      final_scale, layers, h)
    return back(g)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _states_value_and_grad(top, states, tokens, hp_items, q):
    (loss, aux), grads = jax.value_and_grad(loss_of_states, argnums=(0, 1), has_aux=True)(
        top, states, tokens, dict(hp_items), q)
    return loss, grads, aux


@jax.jit
def _added(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


@jax.jit
def _embed_grad(table, tokens, g):
    return jnp.zeros_like(table).at[tokens.reshape(-1)].add(g.reshape(-1, g.shape[-1]))


def value_and_grad_layers(outer, layers, kinds, tokens, hp, q=None):
    """(loss, (d outer, [d layer]), aux) of the UNSTACKED parameters, as
    `decoder_lm.value_and_grad_layers` without picks or load: aux is
    `loss_of_states`'. The chain rule through the passes is written out,
    each piece its own program: the forward keeps every pass's input and
    output; the loss's gradient reaches each pass's output; then the passes
    from the last to the first, each pass's vjp taking the cotangent of its
    output (from the loss and from the next pass) and giving the layers'
    and the final norm's gradient (summed over the passes) and its input's.
    One program over all four passes plans 15.8 GB on a 16 GB chip at the
    cell's size; each piece here plans a pass's worth."""
    del kinds
    items = tuple(sorted(hp.items()))
    scale, table = outer["final_norm"]["scale"], outer["embed"]["table"]
    inputs, states = [table[tokens]], []
    for _ in range(hp["passes"]):
        states.append(_pass(scale, layers, inputs[-1], items, q))
        inputs.append(states[-1])
    top = {"head": outer["head"], "exit_gate": outer["exit_gate"]}
    loss, (d_top, d_states), aux = _states_value_and_grad(top, states, tokens, items, q)
    del states
    d_scale, d_layers, d_h = None, None, None
    for t in reversed(range(hp["passes"])):
        g = d_states[t] if d_h is None else _added(d_states[t], d_h)
        d_scale_t, d_layers_t, d_h = _pass_vjp(scale, layers, inputs[t], g, items, q)
        d_scale = d_scale_t if d_scale is None else _added(d_scale, d_scale_t)
        d_layers = d_layers_t if d_layers is None else _added(d_layers, d_layers_t)
    d_outer = {"embed": {"table": _embed_grad(table, tokens, d_h)},
               "final_norm": {"scale": d_scale}, **d_top}
    return loss, (d_outer, d_layers), aux


def value_and_grad(params, tokens, hp, q=None):
    """The same on the stacked tree: (loss, gradients, aux)."""
    outer, layers, kinds = unstack(params)
    loss, (d_outer, d_layers), aux = value_and_grad_layers(
        outer, layers, kinds, tokens, hp, q)
    return loss, restack(d_outer, d_layers, kinds), aux


def train_step_layers(outer, layers, kinds, opt, grads, hp):
    """Adam on the unstacked tree; nothing lies outside the gradient. The
    parameters and the moments are consumed. Returns ((outer, layers),
    opt)."""
    del kinds
    t = opt["t"] + 1
    (outer, layers), m, v = _adam_apply((outer, layers), grads, opt["m"],
                                        opt["v"], t, hp["lr"])
    return (outer, layers), {"m": m, "v": v, "t": t}


def train_step(params, opt, grads, hp):
    """The same on the stacked tree."""
    t = opt["t"] + 1
    params, m, v = _adam_apply(params, grads, opt["m"], opt["v"], t, hp["lr"])
    return params, {"m": m, "v": v, "t": t}


def logits(params, tokens, hp):
    """(T, B, L, V) logits of every pass of the stacked tree, for the tests."""
    outer, layers, _ = unstack(params)
    return jnp.stack([mm(h, outer["head"]["w"])
                      for h in pass_states(outer, layers, tokens, hp)])
