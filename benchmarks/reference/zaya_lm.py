"""Plain reference of the `zaya` decoder's training step (ZAYA1): forward,
next-token loss, gradients, Adam and the router's bias update.

Straightforward `jax.numpy` in float32 with every contraction at
`Precision.HIGHEST`; no kernels, no streaming softmax, no sorted
dispatch, no scan over layers (a Python loop over the unstacked layers,
the router's state handed from one to the next by hand), no compute-dtype
casts. It imports nothing of the program; from `decoder_lm.py` it takes
only what is not MLA's (`mm`, `ein`, `rms_norm`, `rope`, `blocked`,
`swiglu`, `cross_entropy`, the unstacking, Adam and the bias move). The
equations are the CCA paper's (Figliolia et al., arXiv:2510.04476, the
CCGQA form) and the ZAYA1 report's (Anthony et al., arXiv:2511.17127). d
the hidden size, h query heads and hk key heads of dh lanes, g = h / hk,
x_t a sublayer's RMSNorm'd input; every convolution pads on the left
only:

  block   for each sublayer f (CCA, then experts) with its own RMSNorm:
          h = (a * h + c) + f(RMSNorm(h)); final RMSNorm; logits = h E^T
          with E the embedding table (tied)
  CCA     q0 = x W_q, k0 = x W_k; [qc | kc] = Conv_b(Conv_a([q0 | k0])):
          Conv_a depthwise, Conv_b grouped by head (dh -> dh), both with
          bias, tap j of T reading position t - (T - 1) + j;
          q = qc + (q0 + repeat_g(k0)) / 2, k = kc + (mean_g(q0) + k0) / 2;
          q = sqrt(dh) q / |q|, k = sqrt(dh) tau k / |k| per head;
          v = [x_t W_v1 | x_(t-1) W_v2], x_(-1) = 0: the first half of
          the key heads hold this token's values, the second half the
          previous token's; RoPE (interleaved pairs) on the first `rot`
          lanes of each head of q and k; softmax(q k^T / sqrt(dh)) under
          the causal mask, key head j serving query heads [j g, (j + 1) g),
          materialised for a block of queries at a time; W_o
  router  r_l = RMSNorm(x) W_down; r_l += gamma_l * r_(l-1), r_(-1) = 0;
          s = W_3 gelu(W_2 gelu(W_1 r_l)) with biases (tanh gelu);
          p = softmax(s); the pick is argmax(p + b); the sublayer gives
          p[pick] SwiGLU_pick(x) where the pick is HELD and nothing
          otherwise: a dense loop over the held experts, every token
          through each. What the absent experts would add is left out, as
          in the program: the same share of the same deployment.

Departures from the sources, and what they do not fix, are argued in the
configuration file's `assumed` (benchmarks/configs/zaya1_8b_ep2_l5.json).

`hp`: heads, kv_heads, dh, rot, eps, theta, top_k, held (lo, hi), lr,
bias_rate, and the blocks (`attn_block` queries, `ff_block` tokens of a
feed-forward, `loss_block` rows of logits; 0 = whole).

`q` is the operand rounding of the control (`lowprec.py`), applied to
both operands of every contraction and convolution. `None` is the
reference itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference.decoder_lm import (  # noqa: F401  (the kind reads them here)
    _q, adam_init, blocked, cross_entropy, ein, mm, restack, rms_norm, rope,
    stacked_norms, swiglu, train_step, train_step_layers, unstack)


def conv_taps(x, w, b, product):
    """sum_j product(x at t - (T - 1) + j, w[j]) + b over the sequence
    axis 1, zeros before position 0. x: (B, L, H, dh); w: (T, ...)."""
    taps, length = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0), (0, 0)))
    y = sum(product(padded[:, j:j + length], w[j]) for j in range(taps))
    return y + b.reshape(x.shape[2:])


def grouped_causal_attention(qh, k, v, scale, block, q=None):
    """softmax(qh k^T * scale) v under the causal mask, key head j serving
    query heads [j g, (j + 1) g); the logits of `block` queries against
    every key stand at a time. qh: (B, L, h, dh); k, v: (B, L, hk, dh)."""
    B, L, h, dh = qh.shape
    hk = k.shape[2]
    qg = qh.reshape(B, L, hk, h // hk, dh)
    block = L if not block or block > L else block
    while L % block:
        block -= 1
    cols = jnp.arange(L)

    @jax.checkpoint
    def one(q_blk, row0):
        logits = ein("bikgd,bjkd->bkgij", q_blk, k, q) * scale
        rows = row0 + jnp.arange(block)
        logits = jnp.where(cols[None, :] <= rows[:, None], logits, -jnp.inf)
        return ein("bkgij,bjkd->bikgd", jax.nn.softmax(logits, axis=-1), v, q)

    blocks = qg.reshape(B, L // block, block, hk, h // hk, dh).swapaxes(0, 1)
    out = jax.lax.map(lambda a: one(*a), (blocks, jnp.arange(0, L, block)))
    return out.swapaxes(0, 1).reshape(B, L, h, dh)


def cca(p, x, hp, q=None):
    B, L, _ = x.shape
    h, hk, dh, rot = hp["heads"], hp["kv_heads"], hp["dh"], hp["rot"]
    g = h // hk
    q0 = mm(x, p["q"]["w"], q).reshape(B, L, h, dh)
    k0 = mm(x, p["k"]["w"], q).reshape(B, L, hk, dh)
    qk = jnp.concatenate([q0, k0], axis=2)
    qk = conv_taps(qk, p["conv_a"]["w"], p["conv_a"]["b"],
                   lambda t, w: _q(q, t) * _q(q, w.reshape(h + hk, dh)))
    qk = conv_taps(qk, p["conv_b"]["w"], p["conv_b"]["b"],
                   lambda t, w: ein("blhc,hcd->blhd", t, w, q))
    qh = qk[:, :, :h] + (q0 + jnp.repeat(k0, g, axis=2)) / 2
    k = qk[:, :, h:] + (jnp.mean(q0.reshape(B, L, hk, g, dh), axis=3) + k0) / 2

    def unit(t):
        return t * dh ** 0.5 / jnp.linalg.norm(t, axis=-1, keepdims=True)

    def turned(t):
        return jnp.concatenate([rope(t[..., :rot], hp["theta"]), t[..., rot:]], -1)

    qh = turned(unit(qh))
    k = turned(unit(k) * p["tau"][:, None])
    v_now = mm(x, p["v1"]["w"], q)
    v_before = mm(x, p["v2"]["w"], q)
    v_before = jnp.concatenate([jnp.zeros_like(v_before[:, :1]), v_before[:, :-1]], 1)
    v = jnp.concatenate([v_now, v_before], axis=-1).reshape(B, L, hk, dh)
    out = grouped_causal_attention(qh, k, v, dh ** -0.5, hp["attn_block"], q)
    return mm(out.reshape(B, L, h * dh), p["o"]["w"], q)


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def router(p, bias, x, r_before, hp, q=None):
    """(picks (N, top_k), weights (N, top_k), load (E,), state (N, R))."""
    r = mm(rms_norm(p["norm"]["scale"], x, hp["eps"]), p["reduce"]["w"], q)
    r = r + p["gamma"] * r_before
    t = gelu(mm(r, p["fc1"]["w"], q) + p["fc1"]["b"])
    t = gelu(mm(t, p["fc2"]["w"], q) + p["fc2"]["b"])
    prob = jax.nn.softmax(mm(t, p["fc3"]["w"], q) + p["fc3"]["b"], axis=-1)
    _, idx = jax.lax.top_k(prob + jax.lax.stop_gradient(bias), hp["top_k"])
    w = jnp.take_along_axis(prob, idx, axis=-1)
    load = jnp.sum(jax.nn.one_hot(idx, prob.shape[-1], dtype=jnp.float32), axis=(0, 1))
    return idx, w, jax.lax.stop_gradient(load), r


def experts(p, x, idx, w, hp, q=None):
    """sum over the experts HELD of w_e SwiGLU_e(x), a dense loop over
    them with every token through each. x: (N, d)."""
    lo, hi = hp["held"]

    def routed(x_blk, idx_blk, w_blk):
        def one(y, e_and_params):
            e, pe = e_and_params
            # the expert's weight for each token: 0 where it was not picked
            w_e = jnp.sum(jnp.where(idx_blk == e, w_blk, 0.0), axis=-1)
            return y + w_e[:, None] * swiglu(pe, x_blk, q), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x_blk), (jnp.arange(lo, hi), p))
        return y

    return blocked(routed, (x, idx, w), hp["ff_block"])


def layer(lp, h, r, hp, q=None):
    """One layer: (h (B, L, d), r (B L, R)) -> (h, r, (picks, load))."""
    y = cca(lp["attn"], rms_norm(lp["attn_norm"]["scale"], h, hp["eps"]), hp, q)
    h = lp["attn_res"]["a"] * h + lp["attn_res"]["c"] + y
    B, L, d = h.shape
    x = rms_norm(lp["mlp_norm"]["scale"], h, hp["eps"]).reshape(B * L, d)
    idx, w, load, r = router(lp["mlp"]["router"], lp["mlp"]["bias"], x, r, hp, q)
    y = experts(lp["mlp"]["experts"], x, idx, w, hp, q)
    h = lp["mlp_res"]["a"] * h + lp["mlp_res"]["c"] + y.reshape(B, L, d)
    return h, r, (idx, load)


def hidden_of_layers(outer, layers, tokens, hp, q=None):
    """(hidden (B, L, d) after the final norm, picks (n, N, top_k), load
    (n, E)): the Python loop over the layers, each under checkpoint, the
    router's state handed on by hand."""
    B, L = tokens.shape
    h = outer["embed"]["table"][tokens]
    r = jnp.zeros((B * L, layers[0]["mlp"]["router"]["gamma"].shape[-1]), jnp.float32)
    picks, loads = [], []
    for lp in layers:
        h, r, (idx, load) = jax.checkpoint(
            functools.partial(layer, hp=hp, q=q))(lp, h, r)
        picks.append(idx)
        loads.append(load)
    h = rms_norm(outer["final_norm"]["scale"], h, hp["eps"])
    return h, jnp.stack(picks), jnp.stack(loads)


def loss_of_layers(outer, layers, kinds, tokens, hp, q=None):
    """Mean over the L - 1 targets of each sequence, then over sequences.
    Returns (loss, (picks (n, N, top_k), load (n, E)))."""
    del kinds  # every layer is of one kind
    B, L = tokens.shape
    h, picks, loads = hidden_of_layers(outer, layers, tokens, hp, q)
    # every position gives a row, so that the rows divide into blocks; the
    # last of each sequence has no target and weighs nothing
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    weights = jnp.broadcast_to((jnp.arange(L) < L - 1).astype(jnp.float32), (B, L))
    total = cross_entropy(h.reshape(B * L, -1), outer["embed"]["table"].T,
                          targets.reshape(-1), weights.reshape(-1),
                          hp["loss_block"], q)
    return total / (B * (L - 1)), (picks, loads)


@functools.partial(jax.jit, static_argnums=(2, 4, 5))
def _value_and_grad_layers(outer, layers, kinds, tokens, hp_items, q):
    (loss, (picks, load)), grads = jax.value_and_grad(
        loss_of_layers, argnums=(0, 1), has_aux=True)(
            outer, layers, kinds, tokens, dict(hp_items), q)
    return loss, grads, picks, load


def value_and_grad_layers(outer, layers, kinds, tokens, hp, q=None):
    """(loss, (d outer, [d layer]), picks, load) of the UNSTACKED
    parameters, as `decoder_lm.value_and_grad_layers`. The table's
    gradient is the sum of the lookup's and the head's."""
    return _value_and_grad_layers(outer, layers, kinds, tokens,
                                  tuple(sorted(hp.items())), q)


def value_and_grad(params, tokens, hp, q=None):
    """The same on the stacked tree: (loss, gradients, picks, load)."""
    outer, layers, kinds = unstack(params)
    loss, (d_outer, d_layers), picks, load = value_and_grad_layers(
        outer, layers, kinds, tokens, hp, q)
    return loss, restack(d_outer, d_layers, kinds), picks, load


def logits(params, tokens, hp):
    """(B, L, V) logits of the stacked tree, for the tests."""
    outer, layers, _ = unstack(params)
    h, _, _ = hidden_of_layers(outer, layers, tokens, hp)
    return mm(h, outer["embed"]["table"].T)
