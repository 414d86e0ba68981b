"""Plain reference of the `mellum` decoder's training step (Mellum 2):
forward, next-token loss, gradients and Adam.

Straightforward `jax.numpy` in float32 with every contraction at
`Precision.HIGHEST`; no kernels, no streaming softmax, no band schedule,
no sorted dispatch, no scan over layers (a Python loop over the unstacked
layers in the published order, each with the kind `layer_types` gives it),
no compute-dtype casts. It imports nothing of the program; from
`decoder_lm.py` it takes only what is not MLA's (`mm`, `ein`, `rms_norm`,
`blocked`, `swiglu`, `cross_entropy`, the unstacking, Adam). The equations
are the published config.json's keys read as `transformers` reads them
(`model_type: mellum`; the key set is the Qwen3-MoE family's plus
`layer_types` and a `rope_parameters` section a layer kind). d the hidden
size, h query heads and hk key heads of dh lanes, g = h / hk:

  block   h += GQA_l(RMSNorm(h)); h += MoE(RMSNorm(h)) for every layer;
          final RMSNorm; logits = h W_head (untied)
  GQA     q = x W_q, k = x W_k, v = x W_v; RMSNorm over the dh lanes of
          each head of q and of k, a learned scale each that the heads
          share; RoPE (interleaved pairs) over all dh lanes by the layer
          kind's table; softmax(q k^T / sqrt(dh)) under the kind's mask,
          an explicit boolean on the materialised logits of a block of
          queries at a time, key head j serving query heads [j g,
          (j + 1) g) by an einsum over (key head, group); W_o
  kinds   `sliding_attention`: (i - window < j) & (j <= i), plain RoPE at
          the section's theta. `full_attention`: j <= i, YaRN: with
          f_i = theta^(-2i/dh), inv_freq_i = f_i / factor * (1 - m_i) +
          f_i * m_i, m_i = 1 - clip((i - low) / (high - low), 0, 1), low =
          floor(c(beta_fast)), high = ceil(c(beta_slow)), c(t) = dh
          ln(original_max / (2 pi t)) / (2 ln theta); cos and sin times
          `attention_factor`
  MoE     p = softmax(x W_r) over all the router's experts; the top_k
          largest; their weights divided by their sum; the layer gives
          sum_e w_e SwiGLU_e(x) over the picks that are HELD and nothing
          for the others: a dense loop over the held experts, every token
          through each. What the absent experts would add is left out, as
          in the program: the same share of the same deployment.

Departures from the published description, each argued in the
configuration file's `assumed` (benchmarks/configs/
mellum2_12b_a2p5b_ep4_l4.json): the per-head q / k norm and softmax before
top-k are taken from the Qwen3-MoE family, whose key set this is; no
multi-token-prediction head; interleaved RoPE pairs (a fixed permutation
of lanes against `transformers`' rotate-half); no cross-document mask; no
balancing mechanism of any kind.

`hp`: heads, kv_heads, dh, eps, top_k, norm_topk, held (lo, hi), lr,
layer_types (one kind a layer), window, rope ((kind, sorted items of its
section), ...), and the blocks (`attn_block` queries, `ff_block` tokens of
a feed-forward, `loss_block` rows of logits; 0 = whole).

`q` is the operand rounding of the control (`lowprec.py`), applied to
both operands of every contraction. `None` is the reference itself.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from reference.decoder_lm import (  # noqa: F401  (the kind reads them here)
    _adam_apply, adam_init, blocked, cross_entropy, ein, mm, restack, rms_norm,
    stacked_norms, swiglu, unstack)

SLIDING, FULL = "sliding_attention", "full_attention"


def inv_freq_of(section: dict, dh: int):
    """(dh / 2,) rotation frequencies of one `rope_parameters` section."""
    theta = float(section["rope_theta"])
    f = [theta ** (-2.0 * i / dh) for i in range(dh // 2)]
    if section.get("rope_type", "default") == "default":
        return jnp.asarray(f, jnp.float32)

    def c(turns):
        return dh * math.log(section["original_max_position_embeddings"]
                             / (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(c(section["beta_fast"])), 0)
    high = min(math.ceil(c(section["beta_slow"])), dh - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f_i in enumerate(f):
        m = 1.0 - min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f_i / section["factor"] * (1.0 - m) + f_i * m)
    return jnp.asarray(out, jnp.float32)


def turn(x, inv_freq, factor):
    """x (B, L, H, dh): pairs (x_2i, x_2i+1) turned by position *
    inv_freq[i], cos and sin times `factor`."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = factor * jnp.cos(ang)[None, :, None], factor * jnp.sin(ang)[None, :, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def grouped_attention(qh, k, v, scale, window, block, q=None):
    """softmax(qh k^T * scale) v under (i - window < j) & (j <= i) (window
    None: j <= i alone), key head j serving query heads [j g, (j + 1) g);
    the logits of `block` queries against every key stand at a time. qh:
    (B, L, h, dh); k, v: (B, L, hk, dh)."""
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
        seen = cols[None, :] <= rows[:, None]
        if window is not None:
            seen = seen & (rows[:, None] - window < cols[None, :])
        logits = jnp.where(seen, logits, -jnp.inf)
        return ein("bkgij,bjkd->bikgd", jax.nn.softmax(logits, axis=-1), v, q)

    blocks = qg.reshape(B, L // block, block, hk, h // hk, dh).swapaxes(0, 1)
    out = jax.lax.map(lambda a: one(*a), (blocks, jnp.arange(0, L, block)))
    return out.swapaxes(0, 1).reshape(B, L, h, dh)


def gqa(p, x, kind, hp, q=None):
    B, L, _ = x.shape
    h, hk, dh = hp["heads"], hp["kv_heads"], hp["dh"]
    section = dict(dict(hp["rope"])[kind])
    inv_freq, factor = inv_freq_of(section, dh), section.get("attention_factor", 1.0)
    qh = mm(x, p["q"]["w"], q).reshape(B, L, h, dh)
    k = mm(x, p["k"]["w"], q).reshape(B, L, hk, dh)
    v = mm(x, p["v"]["w"], q).reshape(B, L, hk, dh)
    qh = turn(rms_norm(p["q_norm"]["scale"], qh, hp["eps"]), inv_freq, factor)
    k = turn(rms_norm(p["k_norm"]["scale"], k, hp["eps"]), inv_freq, factor)
    window = hp["window"] if kind == SLIDING else None
    out = grouped_attention(qh, k, v, dh ** -0.5, window, hp["attn_block"], q)
    return mm(out.reshape(B, L, h * dh), p["o"]["w"], q)


def router(p, x, hp, q=None):
    """(picks (N, top_k), weights (N, top_k), load (E,))."""
    prob = jax.nn.softmax(mm(x, p["w"], q), axis=-1)
    w, idx = jax.lax.top_k(prob, hp["top_k"])
    if hp["norm_topk"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    load = jnp.sum(jax.nn.one_hot(idx, prob.shape[-1], dtype=jnp.float32), axis=(0, 1))
    return idx, w, jax.lax.stop_gradient(load)


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


def layer(lp, h, kind, hp, q=None):
    """One layer of `kind`: h (B, L, d) -> (h, (picks, load))."""
    h = h + gqa(lp["attn"], rms_norm(lp["attn_norm"]["scale"], h, hp["eps"]),
                kind, hp, q)
    B, L, d = h.shape
    x = rms_norm(lp["mlp_norm"]["scale"], h, hp["eps"]).reshape(B * L, d)
    idx, w, load = router(lp["mlp"]["router"], x, hp, q)
    y = experts(lp["mlp"]["experts"], x, idx, w, hp, q)
    return h + y.reshape(B, L, d), (idx, load)


def hidden_of_layers(outer, layers, tokens, hp, q=None):
    """(hidden (B, L, d) after the final norm, picks (n, N, top_k), load
    (n, E)): the Python loop over the layers in the published order, each
    under checkpoint."""
    if len(layers) != len(hp["layer_types"]):
        raise ValueError(f"{len(layers)} layers for layer_types {hp['layer_types']}")
    h = outer["embed"]["table"][tokens]
    picks, loads = [], []
    for lp, kind in zip(layers, hp["layer_types"]):
        h, (idx, load) = jax.checkpoint(
            functools.partial(layer, kind=kind, hp=hp, q=q))(lp, h)
        picks.append(idx)
        loads.append(load)
    h = rms_norm(outer["final_norm"]["scale"], h, hp["eps"])
    return h, jnp.stack(picks), jnp.stack(loads)


def loss_of_layers(outer, layers, kinds, tokens, hp, q=None):
    """Mean over the L - 1 targets of each sequence, then over sequences.
    Returns (loss, (picks (n, N, top_k), load (n, E)))."""
    del kinds  # `unstack`'s: every layer has the mixture; hp has the kinds
    B, L = tokens.shape
    h, picks, loads = hidden_of_layers(outer, layers, tokens, hp, q)
    # every position gives a row, so that the rows divide into blocks; the
    # last of each sequence has no target and weighs nothing
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    weights = jnp.broadcast_to((jnp.arange(L) < L - 1).astype(jnp.float32), (B, L))
    total = cross_entropy(h.reshape(B * L, -1), outer["head"]["w"],
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
    parameters, as `decoder_lm.value_and_grad_layers`."""
    return _value_and_grad_layers(outer, layers, kinds, tokens,
                                  tuple(sorted(hp.items())), q)


def value_and_grad(params, tokens, hp, q=None):
    """The same on the stacked tree: (loss, gradients, picks, load)."""
    outer, layers, kinds = unstack(params)
    loss, (d_outer, d_layers), picks, load = value_and_grad_layers(
        outer, layers, kinds, tokens, hp, q)
    return loss, restack(d_outer, d_layers, kinds), picks, load


def train_step_layers(outer, layers, kinds, opt, grads, load, hp):
    """Adam on the unstacked tree; the router has no bias to move, so the
    load moves nothing. The parameters and the moments are consumed.
    Returns ((outer, layers), opt)."""
    del kinds, load
    t = opt["t"] + 1
    (outer, layers), m, v = _adam_apply((outer, layers), grads, opt["m"],
                                        opt["v"], t, hp["lr"])
    return (outer, layers), {"m": m, "v": v, "t": t}


def train_step(params, opt, grads, load, hp):
    """The same on the stacked tree."""
    del load
    t = opt["t"] + 1
    params, m, v = _adam_apply(params, grads, opt["m"], opt["v"], t, hp["lr"])
    return params, {"m": m, "v": v, "t": t}


def logits(params, tokens, hp):
    """(B, L, V) logits of the stacked tree, for the tests."""
    outer, layers, _ = unstack(params)
    h, _, _ = hidden_of_layers(outer, layers, tokens, hp)
    return mm(h, outer["head"]["w"])
