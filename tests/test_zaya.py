"""The `zaya` decoder (models/decoder.py `ZayaConfig`: compressed
convolutional attention with grouped keys, the MLP router's carried
state, the scaled residual stream, the tied head) against its plain
reference (benchmarks/reference/zaya_lm.py) at a small size on the CPU,
and the pieces it forced: grouped keys through the causal core, the
caller's routing through `moe_apply`, a chip's share of the experts."""
import dataclasses
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.models import decoder
from alphafold2_tpu.models.decoder import ZayaConfig, decoder_apply, decoder_init
from alphafold2_tpu.ops import moe
from alphafold2_tpu.ops.flash import flash_attention
from alphafold2_tpu.training.harness import (TrainConfig, make_optimizer,
                                             make_train_step)
from alphafold2_tpu.training.lm import (lm_aux_update, lm_loss_fn,
                                        zipf_token_batches)

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
reference = importlib.import_module("reference.zaya_lm")

CFG = ZayaConfig(
    vocab_size=256, hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, moe_intermediate_size=32, num_experts=8,
    router_hidden_size=16, rope_theta=5e6, dtype="float32")


def _hp(cfg, **over):
    hp = {"heads": cfg.num_attention_heads, "kv_heads": cfg.num_key_value_heads,
          "dh": cfg.head_dim, "rot": cfg.rotary_dim, "eps": cfg.rms_norm_eps,
          "theta": cfg.rope_theta, "top_k": cfg.num_experts_per_tok,
          "held": cfg.held, "lr": 3e-4, "bias_rate": cfg.bias_update_rate,
          "attn_block": 16, "ff_block": 32, "loss_block": 64}
    return dict(hp, **over)


def _tokens(seed=5, batch=2, length=64):
    return next(zipf_token_batches(CFG.vocab_size, batch, length, seed))["tokens"]


#: the leaves whose init is a constant: drawn away from it here, so that a
#: path that ignored one of them could not pass
_CONSTANT = {"a": (1.0, 0.02), "c": (0.0, 0.02), "tau": (1.0, 0.1),
             "gamma": (1.0, 0.1), "b": (0.0, 0.02), "bias": (0.0, 0.01)}


def _drawn_away(params, key):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        role = str(path[-1].key)
        if role in _CONSTANT:
            mean, std = _CONSTANT[role]
            leaf = mean + std * jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
        elif str(path[-2].key) in ("fc1", "fc2", "fc3"):
            # 1 / sqrt(16) at this toy width: scores far enough apart that
            # the program's picks and the reference's are no coin tosses
            leaf = leaf * (0.25 / decoder.ROUTER_MLP_STD)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope="module")
def params():
    return _drawn_away(decoder_init(jax.random.PRNGKey(0), CFG), jax.random.PRNGKey(9))


def _share(params, held):
    lo, hi = held
    experts = jax.tree_util.tree_map(lambda t: t[:, lo:hi],
                                     params["moe"]["mlp"]["experts"])
    return {**params, "moe": {**params["moe"], "mlp": {
        **params["moe"]["mlp"], "experts": experts}}}


def _worst(a, b):
    gaps = jax.tree_util.tree_map(
        lambda x, y: float(jnp.max(jnp.abs(x - y)) / (jnp.max(jnp.abs(y)) + 1e-12)), a, b)
    return max(jax.tree_util.tree_leaves(gaps))


@pytest.mark.parametrize("held", [None, (2, 6)], ids=["all_experts", "share_2_6"])
def test_loss_logits_and_every_gradient_leaf_match_reference(params, held):
    cfg = dataclasses.replace(CFG, experts_held=held)
    p = _share(params, held) if held else params
    tokens = _tokens()
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda q: lm_loss_fn(q, cfg, {"tokens": tokens}), has_aux=True))(p)
    want, want_grads, picks, load = reference.value_and_grad(p, tokens, _hp(cfg))
    assert abs(float(loss) - float(want)) < 2e-5 * float(want)
    assert (jax.tree_util.tree_structure(grads)
            == jax.tree_util.tree_structure(want_grads))
    assert _worst(grads, want_grads) < 2e-3
    assert float(jnp.max(jnp.abs(grads["moe"]["mlp"]["bias"]))) == 0.0
    assert set(aux) == {"load", "rows_walked"}
    np.testing.assert_array_equal(aux["load"], load)
    hidden, full = decoder_apply(p, cfg, tokens)
    np.testing.assert_array_equal(full["picks"], picks)
    got = jnp.einsum("bld,vd->blv", hidden, p["embed"]["table"],
                     precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(got, reference.logits(p, tokens, _hp(cfg)),
                               atol=2e-5, rtol=2e-4)


def test_two_train_steps_follow_reference(params):
    tcfg = TrainConfig(grad_accum=1)
    step = jax.jit(make_train_step(CFG, tcfg, loss_fn=lm_loss_fn,
                                   aux_update=lm_aux_update(CFG)))
    state = {"params": params, "opt_state": make_optimizer(tcfg).init(params),
             "step": jnp.zeros((), jnp.int32)}
    ref_p = jax.tree_util.tree_map(jnp.copy, params)
    opt, hp = reference.adam_init(ref_p), _hp(CFG)
    for i in range(2):
        tokens = _tokens(seed=7 + i)
        state, metrics = step(state, {"tokens": tokens[None]}, None)
        want, grads, _, load = reference.value_and_grad(ref_p, tokens, hp)
        ref_p, opt = reference.train_step(ref_p, opt, grads, load, hp)
        assert abs(float(metrics["loss"]) - float(want)) < 1e-4 * float(want)
        np.testing.assert_allclose(metrics["moe_assignments_held"],
                                   np.asarray(load).sum(-1))
    moved = jax.tree_util.tree_map(lambda a, b: a - b, state["params"], params)
    want_moved = jax.tree_util.tree_map(lambda a, b: a - b, ref_p, params)
    assert _worst(moved, want_moved) < 0.05
    np.testing.assert_allclose(state["params"]["moe"]["mlp"]["bias"],
                               ref_p["moe"]["mlp"]["bias"], atol=1e-7)


def test_the_two_shares_add_up_to_the_uncut_expert_sublayer(params):
    """Experts [0, 4) and [4, 8) of one sublayer, each as a chip of the
    deployment computes its part, add up to what the reference gives with
    all 8: there is no shared expert to count once."""
    lp = jax.tree_util.tree_map(lambda t: t[1], params["moe"]["mlp"])
    x = jax.random.normal(jax.random.PRNGKey(3), (96, CFG.hidden_size))
    r = jax.random.normal(jax.random.PRNGKey(4), (96, CFG.router_hidden_size))
    logits, _ = decoder.zaya_router_logits(lp["router"], x, r, CFG)
    routing = moe.route_softmax(logits, lp["bias"], 1)
    parts = []
    for lo, hi in ((0, 4), (4, 8)):
        mine = {"experts": jax.tree_util.tree_map(lambda t: t[lo:hi], lp["experts"])}
        y, aux = moe.moe_apply(mine, x, routing, held=(lo, hi))
        parts.append(y)
    hp = _hp(CFG, held=(0, 8), ff_block=0)
    idx, w, load, _ = reference.router(lp["router"], lp["bias"], x, r, hp)
    whole = reference.experts(lp["experts"], x, idx, w, hp)
    np.testing.assert_array_equal(aux["picks"], idx)
    np.testing.assert_array_equal(aux["load"], load)
    assert float(jnp.max(jnp.abs(parts[0]))) > 0 and float(jnp.max(jnp.abs(parts[1]))) > 0
    np.testing.assert_allclose(parts[0] + parts[1], whole, atol=2e-6, rtol=1e-4)
    # a token whose expert is absent gets nothing from this chip
    absent = np.asarray(idx[:, 0]) >= 4
    assert absent.any() and float(jnp.max(jnp.abs(parts[0][absent]))) == 0.0


@pytest.mark.parametrize("t", [0, 17, 40])
def test_the_mixer_sees_no_later_token(params, t):
    """Changing x at position t leaves every output before t unchanged,
    and of position t's own values only the first half of the key heads
    move: the second half hold the PREVIOUS token's values."""
    lp = jax.tree_util.tree_map(lambda a: a[0], params["moe"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 48, CFG.hidden_size))
    x2 = x.at[0, t].add(jax.random.normal(jax.random.PRNGKey(2), (CFG.hidden_size,)))
    y, y2 = decoder.cca_apply(lp, x, CFG), decoder.cca_apply(lp, x2, CFG)
    np.testing.assert_array_equal(y[0, :t], y2[0, :t])
    assert float(jnp.max(jnp.abs(y[0, t:] - y2[0, t:]))) > 1e-4

    def values(inp):
        half = CFG.num_key_value_heads * CFG.head_dim // 2
        v_prev = decoder._shift(inp @ lp["v2"]["w"], 1)
        return (inp @ lp["v1"]["w"])[0], v_prev[0], half

    (now, before, _), (now2, before2, _) = values(x), values(x2)
    assert float(jnp.max(jnp.abs(now[t] - now2[t]))) > 1e-4
    np.testing.assert_array_equal(before[t], before2[t])
    if t + 1 < x.shape[1]:
        assert float(jnp.max(jnp.abs(before[t + 1] - before2[t + 1]))) > 1e-4


def test_carried_router_state_under_scan_and_checkpoint_equals_the_loop(params):
    """The scan carries (h, r) under the layers' checkpoint; an unrolled
    Python loop over the same layers with r handed on by hand gives the
    same hidden state, picks and gradients."""
    tokens = _tokens(seed=11, length=32)

    def unrolled(p):
        h = p["embed"]["table"][tokens]
        r = jnp.zeros((tokens.size, CFG.router_hidden_size), jnp.float32)
        picks = []
        for i in range(CFG.num_hidden_layers):
            lp = jax.tree_util.tree_map(lambda t: t[i], p["moe"])
            (h, r), aux = decoder._zaya_layer(lp, (h, r), CFG)
            picks.append(aux["picks"])
        return decoder.rms_norm(p["final_norm"], h, CFG.rms_norm_eps), jnp.stack(picks)

    def scanned(p):
        h, aux = decoder_apply(p, CFG, tokens)
        return h, aux["picks"]

    (h_loop, picks_loop), (h_scan, picks_scan) = unrolled(params), scanned(params)
    np.testing.assert_array_equal(picks_loop, picks_scan)
    np.testing.assert_allclose(h_loop, h_scan, atol=1e-5, rtol=1e-5)
    g_loop = jax.grad(lambda p: jnp.sum(jnp.square(unrolled(p)[0])))(params)
    g_scan = jax.grad(lambda p: jnp.sum(jnp.square(scanned(p)[0])))(params)
    assert _worst(g_scan, g_loop) < 1e-4
    # the state matters: with gamma at 0 the later layers pick otherwise
    dead = {**params, "moe": {**params["moe"], "mlp": {**params["moe"]["mlp"], "router": {
        **params["moe"]["mlp"]["router"],
        "gamma": jnp.zeros_like(params["moe"]["mlp"]["router"]["gamma"])}}}}
    assert (np.asarray(scanned(dead)[1][1:]) != np.asarray(picks_scan[1:])).any()
    np.testing.assert_array_equal(scanned(dead)[1][0], picks_scan[0])


def test_tied_table_gradient_is_the_lookups_plus_the_heads(params):
    """With the table split into a lookup copy and a head copy, the tied
    loss's gradient for the one table is the sum of the two."""
    tokens = _tokens(seed=13, length=32)
    batch = {"tokens": tokens}
    tied = jax.grad(lambda p: lm_loss_fn(p, CFG, batch)[0])(params)["embed"]["table"]

    def split_loss(lookup, head):
        p = {**params, "embed": {"table": lookup}, "head": {"w": head.T}}
        return lm_loss_fn(p, CFG, batch)[0]

    table = params["embed"]["table"]
    g_lookup, g_head = jax.grad(split_loss, argnums=(0, 1))(table, table)
    assert float(jnp.max(jnp.abs(g_lookup))) > 0 and float(jnp.max(jnp.abs(g_head))) > 0
    np.testing.assert_allclose(tied, g_lookup + g_head, atol=1e-7, rtol=1e-4)


@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla_arm", "kernel_interpret"])
def test_grouped_keys_through_the_causal_core(use_kernel):
    """8 query heads over 2 key heads against the repeated-key form,
    forward and backward: dk and dv add up over a group's query heads."""
    B, n, h, hk, dh = 2, 256, 8, 2, 128
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (B, n, h, dh))
    k = jax.random.normal(kk, (B, n, hk, dh))
    v = jax.random.normal(kv, (B, n, hk, dh))
    g = jax.random.normal(kg, (B, n, h, dh))
    kw = dict(causal=True, scale=dh ** -0.5, use_kernel=use_kernel)
    if use_kernel:
        kw.update(kernel_qb=128, kernel_kb=64)

    def grouped(q, k, v):
        return flash_attention(q, k, v, **kw)

    def repeated(q, k, v):
        return flash_attention(q, jnp.repeat(k, h // hk, 2), jnp.repeat(v, h // hk, 2), **kw)

    def dense(q, k, v):
        kr, vr = jnp.repeat(k, h // hk, 2), jnp.repeat(v, h // hk, 2)
        s = jnp.einsum("bihd,bjhd->bhij", q, kr,
                       precision=jax.lax.Precision.HIGHEST) * dh ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
        return jnp.einsum("bhij,bjhd->bihd", jax.nn.softmax(s, -1), vr,
                          precision=jax.lax.Precision.HIGHEST)

    out, vjp = jax.vjp(grouped, q, k, v)
    out_r, vjp_r = jax.vjp(repeated, q, k, v)
    out_d, vjp_d = jax.vjp(dense, q, k, v)
    np.testing.assert_allclose(out, out_r, atol=1e-6)
    np.testing.assert_allclose(out, out_d, atol=2e-5, rtol=1e-4)
    for got, same, want in zip(vjp(g), vjp_r(g), vjp_d(g)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, same, atol=1e-5)
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-3)
    with pytest.raises(ValueError, match="do not serve"):
        flash_attention(q, k[:, :, :1].repeat(3, 2), v, causal=True)


def test_init_scales_and_constants():
    cfg = dataclasses.replace(CFG, scaled_init_layers=8, hidden_size=128,
                              router_hidden_size=64)
    p = decoder_init(jax.random.PRNGKey(1), cfg)["moe"]
    for leaf in (p["attn"]["o"], p["mlp"]["experts"]["down"]):
        assert abs(float(jnp.std(leaf["w"])) / 0.005 - 1.0) < 0.1
    for leaf in (p["attn"]["q"], p["attn"]["conv_b"], p["mlp"]["router"]["reduce"],
                 p["mlp"]["experts"]["up"]):
        assert abs(float(jnp.std(leaf["w"])) / 0.02 - 1.0) < 0.1
    for name in ("fc1", "fc2", "fc3"):
        leaf = p["mlp"]["router"][name]
        assert abs(float(jnp.std(leaf["w"])) / decoder.ROUTER_MLP_STD - 1.0) < 0.1
        assert float(jnp.max(jnp.abs(leaf["b"]))) == 0.0
    for ones in (p["attn"]["tau"], p["mlp"]["router"]["gamma"], p["attn_res"]["a"]):
        np.testing.assert_array_equal(ones, jnp.ones_like(ones))
    for zeros in (p["attn_res"]["c"], p["mlp"]["bias"], p["attn"]["conv_a"]["b"]):
        np.testing.assert_array_equal(zeros, jnp.zeros_like(zeros))


@pytest.mark.parametrize("bad", [
    dict(tie_word_embeddings=False), dict(num_key_value_heads=3),
    dict(num_key_value_heads=1), dict(experts_held=(4, 12))])
def test_config_refuses_what_the_model_does_not_compute(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **bad)
