"""The `mellum` decoder (models/decoder.py `MellumConfig`: grouped-query
attention with q / k norms by head, sliding-window and full causal layers
mixed by `layer_types`, YaRN on the full ones, a softmax top-k mixture
with no shared expert, an untied head) against its plain reference
(benchmarks/reference/mellum_lm.py) at a small size on the CPU, and the
pieces it forced: a window through both arms of the causal core, the
band's schedule, the YaRN table, a chip's share of the experts, a stack
of two kinds of layer."""
import dataclasses
import importlib
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.models import decoder
from alphafold2_tpu.models.decoder import (FULL, SLIDING, MellumConfig,
                                           decoder_apply, decoder_init)
from alphafold2_tpu.ops import flash_kernel, moe
from alphafold2_tpu.ops.flash import causal_kernel_plan, flash_attention
from alphafold2_tpu.training.harness import (TrainConfig, make_optimizer,
                                             make_train_step)
from alphafold2_tpu.training.lm import (lm_aux_update, lm_loss_fn,
                                        zipf_token_batches)

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
reference = importlib.import_module("reference.mellum_lm")

ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
           "original_max_position_embeddings": 8192, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.2772588722239782},
    SLIDING: {"rope_type": "default", "rope_theta": 500000},
}
PERIOD = (SLIDING, SLIDING, SLIDING, FULL)
# two periods; the window (24) is under the length (64), so the band's
# edge crosses the tiles of the XLA arm's 16-wide blocks below
CFG = MellumConfig(
    vocab_size=256, hidden_size=64, num_hidden_layers=8, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, moe_intermediate_size=32, num_experts=8,
    num_experts_per_tok=2, layer_types=PERIOD * 2, sliding_window=24,
    rope_parameters=ROPE, dtype="float32")


def _hp(cfg, **over):
    hp = {"heads": cfg.num_attention_heads, "kv_heads": cfg.num_key_value_heads,
          "dh": cfg.head_dim, "eps": cfg.rms_norm_eps,
          "top_k": cfg.num_experts_per_tok, "norm_topk": cfg.norm_topk_prob,
          "held": cfg.held, "lr": 3e-4, "layer_types": cfg.layer_types,
          "window": cfg.sliding_window, "rope": cfg.rope_parameters,
          "attn_block": 16, "ff_block": 32, "loss_block": 64}
    return dict(hp, **over)


def _tokens(seed=5, batch=2, length=64):
    return next(zipf_token_batches(CFG.vocab_size, batch, length, seed))["tokens"]


def _drawn_away(params, key):
    """Every norm's scale drawn away from its init of 1, so that a path
    that ignored one could not pass; the router 1 / sqrt(64) wide, so that
    the program's picks and the reference's are no coin tosses."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        if str(path[-1].key) == "scale":
            leaf = 1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
        elif str(path[-2].key) == "router":
            leaf = leaf * (0.125 / CFG.initializer_range)
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
    assert set(aux) == {"load", "rows_walked"} and aux["load"].shape == (8, 8)
    np.testing.assert_array_equal(aux["load"], load)
    hidden, full = decoder_apply(p, cfg, tokens)
    np.testing.assert_array_equal(full["picks"], picks)
    got = jnp.matmul(hidden, p["head"]["w"], precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(got, reference.logits(p, tokens, _hp(cfg)),
                               atol=2e-5, rtol=2e-4)
    # the window matters at this size: the reference without it differs
    other, *_ = reference.value_and_grad(p, tokens, _hp(cfg, window=None))
    assert abs(float(other) - float(want)) > 1e-5 * float(want)


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
        # a row a layer, in the published order across both kinds of layer
        assert metrics["moe_assignments_held"].shape == (8,)
        np.testing.assert_allclose(metrics["moe_assignments_held"],
                                   np.asarray(load).sum(-1))
        assert metrics["moe_load_max_over_mean"].shape == (8,)
        assert metrics["moe_rows_walked"].shape == (8,)
        assert np.all(np.asarray(metrics["moe_rows_walked"])
                      >= np.asarray(metrics["moe_assignments_held"]))
    moved = jax.tree_util.tree_map(lambda a, b: a - b, state["params"], params)
    want_moved = jax.tree_util.tree_map(lambda a, b: a - b, ref_p, params)
    assert _worst(moved, want_moved) < 0.05
    # the family has no selection bias: the update has nothing to move
    assert "bias" not in state["params"]["moe"]["mlp"]


def test_the_four_shares_add_up_to_the_uncut_layers_mixture(params):
    """Experts [0, 2), [2, 4), [4, 6), [6, 8) of one layer's mixture, each
    as a chip of the deployment computes its part, add up to what the
    reference gives with all 8: there is no shared expert to count once."""
    lp = jax.tree_util.tree_map(lambda t: t[1], params["moe"]["mlp"])
    x = jax.random.normal(jax.random.PRNGKey(3), (96, CFG.hidden_size))
    routing = moe.route_softmax(moe.router_logits(lp, x), None, 2, norm_topk=True)
    parts = []
    for lo in range(0, 8, 2):
        mine = {"experts": jax.tree_util.tree_map(lambda t: t[lo:lo + 2], lp["experts"])}
        y, aux = moe.moe_apply(mine, x, routing, held=(lo, lo + 2))
        parts.append(y)
    hp = _hp(CFG, held=(0, 8), ff_block=0)
    idx, w, load = reference.router(lp["router"], x, hp)
    whole = reference.experts(lp["experts"], x, idx, w, hp)
    np.testing.assert_array_equal(aux["picks"], idx)
    np.testing.assert_array_equal(aux["load"], load)
    np.testing.assert_allclose(jnp.sum(routing[1], axis=-1), 1.0, atol=1e-6)
    assert all(float(jnp.max(jnp.abs(part))) > 0 for part in parts)
    np.testing.assert_allclose(sum(parts), whole, atol=2e-6, rtol=1e-4)
    # a token none of whose picks is held gets nothing from this chip
    absent = (np.asarray(idx) >= 2).all(-1)
    assert absent.any() and float(jnp.max(jnp.abs(parts[0][absent]))) == 0.0


def test_the_stack_equals_an_unrolled_loop_in_layer_types_order(params):
    """The scan over periods, with each run of one kind scanned inside it
    under the layers' checkpoint, against a Python loop over the same
    layers in `layer_types` order: hidden state, picks, gradients."""
    tokens = _tokens(seed=11, length=48)

    def unrolled(p):
        h = p["embed"]["table"][tokens]
        picks = []
        for i, kind in enumerate(CFG.layer_types):
            lp = jax.tree_util.tree_map(lambda t: t[i], p["moe"])
            h, aux = decoder._mellum_layer(lp, h, CFG, kind)
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
    # the order matters: with the kinds the other way round the result differs
    swapped = dataclasses.replace(CFG, layer_types=(FULL,) * 8)
    assert float(jnp.max(jnp.abs(decoder_apply(params, swapped, tokens)[0] - h_scan))) > 1e-4
    assert decoder._runs(PERIOD) == [(SLIDING, 0, 3), (FULL, 3, 4)]
    assert decoder._runs((FULL,)) == [(FULL, 0, 1)]


def _dense(q, k, v, window):
    n, g = q.shape[1], q.shape[2] // k.shape[2]
    kr, vr = jnp.repeat(k, g, 2), jnp.repeat(v, g, 2)
    s = jnp.einsum("bihd,bjhd->bhij", q, kr,
                   precision=jax.lax.Precision.HIGHEST) * q.shape[-1] ** -0.5
    i, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    seen = j <= i if window is None else (i - window < j) & (j <= i)
    return jnp.einsum("bhij,bjhd->bihd", jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1),
                      vr, precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla_arm", "kernel_interpret"])
@pytest.mark.parametrize("n,window", [
    (512, 256), (512, 128), (512, 200), (512, 100), (512, 2), (512, 1), (512, 600), (300, 77)],
    ids=["two_blocks", "one_block", "no_multiple", "under_a_block", "two_keys", "own_key_alone",
         "past_the_length", "length_no_multiple"])
def test_the_causal_core_with_a_window_through_both_arms(use_kernel, n, window):
    """Against a dense masked softmax, forward and all three gradients, at
    blocks of 128 (sub-tiles of 64): a window that is a multiple of the
    block, no multiple, under one block, of two keys, past the length (the
    plain triangle), and a length that is no multiple of the block. Grouped
    keys (4 query heads over 2 key heads) throughout."""
    B, h, hk, dh = 1, 4, 2, 64
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(n + window), 4)
    q = jax.random.normal(kq, (B, n, h, dh))
    k = jax.random.normal(kk, (B, n, hk, dh))
    v = jax.random.normal(kv, (B, n, hk, dh))
    g = jax.random.normal(kg, (B, n, h, dh))
    kw = (dict(kernel_qb=128, kernel_kb=64) if use_kernel else dict(kv_block=128))
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, use_kernel=use_kernel, **kw), q, k, v)
    want, vjp_d = jax.vjp(lambda q, k, v: _dense(q, k, v, window), q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=1e-4)
    for got, ref in zip(vjp(g), vjp_d(g)):
        np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-3)


def test_window_refusals():
    q = jnp.zeros((1, 128, 2, 64))
    with pytest.raises(ValueError, match="holds no key"):
        flash_attention(q, q, q, causal=True, window=0)
    with pytest.raises(ValueError, match="needs causal"):
        flash_attention(q, q, q, window=8)


def _triangle(nb, key_major):
    """The schedule as it was before there was a window, transcribed."""
    if key_major:
        pairs = [(qi, ki) for ki in range(nb) for qi in range(ki, nb)]
        flags = [(qi == ki, qi == nb - 1) for qi, ki in pairs]
    else:
        pairs = [(qi, ki) for qi in range(nb) for ki in range(qi + 1)]
        flags = [(ki == 0, ki == qi) for qi, ki in pairs]
    return np.array([[q for q, _ in pairs], [k for _, k in pairs],
                     [f for f, _ in flags], [l for _, l in flags]], np.int32)


@pytest.mark.parametrize("key_major", [False, True], ids=["query_major", "key_major"])
def test_without_a_window_the_schedule_is_the_triangles(key_major):
    for nb in (1, 2, 3, 8):
        table = flash_kernel.causal_schedule(nb, key_major)
        np.testing.assert_array_equal(table, _triangle(nb, key_major))
        assert table.dtype == np.int32
        # a band as wide as the triangle is the triangle
        np.testing.assert_array_equal(
            flash_kernel.causal_schedule(nb, key_major, window_blocks=nb), table)


@pytest.mark.parametrize("key_major", [False, True], ids=["query_major", "key_major"])
def test_the_bands_schedule_lists_the_tiles_that_hold_a_pair(key_major):
    """Every (query block, key block) with a pair in the band once, none
    without one; each row's (column's) first and last tile flagged."""
    for nb, qb, window in [(8, 1024, 1024), (8, 128, 100), (8, 128, 129),
                           (6, 128, 300), (4, 128, 1), (5, 128, 2)]:
        back = flash_kernel.window_blocks(window, qb)
        table = flash_kernel.causal_schedule(nb, key_major, window_blocks=back)
        want = {(qi, ki) for qi in range(nb) for ki in range(qi + 1)
                if (qi - ki) * qb - (qb - 1) < window}
        got = list(zip(table[0].tolist(), table[1].tolist()))
        assert len(got) == len(set(got)) and set(got) == want
        major = 1 if key_major else 0
        for blk in range(nb):
            mine = [t for t in range(len(got)) if got[t][major] == blk]
            assert mine == list(range(mine[0], mine[-1] + 1))
            assert table[2, mine].tolist() == [1] + [0] * (len(mine) - 1)
            assert table[3, mine].tolist() == [0] * (len(mine) - 1) + [1]
        # the tiles that take a mask: the diagonal's, and those with a pair outside
        masked = flash_kernel.masked_distances(window, qb)
        assert masked == tuple(d for d in range(back + 1)
                               if d == 0 or d * qb + qb - 1 >= window)


def test_the_plan_reports_the_tiles_it_walks():
    """The cell's shape: 8192 positions, blocks and window of 1024: 15 of
    the triangle's 36 tiles; the blocks, the head group and the resident
    dq (so the length the kernel takes) are the triangle's."""
    args = (8192, 32, 128, 128)
    band, triangle = (flash_kernel.causal_plan(*args, window=1024),
                      flash_kernel.causal_plan(*args))
    assert (band.tiles, triangle.tiles) == (15, 36)
    assert band._replace(tiles=36, window=None) == triangle
    assert flash_kernel.causal_plan(*args, window=8192) == triangle
    plan = causal_kernel_plan(*args, jnp.bfloat16, window=1024)
    assert (plan["tiles"], plan["tiles_triangle"], plan["window"]) == (15, 36, 1024)
    assert causal_kernel_plan(*args, jnp.bfloat16)["tiles"] == 36


def _yarn_literal(dim, theta, factor, original_max, beta_fast, beta_slow):
    """`transformers`' `_compute_yarn_parameters`, written out pair by pair."""
    def correction(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(dim // 2):
        f = 1.0 / theta ** (2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        extrapolation = 1.0 - ramp
        out.append(f / factor * (1.0 - extrapolation) + f * extrapolation)
    return low, high, np.array(out)


def test_yarn_table_is_transformers_and_plain_rope_at_factor_one():
    full = ROPE[FULL]
    args = (128, 500000.0, 16, 8192, 32, 1)
    low, high, want = _yarn_literal(*args)
    assert (low, high) == (18, 35)
    got = decoder.yarn_inv_freq(*args)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    plain = 500000.0 ** (-np.arange(0, 128, 2) / 128)
    np.testing.assert_allclose(got[:19], plain[:19], rtol=1e-12)
    np.testing.assert_allclose(got[35:], plain[35:] / 16, rtol=1e-12)
    np.testing.assert_allclose(decoder.yarn_inv_freq(128, 500000.0, 1, 8192, 32, 1),
                               plain, rtol=1e-12)
    np.testing.assert_allclose(reference.inv_freq_of(full, 128), want, rtol=1e-6)
    # through rope(): the table with factor 1 and no attention factor turns
    # as plain RoPE does; the attention factor scales the result
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 2, 128))
    np.testing.assert_allclose(
        decoder.rope(x, 500000.0, decoder.yarn_inv_freq(128, 500000.0, 1, 8192, 32, 1)),
        decoder.rope(x, 500000.0), atol=1e-6)
    theta, inv, factor = decoder._rope_table(full, 128)
    np.testing.assert_allclose(decoder.rope(x, theta, inv, factor),
                               full["attention_factor"] * decoder.rope(x, theta, inv),
                               rtol=1e-5, atol=1e-6)
    assert decoder._rope_table(ROPE[SLIDING], 128) == (500000.0, None, None)
    np.testing.assert_allclose(
        reference.turn(x, reference.inv_freq_of(full, 128), factor),
        decoder.rope(x, theta, inv, factor), atol=1e-5)


@pytest.mark.parametrize("t", [0, 17, 40])
def test_a_window_layer_sees_its_window_and_no_later_token(params, t):
    """Changing x at position t moves a window layer's outputs at t ..
    t + window - 1 alone, a full layer's from t on."""
    lp = jax.tree_util.tree_map(lambda a: a[0], params["moe"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 80, CFG.hidden_size))
    x2 = x.at[0, t].add(jax.random.normal(jax.random.PRNGKey(2), (CFG.hidden_size,)))
    for kind, end in ((SLIDING, t + CFG.sliding_window), (FULL, 80)):
        y, y2 = (decoder.gqa_apply(lp, a, CFG, kind) for a in (x, x2))
        moved = np.abs(np.asarray(y - y2)).max(-1)[0] > 0
        assert not moved[:t].any() and not moved[end:].any()
        assert moved[t:min(end, 80)].all()


def test_init_scales_and_constants():
    cfg = dataclasses.replace(CFG, scaled_init_layers=8, hidden_size=128)
    p = decoder_init(jax.random.PRNGKey(1), cfg)
    for leaf in (p["moe"]["attn"]["o"], p["moe"]["mlp"]["experts"]["down"]):
        assert abs(float(jnp.std(leaf["w"])) / 0.005 - 1.0) < 0.1
    for leaf in (p["moe"]["attn"]["q"], p["moe"]["attn"]["k"], p["moe"]["attn"]["v"],
                 p["moe"]["mlp"]["router"], p["moe"]["mlp"]["experts"]["up"], p["head"]):
        assert abs(float(jnp.std(leaf["w"])) / 0.02 - 1.0) < 0.1
    for ones in (p["moe"]["attn"]["q_norm"], p["moe"]["attn"]["k_norm"],
                 p["moe"]["attn_norm"], p["final_norm"]):
        np.testing.assert_array_equal(ones["scale"], jnp.ones_like(ones["scale"]))
    assert p["moe"]["attn"]["q_norm"]["scale"].shape == (8, 16)
    assert set(p["moe"]["mlp"]) == {"router", "experts"}


@pytest.mark.parametrize("bad", [
    dict(layer_types=PERIOD + (SLIDING,), num_hidden_layers=5),
    dict(layer_types=PERIOD + (SLIDING, SLIDING, FULL), num_hidden_layers=7),
    dict(layer_types=(SLIDING, "chunked_attention") * 4),
    dict(layer_types=PERIOD), dict(tie_word_embeddings=True),
    dict(num_key_value_heads=3), dict(experts_held=(4, 12)),
    dict(rope_parameters={SLIDING: ROPE[SLIDING]})],
    ids=["a_period_and_a_layer", "two_unlike_periods", "unknown_kind",
         "fewer_kinds_than_layers", "tied", "key_heads", "experts_held", "no_rope_section"])
def test_config_refuses_what_the_model_does_not_compute(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **bad)


def test_config_is_hashable_and_gives_its_sections_back():
    assert hash(CFG) == hash(dataclasses.replace(CFG))
    assert CFG.rope_of(FULL) == ROPE[FULL] and CFG.period == PERIOD
    assert CFG.window_of(SLIDING) == 24 and CFG.window_of(FULL) is None
    assert dataclasses.replace(CFG, layer_types=(SLIDING,) * 8).period == (SLIDING,)
    with pytest.raises(ValueError, match="default.*yarn"):
        decoder._rope_table({"rope_type": "llama3", "rope_theta": 1e4}, 16)
