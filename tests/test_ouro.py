"""The `ouro` decoder (models/decoder.py `OuroConfig`: a dense stack of
multi-head attention and SwiGLU layers with sandwich norms, run
`total_ut_steps` times over the same weights, a head and an exit gate after
every pass, the loss weighted by the exit distribution) against its plain
reference (benchmarks/reference/ouro_lm.py) at a small size on the CPU, and
the pieces it forced: the exit distribution and its entropy, the gate's
gradient through the loss weights, the stack's gradient as the sum of the
passes', one pass as the plain next-token loss, the sandwich layer, the
core with no q / k norm."""
import dataclasses
import functools
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.models import decoder
from alphafold2_tpu.models.decoder import FULL, OuroConfig, decoder_apply, decoder_init
from alphafold2_tpu.training import lm
from alphafold2_tpu.training.harness import TrainConfig, make_optimizer, make_train_step
from alphafold2_tpu.training.lm import lm_aux_update, lm_loss_fn, zipf_token_batches

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
reference = importlib.import_module("reference.ouro_lm")

HIGHEST = jax.lax.Precision.HIGHEST
CFG = OuroConfig(
    vocab_size=256, hidden_size=64, num_hidden_layers=6, layers=2,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16, intermediate_size=96,
    total_ut_steps=4, rope_theta=10000.0, beta=0.1, dtype="float32")


def _hp(cfg, **over):
    hp = {"heads": cfg.num_attention_heads, "kv_heads": cfg.num_key_value_heads,
          "dh": cfg.head_dim, "eps": cfg.rms_norm_eps, "theta": cfg.rope_theta,
          "passes": cfg.total_ut_steps, "beta": cfg.beta, "lr": 3e-4,
          "attn_block": 16, "ff_block": 32, "loss_block": 64}
    return dict(hp, **over)


def _tokens(seed=5, batch=2, length=64):
    return next(zipf_token_batches(CFG.vocab_size, batch, length, seed))["tokens"]


def _drawn_away(params, key):
    """Every norm's scale drawn away from its init of 1 and the gate's bias
    away from 0, so that a path that ignored one could not pass; the gate
    wide enough that the four passes weigh unlike amounts."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        if str(path[-1].key) == "scale":
            leaf = 1.0 + 0.1 * jax.random.normal(k, leaf.shape)
        elif str(path[0].key) == "exit_gate":
            leaf = (0.3 if str(path[-1].key) == "b" else 5.0) * (
                leaf + jax.random.normal(k, leaf.shape) * 0.02)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope="module")
def params():
    return _drawn_away(decoder_init(jax.random.PRNGKey(0), CFG), jax.random.PRNGKey(9))


def _worst(a, b):
    gaps = jax.tree_util.tree_map(
        lambda x, y: float(jnp.max(jnp.abs(x - y)) / (jnp.max(jnp.abs(y)) + 1e-12)), a, b)
    return max(jax.tree_util.tree_leaves(gaps))


def _loss_and_grad(params, cfg, tokens):
    return jax.jit(jax.value_and_grad(
        lambda q: lm_loss_fn(q, cfg, {"tokens": tokens}), has_aux=True))(params)


def test_loss_pass_losses_logits_and_every_gradient_leaf_match_reference(params):
    tokens = _tokens()
    (loss, aux), grads = _loss_and_grad(params, CFG, tokens)
    want, want_grads, want_aux = reference.value_and_grad(params, tokens, _hp(CFG))
    assert abs(float(loss) - float(want)) < 2e-5 * float(want)
    assert (jax.tree_util.tree_structure(grads)
            == jax.tree_util.tree_structure(want_grads))
    assert _worst(grads, want_grads) < 2e-3
    # the aux is sums over the targets; the step metrics divide them
    _, metrics = lm_aux_update(CFG)(params, aux)
    np.testing.assert_allclose(metrics["loop_pass_loss"], want_aux["pass_loss"], rtol=2e-5)
    np.testing.assert_allclose(metrics["loop_exit_step_mean"], want_aux["exit_step_mean"],
                               rtol=2e-5)
    # the four passes weigh unlike amounts and read unlike losses
    assert float(jnp.std(metrics["loop_pass_loss"])) > 1e-3
    assert 1.2 < float(metrics["loop_exit_step_mean"]) < 3.5
    states, gates = decoder_apply(params, CFG, tokens)
    assert len(states) == 4 and gates["gate_logits"].shape == (4, 2, 64)
    got = jnp.stack([jnp.matmul(h, params["head"]["w"], precision=HIGHEST) for h in states])
    np.testing.assert_allclose(got, reference.logits(params, tokens, _hp(CFG)),
                               atol=3e-5, rtol=3e-4)


def test_the_references_chain_rule_through_the_passes_is_autodiffs(params):
    """`reference.value_and_grad_layers` writes the backward through the
    passes out, a pass's vjp at a time; it equals autodiff of the same
    loss as one function, leaf by leaf."""
    tokens = _tokens(seed=17, length=48)
    hp = _hp(CFG)
    outer, layers, kinds = reference.unstack(params)
    loss, grads, aux = reference.value_and_grad_layers(outer, layers, kinds, tokens, hp)
    (want, want_aux), want_grads = jax.value_and_grad(
        reference.loss_of_layers, argnums=(0, 1), has_aux=True)(
            outer, layers, kinds, tokens, hp)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    np.testing.assert_allclose(aux["pass_loss"], want_aux["pass_loss"], rtol=1e-6)
    assert _worst(grads, want_grads) < 1e-5


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
        want, grads, aux = reference.value_and_grad(ref_p, tokens, hp)
        ref_p, opt = reference.train_step(ref_p, opt, grads, hp)
        assert abs(float(metrics["loss"]) - float(want)) < 1e-4 * float(want)
        assert metrics["loop_pass_loss"].shape == (4,)
        np.testing.assert_allclose(metrics["loop_pass_loss"], aux["pass_loss"], rtol=1e-4)
        np.testing.assert_allclose(metrics["loop_exit_step_mean"], aux["exit_step_mean"],
                                   rtol=1e-4)
        assert not [k for k in metrics if k.startswith("moe_")]
    moved = jax.tree_util.tree_map(lambda a, b: a - b, state["params"], params)
    want_moved = jax.tree_util.tree_map(lambda a, b: a - b, ref_p, params)
    assert _worst(moved, want_moved) < 0.05


def _literal_exit(z):
    """p(t) and H(p) from the gate logits as the equations write them, one
    token at a time, in float64."""
    z = np.asarray(z, np.float64)
    lam = 1.0 / (1.0 + np.exp(-z))
    T = z.shape[0]
    p = np.zeros_like(z)
    for t in range(T):
        p[t] = np.prod(1.0 - lam[:t], axis=0) * (lam[t] if t < T - 1 else 1.0)
    return p, -np.sum(p * np.log(p), axis=0)


@pytest.mark.parametrize("passes", [1, 2, 4, 7])
def test_the_exit_distribution_sums_to_one_and_its_entropy_is_the_literal(passes):
    z = 3.0 * jax.random.normal(jax.random.PRNGKey(passes), (passes, 3, 5))
    log_p = lm.exit_log_distribution(z)
    want_p, want_h = _literal_exit(z)
    np.testing.assert_allclose(jnp.sum(jnp.exp(log_p), axis=0), 1.0, atol=1e-6)
    np.testing.assert_allclose(jnp.exp(log_p), want_p, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(lm.exit_entropy(log_p), want_h, rtol=1e-5, atol=1e-6)
    lam = jax.nn.sigmoid(z)
    np.testing.assert_allclose(reference.exit_distribution(lam), want_p, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(reference.entropy(reference.exit_distribution(lam)), want_h,
                               rtol=1e-4, atol=1e-6)
    # the last pass's gate is not read
    moved = z.at[-1].add(10.0)
    np.testing.assert_array_equal(lm.exit_log_distribution(moved), log_p)


def test_the_gradient_reaches_the_gate_through_the_loss_weights(params):
    tokens = _tokens(seed=3)
    _, grads = _loss_and_grad(params, CFG, tokens)
    _, want, _ = reference.value_and_grad(params, tokens, _hp(CFG))
    for leaf in ("w", "b"):
        got = grads["exit_gate"][leaf]
        assert float(jnp.max(jnp.abs(got))) > 1e-4
        np.testing.assert_allclose(got, want["exit_gate"][leaf], rtol=2e-3,
                                   atol=2e-3 * float(jnp.max(jnp.abs(got))))
    # with beta 0 the gate still learns: the weights of the passes' losses
    # alone carry its gradient
    plain = dataclasses.replace(CFG, beta=0.0)
    _, grads0 = _loss_and_grad(params, plain, tokens)
    assert float(jnp.max(jnp.abs(grads0["exit_gate"]["w"]))) > 1e-4


def test_the_stacks_gradient_is_the_sum_of_the_four_passes(params):
    """Against an unrolled loop in which each pass has its own copy of the
    layers' weights: the program's gradient of the one stack equals the
    sum of the four copies' gradients, and no copy's is zero."""
    tokens = _tokens(seed=11, length=48)
    B, L = tokens.shape
    layer = functools.partial(decoder._ouro_layer, cfg=CFG)

    def loss_of(outer, copies):
        h = outer["embed"]["table"][tokens]
        states, gates = [], []
        for stack in copies:
            for i in range(CFG.layers):
                h, _ = layer(jax.tree_util.tree_map(lambda t: t[i], stack), h)
            h = decoder.rms_norm(outer["final_norm"], h, CFG.rms_norm_eps)
            states.append(h)
            gates.append(decoder.exit_gate_logits(outer["exit_gate"], h))
        targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        weights = jnp.broadcast_to((jnp.arange(L) < L - 1).astype(jnp.float32), (B, L))
        total, _ = lm._expected_loss(states, jnp.stack(gates), outer["head"]["w"],
                                     targets, weights, CFG.beta)
        return total / (B * (L - 1))

    outer = {k: v for k, v in params.items() if k != "dense"}
    copies = [params["dense"]] * CFG.total_ut_steps
    loss, per_pass = jax.jit(jax.value_and_grad(loss_of, argnums=1))(outer, copies)
    (want_loss, _), grads = _loss_and_grad(params, CFG, tokens)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *per_pass)
    assert _worst(grads["dense"], summed) < 1e-4
    for g in per_pass:
        assert float(jnp.max(jnp.abs(g["attn"]["q"]["w"]))) > 0.0


def test_one_pass_is_the_plain_next_token_loss(params):
    """T = 1: p(1) = 1, the entropy is 0, and the loss is the mean
    next-token cross-entropy of the same layers after the final norm."""
    one = dataclasses.replace(CFG, total_ut_steps=1)
    tokens = _tokens(seed=13)
    (loss, aux), _ = _loss_and_grad(params, one, tokens)
    (h,), gates = decoder_apply(params, one, tokens)
    logp = jax.nn.log_softmax(jnp.matmul(h, params["head"]["w"], precision=HIGHEST), -1)
    picked = jnp.take_along_axis(logp[:, :-1], tokens[:, 1:, None], -1)
    np.testing.assert_allclose(loss, -jnp.mean(picked), rtol=2e-6)
    assert float(lm.exit_entropy(lm.exit_log_distribution(gates["gate_logits"])).max()) == 0.0
    _, metrics = lm_aux_update(one)(params, aux)
    np.testing.assert_allclose(metrics["loop_pass_loss"], [loss], rtol=2e-6)
    assert float(metrics["loop_exit_step_mean"]) == 1.0


def test_the_sandwich_layer_is_its_transcription(params):
    lp = jax.tree_util.tree_map(lambda t: t[1], params["dense"])
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 40, CFG.hidden_size))
    eps = CFG.rms_norm_eps

    def norm(p, x):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["scale"]

    def mm(x, w):
        return jnp.matmul(x, w["w"], precision=HIGHEST)

    x = norm(lp["attn_norm"], h)
    B, L, _ = x.shape
    q, k, v = (mm(x, lp["attn"][n]).reshape(B, L, 4, 16) for n in "qkv")
    q, k = reference.rope(q, CFG.rope_theta), reference.rope(k, CFG.rope_theta)
    s = jnp.einsum("bihd,bjhd->bhij", q, k, precision=HIGHEST) / 4.0
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    a = jnp.einsum("bhij,bjhd->bihd", jax.nn.softmax(s, -1), v, precision=HIGHEST)
    h1 = h + norm(lp["attn_out_norm"], mm(a.reshape(B, L, 64), lp["attn"]["o"]))
    x = norm(lp["mlp_norm"], h1)
    m = mm(jax.nn.silu(mm(x, lp["mlp"]["gate"])) * mm(x, lp["mlp"]["up"]), lp["mlp"]["down"])
    want = h1 + norm(lp["mlp_out_norm"], m)
    got, aux = decoder._ouro_layer(lp, h, CFG)
    assert aux is None
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_attention_without_a_qk_norm_is_a_dense_masked_softmax(params):
    """`gqa_apply` on a layer with no q / k norm (one key head a query
    head) against a dense masked softmax; the same layer given norms of
    scale 2 reads otherwise, so the norm is applied where it is."""
    lp = jax.tree_util.tree_map(lambda t: t[0], params["dense"]["attn"])
    assert "q_norm" not in lp and "k_norm" not in lp
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 56, CFG.hidden_size))
    B, L, _ = x.shape
    q, k, v = (jnp.matmul(x, lp[n]["w"], precision=HIGHEST).reshape(B, L, 4, 16)
               for n in "qkv")
    q, k = reference.rope(q, CFG.rope_theta), reference.rope(k, CFG.rope_theta)
    want = reference.mm(reference.causal_attention(q, k, v, 0.25, 0).reshape(B, L, 64),
                        lp["o"]["w"])
    got = decoder.gqa_apply(lp, x, CFG, FULL)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    normed = {**lp, "q_norm": {"scale": 2.0 * jnp.ones(16)},
              "k_norm": {"scale": 2.0 * jnp.ones(16)}}
    assert float(jnp.max(jnp.abs(decoder.gqa_apply(normed, x, CFG, FULL) - got))) > 1e-3


def test_init_scales_and_constants():
    cfg = dataclasses.replace(CFG, scaled_init_layers=8, hidden_size=128, layers=3)
    p = decoder_init(jax.random.PRNGKey(1), cfg)
    for leaf in (p["dense"]["attn"]["o"], p["dense"]["mlp"]["down"]):
        assert abs(float(jnp.std(leaf["w"])) / 0.005 - 1.0) < 0.1
    for leaf in (p["dense"]["attn"]["q"], p["dense"]["attn"]["k"], p["dense"]["mlp"]["up"],
                 p["head"]):
        assert abs(float(jnp.std(leaf["w"])) / 0.02 - 1.0) < 0.1
    assert abs(float(jnp.std(p["exit_gate"]["w"])) / 0.02 - 1.0) < 0.2
    np.testing.assert_array_equal(p["exit_gate"]["b"], jnp.zeros((1,)))
    for name in ("attn_norm", "attn_out_norm", "mlp_norm", "mlp_out_norm"):
        np.testing.assert_array_equal(p["dense"][name]["scale"], jnp.ones((3, 128)))
    assert set(p) == {"embed", "final_norm", "head", "exit_gate", "dense"}
    assert set(p["dense"]["attn"]) == {"q", "k", "v", "o"}


@pytest.mark.parametrize("bad", [
    dict(total_ut_steps=0), dict(total_ut_steps=-1), dict(layers=7), dict(layers=0),
    dict(tie_word_embeddings=True), dict(num_key_value_heads=3)],
    ids=["no_pass", "negative_passes", "layers_over_published", "no_layer", "tied",
         "key_heads"])
def test_config_refuses_what_the_model_does_not_compute(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **bad)


def test_config_defaults_to_the_published_depth_and_is_hashable():
    cfg = dataclasses.replace(CFG, layers=None)
    assert cfg.layers == cfg.num_hidden_layers == 6
    assert hash(CFG) == hash(dataclasses.replace(CFG))
    assert CFG.window_of(FULL) is None
    assert CFG.rope_of(FULL) == {"rope_type": "default", "rope_theta": 10000.0}
    assert decoder.FAMILIES["ouro"] is OuroConfig
