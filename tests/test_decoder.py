"""The decoder language model (models/decoder.py, ops/moe.py,
training/lm.py) against its plain reference
(benchmarks/reference/decoder_lm.py) at a small size on the CPU, the
causal flash attention of both arms against materialised logits, and the
expert layer's share of a deployment."""
import dataclasses
import importlib.util
import os

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import numpy as np
import pytest
from jaxpr_tools import kernel_call_sites

from alphafold2_tpu.models import decoder
from alphafold2_tpu.models.decoder import (DecoderConfig, decoder_apply,
                                            decoder_init)
from alphafold2_tpu.ops import moe
from alphafold2_tpu.ops.flash import flash_attention
from alphafold2_tpu.training.harness import (TrainConfig, make_optimizer,
                                             make_train_step)
from alphafold2_tpu.training.lm import (lm_aux_update, lm_loss_fn,
                                        zipf_token_batches)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, *parts):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("bench_reference_decoder_lm", "benchmarks", "reference",
                  "decoder_lm.py")

CFG = DecoderConfig(
    vocab_size=256, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, kv_lora_rank=32, intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
    n_shared_experts=2, routed_scaling_factor=2.448, rope_theta=1e6,
    dtype="float32")


def _hp(cfg, **over):
    hp = {"heads": cfg.num_attention_heads, "nope": cfg.qk_nope_head_dim,
          "rope": cfg.qk_rope_head_dim, "dv": cfg.v_head_dim,
          "lora": cfg.kv_lora_rank, "eps": cfg.rms_norm_eps,
          "theta": cfg.rope_theta, "top_k": cfg.num_experts_per_tok,
          "scaling": cfg.routed_scaling_factor, "norm_topk": cfg.norm_topk_prob,
          "held": cfg.held, "lr": 3e-4, "bias_rate": cfg.bias_update_rate,
          "attn_block": 16, "ff_block": 32, "loss_block": 64}
    return dict(hp, **over)


def _tokens(seed=5, batch=2, length=64):
    return next(zipf_token_batches(CFG.vocab_size, batch, length, seed))["tokens"]


@pytest.fixture(scope="module")
def params():
    p = decoder_init(jax.random.PRNGKey(0), CFG)
    # a bias that matters: the picks must be of s + b, the weights of s
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(9), p["moe"]["mlp"]["bias"].shape)
    p["moe"]["mlp"]["bias"] = bias
    return p


def _worst(a, b):
    gaps = jax.tree_util.tree_map(
        lambda x, y: float(jnp.max(jnp.abs(x - y)) / (jnp.max(jnp.abs(y)) + 1e-12)), a, b)
    return max(jax.tree_util.tree_leaves(gaps))


@pytest.mark.parametrize("held", [None, (2, 6)], ids=["all_experts", "share_2_6"])
def test_loss_and_gradients_match_reference(params, held):
    cfg = dataclasses.replace(CFG, experts_held=held)
    p = params
    if held:
        lo, hi = held
        experts = jax.tree_util.tree_map(lambda t: t[:, lo:hi],
                                         params["moe"]["mlp"]["experts"])
        p = {**params, "moe": {**params["moe"], "mlp": {
            **params["moe"]["mlp"], "experts": experts}}}
    tokens = _tokens()
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda q: lm_loss_fn(q, cfg, {"tokens": tokens}), has_aux=True))(p)
    want, want_grads, picks, load = reference.value_and_grad(p, tokens, _hp(cfg))
    assert abs(float(loss) - float(want)) < 2e-5 * float(want)
    assert _worst(grads, want_grads) < 2e-3
    assert float(jnp.max(jnp.abs(grads["moe"]["mlp"]["bias"]))) == 0.0
    # what a step sums; the picks are per token
    assert set(aux) == {"load", "rows_walked"}
    np.testing.assert_array_equal(aux["load"], load)
    got_picks = decoder_apply(p, cfg, tokens)[1]["picks"]
    np.testing.assert_array_equal(np.sort(got_picks, -1), np.sort(picks, -1))


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
        held = np.asarray(load)[:, :]
        np.testing.assert_allclose(metrics["moe_assignments_held"], held.sum(-1))
        np.testing.assert_allclose(metrics["moe_load_max_over_mean"],
                                   held.max(-1) / held.mean(-1), rtol=1e-6)
        block = moe.block_rows_for(tokens.size, CFG.num_experts_per_tok,
                                   CFG.n_routed_experts, CFG.n_routed_experts)
        np.testing.assert_array_equal(
            metrics["moe_rows_walked"], np.ceil(held.sum(-1) / block) * block)
        assert set(metrics) == {"loss", "grad_norm", "moe_assignments_held",
                                "moe_rows_walked", "moe_load_max_over_mean"}
    # Adam moves every leaf by about lr a step: compare the changes
    moved = jax.tree_util.tree_map(lambda a, b: a - b, state["params"], params)
    want_moved = jax.tree_util.tree_map(lambda a, b: a - b, ref_p, params)
    assert _worst(moved, want_moved) < 0.05
    np.testing.assert_allclose(state["params"]["moe"]["mlp"]["bias"],
                               ref_p["moe"]["mlp"]["bias"], atol=1e-7)


def test_scaled_init_narrows_the_residual_branches_last_projections():
    cfg = dataclasses.replace(CFG, scaled_init_layers=8)  # 0.02 / sqrt(16)
    p = decoder_init(jax.random.PRNGKey(1), cfg)
    narrow = [p["dense"]["attn"]["o"], p["moe"]["attn"]["o"], p["dense"]["mlp"]["down"],
              p["moe"]["mlp"]["experts"]["down"], p["moe"]["mlp"]["shared"]["down"]]
    wide = [p["dense"]["attn"]["q"], p["moe"]["mlp"]["experts"]["up"],
            p["moe"]["mlp"]["router"], p["head"]]
    for leaf in narrow:
        assert abs(float(jnp.std(leaf["w"])) / 0.005 - 1.0) < 0.1
    for leaf in wide:
        assert abs(float(jnp.std(leaf["w"])) / 0.02 - 1.0) < 0.1
    assert abs(float(jnp.std(p["embed"]["table"])) / 0.02 - 1.0) < 0.1


# (tokens, picks a token, held, experts): the three decoder cells' layers
# (kanana2, zaya1, mellum2 at 2 x 8192 tokens) and two small ones
_LAYER_SHAPES = [
    pytest.param(16384, 6, 16, 128, id="kanana2"),
    pytest.param(16384, 1, 8, 16, id="zaya1"),
    pytest.param(16384, 8, 16, 64, id="mellum2"),
    pytest.param(1000, 6, 16, 128, id="1000_tokens"),
    pytest.param(128, 2, 2, 8, id="toy"),
]


@pytest.mark.parametrize("n_tokens,top_k,n_held,n_experts", _LAYER_SHAPES)
def test_block_rows_are_whole_tiles_within_the_worst_case(n_tokens, top_k, n_held,
                                                          n_experts):
    block = moe.block_rows_for(n_tokens, top_k, n_held, n_experts)
    worst = n_tokens * min(top_k, n_held)
    assert 0 < block <= worst
    assert block % moe.ROW_TILE == 0 or block == worst
    # the same rule for every family: one block holds the load a balanced
    # router gives and a quarter more, to the tile
    expected = n_tokens * top_k * n_held / n_experts
    assert block == worst or 0 <= block - 1.25 * expected < moe.ROW_TILE
    assert moe.block_rows_for(2 * n_tokens, top_k, n_held, n_experts) >= block


@pytest.mark.parametrize("n_tokens,top_k,n_held,n_experts", _LAYER_SHAPES)
def test_rows_walked_are_the_live_blocks(n_tokens, top_k, n_held, n_experts):
    block = moe.block_rows_for(n_tokens, top_k, n_held, n_experts)
    worst = n_tokens * min(top_k, n_held)
    assert float(moe.rows_walked(0, block)) == 0.0
    for held in (1, block - 1, block, block + 1, worst // 2, worst):
        walked = float(moe.rows_walked(held, block))
        assert walked % block == 0
        assert held <= walked < held + block
    # a balanced router's load is one block: a quarter more than it holds
    expected = n_tokens * top_k * n_held / n_experts
    if expected >= 8 * moe.ROW_TILE:
        assert float(moe.rows_walked(expected, block)) == block <= 1.25 * expected


def test_bias_update_moves_toward_the_mean():
    load = jnp.array([[4.0, 0.0, 2.0, 2.0], [1.0, 1.0, 1.0, 5.0]])
    out = moe.bias_update(jnp.zeros((2, 4)), load, 0.001)
    np.testing.assert_allclose(out, [[-0.001, 0.001, 0.0, 0.0],
                                     [0.001, 0.001, 0.001, -0.001]])


def _moe_params(key, d=64, f=32, n_experts=8, shared=64):
    p = decoder_init(key, dataclasses.replace(CFG, num_hidden_layers=2))
    return jax.tree_util.tree_map(lambda t: t[0], p["moe"]["mlp"])


def test_shares_add_up_to_the_uncut_layer():
    """The routed parts of all four shares [0,2) .. [6,8), with the shared
    experts counted once, equal the uncut reference's layer."""
    p = _moe_params(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (96, 64))
    kw = dict(top_k=2, scaling=2.448, norm_topk=True)
    idx, w, _ = moe.route(p, x, **kw)
    routed = sum(
        moe.experts_apply(
            jax.tree_util.tree_map(lambda t: t[lo:lo + 2], p["experts"]),
            x, idx, w, held=(lo, lo + 2), block_rows=40)
        for lo in range(0, 8, 2))
    whole = routed + moe.swiglu(p["shared"], x, jnp.float32)
    want, _, _ = reference.moe(p, x, _hp(CFG, held=(0, 8)))
    np.testing.assert_allclose(whole, want, rtol=2e-5, atol=2e-6)


def test_every_token_to_one_held_expert_drops_none():
    p = _moe_params(jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (80, 64))
    # expert 5 is picked by every token, first; held here with its neighbour
    p = {**p, "bias": jnp.zeros((8,)).at[5].set(10.0)}
    kw = dict(top_k=2, scaling=2.448, norm_topk=True)
    idx, w, load = moe.route(p, x, **kw)
    assert float(load[5]) == 80.0
    share = jax.tree_util.tree_map(lambda t: t[4:6], p["experts"])
    # blocks of 16 rows: the 80 assignments of expert 5 span five of them
    got = moe.experts_apply(share, x, idx, w, held=(4, 6), block_rows=16)
    w5 = jnp.sum(jnp.where(idx == 5, w, 0.0), -1)
    w4 = jnp.sum(jnp.where(idx == 4, w, 0.0), -1)
    e = lambda i: jax.tree_util.tree_map(lambda t: t[i], p["experts"])  # noqa: E731
    want = (w5[:, None] * moe.swiglu(e(5), x, jnp.float32)
            + w4[:, None] * moe.swiglu(e(4), x, jnp.float32))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert float(jnp.min(jnp.abs(w5))) > 0.0  # every token's part is there


def _picks_with_held(n_tokens: int, n_held_picks: int):
    """(n_tokens, 2) picks over 8 experts of which exactly `n_held_picks`
    lie in [2, 6): slot 0 of token 0, 1, ..., then slot 1, a token's two
    picks distinct; the rest go to experts 0, 1, 6, 7."""
    idx = np.stack([np.arange(n_tokens) % 2, 6 + np.arange(n_tokens) % 2], -1)
    for j in range(n_held_picks):
        token, slot = j % n_tokens, j // n_tokens
        idx[token, slot] = 2 + (token + slot) % 4
    return jnp.asarray(idx, jnp.int32)


def _dense_share(share, x, idx, w, held):
    """sum over the held experts e of (w at e's picks) * SwiGLU_e(x), every
    expert over every token."""
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(*held):
        one = jax.tree_util.tree_map(lambda t: t[e - held[0]], share)
        we = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        out = out + we[:, None] * moe.swiglu(one, x, jnp.float32)
    return out


@pytest.mark.parametrize("n_held_picks", [
    pytest.param(0, id="none_held-zero_trips"),
    pytest.param(20, id="inside_one_block"),
    pytest.param(64, id="exactly_two_blocks"),
    pytest.param(65, id="one_row_over"),
    pytest.param(96, id="every_pick_held-worst_case"),
])
def test_expert_loop_matches_the_dense_sum_at_every_load(n_held_picks):
    """48 tokens x 2 picks in blocks of 32 rows: value and the gradients to
    the experts, x and the routing weights, whatever the loop's bound."""
    p = _moe_params(jax.random.PRNGKey(11))
    share = jax.tree_util.tree_map(lambda t: t[2:6], p["experts"])
    x = jax.random.normal(jax.random.PRNGKey(12), (48, 64))
    w = jax.random.uniform(jax.random.PRNGKey(13), (48, 2), minval=0.2, maxval=1.0)
    idx = _picks_with_held(48, n_held_picks)
    assert int(jnp.sum((idx >= 2) & (idx < 6))) == n_held_picks

    def value_and_grads(layer):
        return jax.jit(jax.value_and_grad(
            lambda share, x, w: jnp.sum(jnp.sin(layer(share, x, w) + 0.3)),
            argnums=(0, 1, 2)))(share, x, w)

    got = value_and_grads(lambda share, x, w: moe.experts_apply(
        share, x, idx, w, held=(2, 6), block_rows=32))
    want = value_and_grads(lambda share, x, w: _dense_share(share, x, idx, w, (2, 6)))
    for g, t in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, t, rtol=2e-5, atol=2e-6)
    if n_held_picks == 0:
        out = moe.experts_apply(share, x, idx, w, held=(2, 6), block_rows=32)
        assert float(jnp.max(jnp.abs(out))) == 0.0
        for g in jax.tree_util.tree_leaves(got[1]):
            assert float(jnp.max(jnp.abs(g))) == 0.0


def test_rows_of_no_group_stay_out_of_result_and_gradient(monkeypatch):
    """On a TPU a grouped product leaves the rows past the groups' sum
    unwritten, forward and in x's gradient. With NaN there, the expert
    layer's result and all its gradients are what they are without."""
    p = _moe_params(jax.random.PRNGKey(7))
    x = jax.random.normal(jax.random.PRNGKey(8), (96, 64))
    share = jax.tree_util.tree_map(lambda t: t[2:4], p["experts"])
    idx, w, _ = moe.route(p, x, top_k=2, scaling=2.448, norm_topk=True)

    def poison(t, sizes):
        return jnp.where((jnp.arange(t.shape[0]) < jnp.sum(sizes))[:, None], t, jnp.nan)

    @jax.custom_vjp
    def poisoned(x, w, sizes):
        return poison(jax.lax.ragged_dot(x, w, sizes), sizes)

    def fwd(x, w, sizes):
        return poisoned(x, w, sizes), (x, w, sizes)

    def bwd(res, ct):
        x, w, sizes = res
        dx, dw = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, sizes), x, w)[1](
            jnp.where(jnp.isnan(ct), 0.0, ct))
        return poison(dx, sizes), dw, None

    poisoned.defvjp(fwd, bwd)

    def run():
        return jax.value_and_grad(
            lambda share, x, w: jnp.sum(jnp.sin(moe.experts_apply(
                share, x, idx, w, held=(2, 4), block_rows=64))),
            argnums=(0, 1, 2))(share, x, w)

    want = run()
    monkeypatch.setattr(moe, "grouped_matmul", lambda x, w, sizes: poisoned(x, w, sizes))
    got = run()
    for g, t in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, t, rtol=1e-6, atol=1e-7)


def _materialised(q, k, v, scale):
    s = jnp.einsum("bihd,bjhd->bhij", q, k) * scale
    n = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    return jnp.einsum("bhij,bjhd->bihd", jax.nn.softmax(s, -1), v)


_KERNEL_64 = dict(use_kernel=True, kernel_qb=64, kernel_kb=64)
# the query block larger than the key sub-tile: two sub-tiles a step
_KERNEL_128_64 = dict(use_kernel=True, kernel_qb=128, kernel_kb=64)


@pytest.mark.parametrize("arm,n,h,dh,dv", [
    pytest.param(dict(use_kernel=False, kv_block=64), 256, 2, 24, 16,
                 id="xla_stream-256"),
    pytest.param(dict(use_kernel=False, kv_block=64), 200, 2, 24, 16,
                 id="xla_stream-200"),
    # n = 256 at blocks of 64: four query blocks, so tiles below the
    # diagonal, on it and never visited all occur; 200 pads to the blocks
    pytest.param(_KERNEL_64, 256, 2, 24, 16, id="kernel-256"),
    pytest.param(_KERNEL_64, 200, 2, 24, 16, id="kernel-200"),
    pytest.param(_KERNEL_128_64, 256, 2, 24, 16, id="kernel_uneven_blocks-256"),
    pytest.param(_KERNEL_128_64, 200, 2, 24, 16, id="kernel_uneven_blocks-200"),
    # four heads in the (B, n, h * d) layout: one group of four at 24 / 16
    # (a block as wide as the array), two groups of two at the decoder's
    # 192 / 128 (each head in the 256-lane window that holds its lanes)
    pytest.param(_KERNEL_64, 200, 4, 24, 16, id="kernel-200-h4"),
    pytest.param(_KERNEL_128_64, 384, 4, 24, 16, id="kernel_uneven_blocks-384-h4"),
    pytest.param(_KERNEL_64, 192, 2, 192, 128, id="kernel-192-mla_heads"),
    pytest.param(_KERNEL_128_64, 200, 2, 192, 128,
                 id="kernel_uneven_blocks-200-mla_heads"),
    pytest.param(_KERNEL_128_64, 384, 4, 192, 128,
                 id="kernel_uneven_blocks-384-h4-mla_heads"),
    pytest.param(dict(use_kernel=True), 200, 4, 192, 128,
                 id="kernel_own_plan-200-h4-mla_heads"),
    # heads of 96: the four windows of a group are 128, 256, 256, 128 wide
    pytest.param(_KERNEL_128_64, 200, 4, 96, 32, id="kernel_uneven_blocks-200-h4-96"),
])
def test_causal_flash_attention_unequal_head_sizes(arm, n, h, dh, dv):
    """qk heads of dh, v heads of dv, against materialised logits in
    float32: forward and all three gradients (the kernel in interpret
    mode)."""
    keys = jax.random.split(jax.random.PRNGKey(n), 3)
    q, k = (jax.random.normal(kk, (2, n, h, dh)) for kk in keys[:2])
    v = jax.random.normal(keys[2], (2, n, h, dv))
    scale = dh ** -0.5

    def run(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2))(q, k, v)

    want, want_grads = run(lambda *a: _materialised(*a, scale))
    got, grads = run(lambda *a: flash_attention(*a, causal=True, **arm))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, atol=2e-5)
    assert flash_attention(q, k, v, causal=True, **arm).shape == (2, n, h, dv)


@pytest.mark.parametrize("nb", [1, 2, 3, 8, 16])
def test_causal_schedule_lists_the_tiles_on_or_below_the_diagonal(nb):
    """Both walks list exactly the nb (nb + 1) / 2 pairs with key <= query,
    each once; `first` / `last` flag a query block's first and last tile
    in the forward's walk and a key block's in the backward's."""
    from alphafold2_tpu.ops.flash_kernel import causal_plan, causal_schedule

    want = {(qi, ki) for qi in range(nb) for ki in range(qi + 1)}
    for key_major in (False, True):
        table = causal_schedule(nb, key_major=key_major)
        assert table.shape == (4, nb * (nb + 1) // 2) and table.dtype == np.int32
        pairs = list(zip(table[0].tolist(), table[1].tolist()))
        assert len(pairs) == len(set(pairs)) and set(pairs) == want
        # the block the accumulators belong to: the query block in the
        # forward's walk, the key block in the backward's
        own = table[1] if key_major else table[0]
        for t, (qi, ki) in enumerate(pairs):
            first = t == 0 or own[t - 1] != own[t]
            last = t == len(pairs) - 1 or own[t + 1] != own[t]
            assert (bool(table[2, t]), bool(table[3, t])) == (first, last)
            if key_major:  # a key block is met first on the diagonal
                assert bool(table[2, t]) == (qi == ki)
                assert bool(table[3, t]) == (qi == nb - 1)
            else:          # a query block ends on it
                assert bool(table[2, t]) == (ki == 0)
                assert bool(table[3, t]) == (qi == ki)
    # the plan's count of grid steps a row is the table's length
    assert causal_plan(8192, 32, 192, 128, qb=512, kb=512).tiles == 136
    assert causal_plan(8192, 32, 192, 128, qb=1024, kb=1024).tiles == 36


def test_causal_kernel_bound_in_n_and_the_xla_arm_beyond_it(monkeypatch):
    """The backward keeps dq for a whole row in VMEM, so `supported_causal`
    bounds n; past the bound "auto" resolves to the XLA arm on a TPU, and
    forcing the kernel raises."""
    from alphafold2_tpu.ops import dispatch, flash_kernel

    plan = flash_kernel.causal_plan(8192, 32, 192, 128)
    assert plan is not None and plan.g == 2
    assert plan.vmem <= flash_kernel._CAUSAL_VMEM_CAP
    assert flash_kernel.supported_causal(8192, 8192, 192, 128)
    assert not flash_kernel.supported_causal(32768, 32768, 192, 128)
    assert flash_kernel.causal_plan(32768, 32, 192, 128) is None
    # narrower heads leave room for a longer row
    assert flash_kernel.supported_causal(32768, 32768, 64, 64)
    shape = dict(dh=192, dv=128, causal=True)
    assert dispatch.resolve("flash_attention", request="auto", platform="tpu",
                            i=8192, j=8192, **shape) == dispatch.ARM_PALLAS_TPU
    assert dispatch.resolve("flash_attention", request="auto", platform="tpu",
                            i=32768, j=32768, **shape) == dispatch.ARM_XLA_REF
    with pytest.raises(ValueError, match="does not support"):
        dispatch.resolve("flash_attention", request=True, platform="tpu",
                         i=32768, j=32768, **shape)
    # the sub-tile divides the block
    with pytest.raises(ValueError, match="must divide"):
        flash_kernel.causal_plan(256, 2, 24, 16, qb=64, kb=48)


def test_causal_takes_no_bias_and_noncausal_is_untouched():
    q = jnp.ones((1, 8, 1, 8))
    with pytest.raises(ValueError, match="no key bias"):
        flash_attention(q, q, q, jnp.zeros((1, 8)), causal=True, use_kernel=False)
    with pytest.raises(ValueError, match="no pair bias"):
        flash_attention(q, q, q, gate=q, causal=True, use_kernel=False)


# --- what the layer checkpoint saves -----------------------------------------

_ARM = "AF2_KERNEL_BACKEND_FLASH_ATTENTION"


def _bare_checkpoint(layer):
    """The layer under a `jax.checkpoint` without a policy, which recomputes
    the whole layer, the core's forward kernel included."""
    return jax.checkpoint(lambda h, lp: layer(lp, h))


@pytest.mark.parametrize("checkpoint,forward_sites", [("saved_names", 1), ("bare", 2)])
def test_differentiated_stack_calls_the_forward_kernel_once(monkeypatch, checkpoint,
                                                            forward_sites):
    """The two-layer dense stack's gradient with the kernel arm (interpret
    mode): ONE call site of the forward kernel and one of the backward
    kernel, since the checkpoint keeps `out` and `lse`. The case this
    guards is the bare checkpoint's, whose backward scan runs the forward
    kernel a second time."""
    monkeypatch.setenv(_ARM, "pallas_tpu")
    if checkpoint == "bare":
        monkeypatch.setattr(decoder, "_checkpointed_layer", _bare_checkpoint)
    cfg = dataclasses.replace(CFG, num_hidden_layers=2, first_k_dense_replace=2)
    p = decoder_init(jax.random.PRNGKey(1), cfg)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q: lm_loss_fn(q, cfg, {"tokens": _tokens()})[0]))(p)
    assert kernel_call_sites(jaxpr.jaxpr) == {
        "_causal_fwd_kernel": forward_sites, "_causal_bwd_kernel": 1}


@pytest.mark.parametrize("arm", ["pallas_tpu", "xla_ref"])
def test_layer_checkpoint_keeps_the_kernels_results_and_nothing_else(
        monkeypatch, capsys, params, arm):
    """With the kernel arm a layer keeps, besides its inputs, the core's
    `out` (B, L, h * dv) and `lse`; with the XLA arm it holds no such name
    and keeps nothing. Either way the loss and every gradient leaf are the
    bare checkpoint's."""
    monkeypatch.setenv(_ARM, arm)
    tokens = _tokens()
    B, L = tokens.shape
    layer = jax.tree_util.tree_map(lambda t: t[0], params["moe"])
    jax.ad_checkpoint.print_saved_residuals(
        decoder._checkpointed_layer(lambda lp, h: decoder._layer(lp, h, CFG, True)),
        jnp.zeros((B, L, CFG.hidden_size)), layer)
    kept = [line for line in capsys.readouterr().out.splitlines()
            if "from the argument" not in line and "from a constant" not in line]
    if arm == "xla_ref":
        assert kept == []
    else:
        from alphafold2_tpu.ops.flash_kernel import causal_plan

        h, dv = CFG.num_attention_heads, CFG.v_head_dim
        qb = causal_plan(L, h, CFG.qk_head_dim, dv, itemsize=4).qb
        assert len(kept) == 2 and all("flash_kernel.py" in line for line in kept)
        # the kernel's own row length: L padded to its block
        assert kept[0].startswith(f"f32[{B},{-(-L // qb) * qb},{h * dv}] ")
        assert "named 'attn_core_lse'" in kept[1]

    def value_and_grad():
        return jax.jit(jax.value_and_grad(
            lambda q: lm_loss_fn(q, CFG, {"tokens": tokens})[0]))(params)

    loss, grads = value_and_grad()
    monkeypatch.setattr(decoder, "_checkpointed_layer", _bare_checkpoint)
    want, want_grads = value_and_grad()
    assert abs(float(loss) - float(want)) <= 1e-6 * abs(float(want))
    assert _worst(grads, want_grads) < 1e-6
