"""Reversible trunk: gradient parity and reference parity.

Mirrors the reference's only numerical-parity test
(reference tests/test_reversible.py): same weights through the O(1)-memory
reversible path and the plain-autodiff path must give equal outputs and
equal gradients (reference tolerance atol=1e-3; we hold 1e-4 in float32).
Adds what the reference never had: full-model forward parity of the
reversible Alphafold2 against the reference PyTorch implementation on
converted weights.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from alphafold2_tpu.models import (
    Alphafold2Config,
    alphafold2_init,
    alphafold2_apply,
    reversible_trunk_init,
    reversible_trunk_apply,
)

CFG = Alphafold2Config(dim=32, depth=3, heads=2, dim_head=8, max_seq_len=64,
                       reversible=True)
B, N, R, C = 2, 6, 3, 6


def _streams(seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(B, N, N, CFG.dim).astype(np.float32))
    m = jnp.asarray(rng.randn(B, R, C, CFG.dim).astype(np.float32))
    x_mask = jnp.asarray(rng.rand(B, N, N) > 0.1)
    msa_mask = jnp.asarray(rng.rand(B, R, C) > 0.1)
    return x, m, x_mask, msa_mask


def _loss_fn(reverse, with_rng):
    def loss(params, x, m, x_mask, msa_mask):
        rng = jax.random.PRNGKey(7) if with_rng else None
        xo, mo = reversible_trunk_apply(
            params, CFG, x, m, x_mask=x_mask, msa_mask=msa_mask,
            rng=rng, reverse=reverse,
        )
        return jnp.sum(xo ** 2) + jnp.sum(mo ** 2)
    return loss


@pytest.mark.parametrize(
    "with_rng", [False, pytest.param(True, marks=pytest.mark.slow)]
)
def test_grad_parity_reversible_vs_autodiff(with_rng):
    # with_rng threads a key through both paths (dropout rates are 0 here,
    # so outputs stay equal; live-dropout parity is covered by
    # test_grad_parity_with_dropout_keys below)
    params = reversible_trunk_init(jax.random.PRNGKey(0), CFG)
    x, m, x_mask, msa_mask = _streams()

    v_rev, g_rev = jax.value_and_grad(_loss_fn(True, with_rng), argnums=(0, 1, 2))(
        params, x, m, x_mask, msa_mask
    )
    v_irr, g_irr = jax.value_and_grad(_loss_fn(False, with_rng), argnums=(0, 1, 2))(
        params, x, m, x_mask, msa_mask
    )

    np.testing.assert_allclose(v_rev, v_irr, rtol=1e-5)
    flat_rev = jax.tree_util.tree_leaves(g_rev)
    flat_irr = jax.tree_util.tree_leaves(g_irr)
    assert len(flat_rev) == len(flat_irr)
    for a, b in zip(flat_rev, flat_irr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_grad_parity_geglu_kernel_arm(monkeypatch):
    """The reversible trunk's backward runs each feed-forward's forward
    under `jax.vjp` (the reconstruct pass): with the GEGLU kernel forced
    (interpret mode here) its outputs and gradients equal the XLA arm's.
    dim 128 puts the feed-forward on whole lane tiles."""
    import dataclasses

    from alphafold2_tpu.ops import dispatch

    cfg = dataclasses.replace(CFG, dim=128, depth=2)
    params = reversible_trunk_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(B, N, N, cfg.dim).astype(np.float32))
    m = jnp.asarray(rng.randn(B, R, C, cfg.dim).astype(np.float32))

    def loss(p, x, m):
        xo, mo = reversible_trunk_apply(p, cfg, x, m, reverse=True)
        return jnp.sum(xo ** 2) + jnp.sum(mo ** 2)

    got = {}
    for arm in ("pallas_tpu", "xla_ref"):
        monkeypatch.setenv("AF2_KERNEL_BACKEND_GEGLU_FF", arm)
        dispatch.reset_decisions()
        got[arm] = jax.value_and_grad(loss, argnums=(0, 1, 2))(params, x, m)
        arms = {k.split(" @ ")[0] for k in dispatch.decisions()
                if k.startswith("geglu_ff")}
        assert arms == {f"geglu_ff -> {arm}"}
    (v_k, g_k), (v_x, g_x) = got["pallas_tpu"], got["xla_ref"]
    np.testing.assert_allclose(v_k, v_x, rtol=1e-5)
    flat_k, flat_x = (jax.tree_util.tree_leaves(g) for g in (g_k, g_x))
    assert len(flat_k) == len(flat_x)
    for a, b in zip(flat_k, flat_x):
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b,
                                   atol=1e-4 * max(1.0, np.abs(b).max()))


@pytest.mark.slow
def test_grad_parity_with_dropout_keys():
    """With dropout ON, the custom backward must re-derive the same keys the
    forward used (the reference needs RNG capture/replay for this,
    reference reversible.py:26-56; here it's fold_in determinism)."""
    import dataclasses
    cfg = dataclasses.replace(CFG, attn_dropout=0.2, ff_dropout=0.2)
    params = reversible_trunk_init(jax.random.PRNGKey(1), cfg)
    x, m, x_mask, msa_mask = _streams(seed=3)
    rng = jax.random.PRNGKey(11)

    def loss(reverse):
        def f(params):
            xo, mo = reversible_trunk_apply(
                params, cfg, x, m, x_mask=x_mask, msa_mask=msa_mask,
                rng=rng, reverse=reverse,
            )
            return jnp.sum(xo ** 2) + jnp.sum(mo ** 2)
        return f

    v_rev, g_rev = jax.value_and_grad(loss(True))(params)
    v_irr, g_irr = jax.value_and_grad(loss(False))(params)
    np.testing.assert_allclose(v_rev, v_irr, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_rev), jax.tree_util.tree_leaves(g_irr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_reversible_model_forward_and_grad():
    cfg = Alphafold2Config(dim=32, depth=2, heads=2, dim_head=8, max_seq_len=64,
                           reversible=True)
    params = alphafold2_init(jax.random.PRNGKey(2), cfg)
    rs = np.random.RandomState(5)
    seq = jnp.asarray(rs.randint(0, 21, size=(1, 8)))
    msa = jnp.asarray(rs.randint(0, 21, size=(1, 3, 8)))

    @jax.jit
    def loss(params):
        out = alphafold2_apply(params, cfg, seq, msa)
        return jnp.sum(out ** 2)

    g = jax.grad(loss)(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()


def test_reversible_model_parity_vs_reference():
    torch = pytest.importorskip("torch")
    from ref_loader import load_reference, convert_alphafold2

    ref = load_reference()
    torch.manual_seed(9)
    m_ref = ref.Alphafold2(
        dim=32, depth=2, heads=2, dim_head=8, max_seq_len=64, reversible=True
    ).eval()
    cfg = Alphafold2Config(dim=32, depth=2, heads=2, dim_head=8, max_seq_len=64,
                           reversible=True)
    params = convert_alphafold2(m_ref)

    rs = np.random.RandomState(6)
    seq = rs.randint(0, 21, size=(1, 8)).astype(np.int64)
    msa = rs.randint(0, 21, size=(1, 3, 8)).astype(np.int64)
    with torch.no_grad():
        want = m_ref(torch.from_numpy(seq), msa=torch.from_numpy(msa)).numpy()
    got = alphafold2_apply(params, cfg, jnp.asarray(seq), jnp.asarray(msa))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)


@pytest.mark.slow
def test_reversible_with_sparse_layers():
    """Mixed sparse/dense layers in the reversible trunk (the reference's
    sparse_self_attn=(True, False)*k with reversible=True, reference
    alphafold2.py:349,407-411): reverse=True grads must match plain
    autodiff through the segmented cores."""
    cfg = Alphafold2Config(
        dim=16,
        depth=4,
        heads=2,
        dim_head=8,
        max_seq_len=32,
        reversible=True,
        sparse_self_attn=(True, False) * 2,
        sparse_block_size=4,
        sparse_num_random_blocks=1,
        sparse_num_local_blocks=2,
        sparse_use_kernel=False,
    )
    stacked = reversible_trunk_init(jax.random.PRNGKey(0), cfg)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    x = jax.random.normal(ks[0], (1, 8, 8, 16))
    m = jax.random.normal(ks[1], (1, 2, 8, 16))

    def loss(p, reverse):
        xo, mo = reversible_trunk_apply(p, cfg, x, m, reverse=reverse)
        return jnp.sum(jnp.square(xo)) + jnp.sum(jnp.square(mo))

    v_rev = loss(stacked, True)
    v_ref = loss(stacked, False)
    np.testing.assert_allclose(float(v_rev), float(v_ref), rtol=1e-5)

    g_rev = jax.grad(lambda p: loss(p, True))(stacked)
    g_ref = jax.grad(lambda p: loss(p, False))(stacked)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_rev), jax.tree_util.tree_leaves(g_ref)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
