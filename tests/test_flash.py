"""Blockwise (flash-style) attention: parity vs the dense path, and the
column-aligned cross-attention trunk mode.

The dense attention path (ops/attention.py einsum/softmax) is the oracle:
blockwise streaming must match it to float tolerance, including gradients
and masked keys, across tiling regimes (batch-chunked, query-chunked,
kv-streamed)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.models import (
    Alphafold2Config,
    alphafold2_apply,
    alphafold2_init,
)
from alphafold2_tpu.ops.attention import (
    AttentionConfig,
    attention_apply,
    attention_init,
)
from alphafold2_tpu.ops.flash import blockwise_attention


def _dense_reference(q, k, v, key_bias, scale):
    logits = jnp.einsum("bihd,bjhd->bhij", q, k).astype(jnp.float32) * scale
    if key_bias is not None:
        logits = logits + key_bias[:, None, None, :]
    attn = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhij,bjhd->bihd", attn.astype(q.dtype), v)


@pytest.mark.parametrize(
    "B,i,j,tile_elems,kv_block",
    [
        (1, 64, 64, 1 << 30, 2048),  # single-shot fast path
        (1, 64, 64, 512, 2048),  # query-chunked
        (8, 16, 48, 256, 16),  # batch-chunked + kv-streamed
        (6, 33, 20, 128, 8),  # non-divisible i (padding) + kv padding
    ],
)
def test_blockwise_matches_dense(B, i, j, tile_elems, kv_block):
    h, dh = 2, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, i, h, dh))
    k = jax.random.normal(ks[1], (B, j, h, dh))
    v = jax.random.normal(ks[2], (B, j, h, dh))
    mask = jax.random.bernoulli(ks[3], 0.8, (B, j))
    mask = mask.at[:, 0].set(True)  # no fully-masked batch rows
    bias = jnp.where(mask, 0.0, float("-inf")).astype(jnp.float32)

    got = jax.jit(
        lambda q, k, v, b: blockwise_attention(
            q, k, v, b, scale=dh**-0.5, tile_elems=tile_elems, kv_block=kv_block
        )
    )(q, k, v, bias)
    want = _dense_reference(q, k, v, bias, dh**-0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.slow
def test_blockwise_gradients_match_dense():
    B, i, j, h, dh = 4, 24, 40, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, i, h, dh))
    k = jax.random.normal(ks[1], (B, j, h, dh))
    v = jax.random.normal(ks[2], (B, j, h, dh))
    mask = jax.random.bernoulli(ks[3], 0.7, (B, j)).at[:, 0].set(True)
    bias = jnp.where(mask, 0.0, float("-inf")).astype(jnp.float32)

    def loss_block(q, k, v):
        o = blockwise_attention(
            q, k, v, bias, scale=dh**-0.5, tile_elems=256, kv_block=16
        )
        return jnp.sum(jnp.sin(o))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(_dense_reference(q, k, v, bias, dh**-0.5)))

    g1 = jax.jit(jax.grad(loss_block, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_fully_masked_keys_give_zeros():
    B, i, j, h, dh = 2, 8, 12, 2, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, i, h, dh))
    k = jax.random.normal(ks[1], (B, j, h, dh))
    v = jax.random.normal(ks[2], (B, j, h, dh))
    bias = jnp.full((B, j), float("-inf"), jnp.float32)
    out = jax.jit(
        lambda q, k, v, b: blockwise_attention(q, k, v, b, scale=dh**-0.5)
    )(q, k, v, bias)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), 0.0)

    # gradients stay finite through the all-masked edge case
    g = jax.jit(jax.grad(
        lambda q: jnp.sum(blockwise_attention(q, k, v, bias, scale=dh**-0.5))
    ))(q)
    assert np.isfinite(np.asarray(g)).all()


def test_attention_apply_flash_matches_dense():
    """cfg.flash=True must reproduce the dense path (valid rows) through the
    full attention_apply op, self- and cross-attention."""
    cfg_d = AttentionConfig(dim=32, heads=2, dim_head=8, flash=False)
    cfg_f = AttentionConfig(dim=32, heads=2, dim_head=8, flash=True)
    params = attention_init(jax.random.PRNGKey(0), cfg_d)
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (2, 24, 32))
    ctx = jax.random.normal(ks[1], (2, 18, 32))
    mask = jnp.ones((2, 24), bool).at[0, -4:].set(False)
    cmask = jnp.ones((2, 18), bool).at[1, -3:].set(False)

    # self-attention: compare on valid query rows only (dense gives masked
    # rows uniform-attention garbage, flash gives normal garbage)
    o_d = jax.jit(lambda p, x, m: attention_apply(p, cfg_d, x, mask=m))(params, x, mask)
    o_f = jax.jit(lambda p, x, m: attention_apply(p, cfg_f, x, mask=m))(params, x, mask)
    valid = np.asarray(mask)
    np.testing.assert_allclose(
        np.asarray(o_f)[valid], np.asarray(o_d)[valid], atol=1e-5
    )

    # cross-attention with context mask
    o_d = jax.jit(
        lambda p, x, c, m, cm: attention_apply(p, cfg_d, x, context=c, mask=m, context_mask=cm)
    )(params, x, ctx, mask, cmask)
    o_f = jax.jit(
        lambda p, x, c, m, cm: attention_apply(p, cfg_f, x, context=c, mask=m, context_mask=cm)
    )(params, x, ctx, mask, cmask)
    np.testing.assert_allclose(
        np.asarray(o_f)[valid], np.asarray(o_d)[valid], atol=1e-5
    )


@pytest.mark.slow
def test_aligned_cross_mode_full_model():
    """cross_attn_mode='aligned' runs the full model (seq len a multiple of
    MSA cols), yields finite outputs and gradients, and differs from flat
    (it is a different, documented connectivity)."""
    base = dict(dim=32, depth=2, heads=2, dim_head=8, max_seq_len=64)
    cfg_flat = Alphafold2Config(**base, cross_attn_mode="flat")
    cfg_al = Alphafold2Config(**base, cross_attn_mode="aligned")
    params = alphafold2_init(jax.random.PRNGKey(0), cfg_flat)

    rs = np.random.RandomState(0)
    seq = jnp.asarray(rs.randint(0, 21, size=(1, 24)))
    msa = jnp.asarray(rs.randint(0, 21, size=(1, 3, 12)))  # 24 = 2 * 12
    mask = jnp.ones((1, 24), bool)
    msa_mask = jnp.ones((1, 3, 12), bool)

    o_flat = alphafold2_apply(params, cfg_flat, seq, msa, mask=mask, msa_mask=msa_mask)
    o_al = alphafold2_apply(params, cfg_al, seq, msa, mask=mask, msa_mask=msa_mask)
    assert o_al.shape == o_flat.shape
    assert np.isfinite(np.asarray(o_al)).all()
    assert not np.allclose(np.asarray(o_al), np.asarray(o_flat))

    def loss(p):
        return jnp.sum(
            jnp.square(alphafold2_apply(p, cfg_al, seq, msa, mask=mask, msa_mask=msa_mask))
        )

    g = jax.grad(loss)(params)
    leaves = jax.tree_util.tree_leaves(g)
    assert all(np.isfinite(np.asarray(l)).all() for l in leaves)
    # cross-attention params receive gradient signal in aligned mode
    gnorm = sum(float(jnp.sum(jnp.abs(l))) for l in leaves)
    assert gnorm > 0


@pytest.mark.slow
def test_aligned_mode_reversible_consistent():
    """Aligned cross-attn inside the reversible trunk: reverse=True grads
    match plain autodiff (the reference's reversible parity contract,
    tests/test_reversible.py:48-52, under the new mode)."""
    from alphafold2_tpu.models.reversible import (
        reversible_trunk_apply,
        reversible_trunk_init,
    )

    cfg = Alphafold2Config(
        dim=16, depth=2, heads=2, dim_head=8, max_seq_len=32,
        reversible=True, cross_attn_mode="aligned",
    )
    stacked = reversible_trunk_init(jax.random.PRNGKey(0), cfg)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    x = jax.random.normal(ks[0], (1, 12, 12, 16))
    m = jax.random.normal(ks[1], (1, 3, 6, 16))  # 12 = 2 * 6

    def loss(p, reverse):
        xo, mo = reversible_trunk_apply(p, cfg, x, m, reverse=reverse)
        return jnp.sum(jnp.square(xo)) + jnp.sum(jnp.square(mo))

    g_rev = jax.grad(lambda p: loss(p, True))(stacked)
    g_ref = jax.grad(lambda p: loss(p, False))(stacked)
    for a, b in zip(jax.tree_util.tree_leaves(g_rev), jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_aligned_mode_rejects_misaligned_shapes():
    cfg = Alphafold2Config(
        dim=16, depth=1, heads=2, dim_head=8, max_seq_len=32,
        cross_attn_mode="aligned",
    )
    params = alphafold2_init(jax.random.PRNGKey(0), cfg)
    seq = jnp.zeros((1, 14), jnp.int32)
    msa = jnp.zeros((1, 2, 9), jnp.int32)  # 14 % 9 != 0
    with pytest.raises(ValueError, match="aligned cross-attention"):
        # jit: the shape check raises at trace time, skipping eager
        # execution of the embedding prefix
        jax.jit(lambda p, s, m: alphafold2_apply(p, cfg, s, m))(params, seq, msa)


def test_batch_chunked_attention_matches_dense():
    """cfg.batch_chunk must reproduce the unchunked op exactly (self and
    cross, masks, non-divisible batch)."""
    cfg0 = AttentionConfig(dim=32, heads=2, dim_head=8, batch_chunk=0)
    cfgc = AttentionConfig(dim=32, heads=2, dim_head=8, batch_chunk=4)
    params = attention_init(jax.random.PRNGKey(0), cfg0)
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    B = 10  # not a multiple of the chunk
    x = jax.random.normal(ks[0], (B, 12, 32))
    ctx = jax.random.normal(ks[1], (B, 7, 32))
    mask = jax.random.bernoulli(ks[2], 0.8, (B, 12)).at[:, 0].set(True)
    cmask = jax.random.bernoulli(ks[3], 0.8, (B, 7)).at[:, 0].set(True)

    o0 = jax.jit(lambda p, x, m: attention_apply(p, cfg0, x, mask=m))(params, x, mask)
    oc = jax.jit(lambda p, x, m: attention_apply(p, cfgc, x, mask=m))(params, x, mask)
    np.testing.assert_allclose(np.asarray(oc), np.asarray(o0), atol=1e-5)

    o0 = jax.jit(
        lambda p, x, c, cm: attention_apply(p, cfg0, x, context=c, context_mask=cm)
    )(params, x, ctx, cmask)
    oc = jax.jit(
        lambda p, x, c, cm: attention_apply(p, cfgc, x, context=c, context_mask=cm)
    )(params, x, ctx, cmask)
    np.testing.assert_allclose(np.asarray(oc), np.asarray(o0), atol=1e-5)

    # gradients flow and match
    def loss(p, cfg):
        return jnp.sum(jnp.sin(attention_apply(p, cfg, x, context=ctx, context_mask=cmask)))

    g0 = jax.jit(jax.grad(loss), static_argnums=1)(params, cfg0)
    gc = jax.jit(jax.grad(loss), static_argnums=1)(params, cfgc)
    for a, b in zip(jax.tree_util.tree_leaves(gc), jax.tree_util.tree_leaves(g0)):
        # recompute-order float noise only
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_kernel_disable_env_var(monkeypatch):
    """`AF2_KERNEL_BACKEND=off` downgrades auto-dispatch to XLA streaming
    (the way out when a kernel compile regresses on chip).

    Off-TPU the auto path never reaches the kernel, so the TPU platform
    gate is faked: the negative control (no env var -> kernel invoked)
    proves the fake actually routes to the kernel, making the env-var
    branch non-vacuous."""
    import alphafold2_tpu.ops.flash as flash_mod
    from alphafold2_tpu.ops import dispatch, flash_kernel

    calls = []

    def spy_kernel(q, k, v, bias, scale, qb=None, kb=None):
        calls.append("kernel")
        return jnp.zeros(q.shape, q.dtype)

    class FakeTpu:
        platform = "tpu"

    monkeypatch.setattr(flash_mod.jax, "devices", lambda: [FakeTpu()])
    monkeypatch.setattr(flash_kernel, "flash_attention_tpu", spy_kernel)
    monkeypatch.setattr(flash_kernel, "supported", lambda *a: True)
    # short-j auto-dispatch prefers XLA streaming (the measured crossover);
    # zero it here so these tiny shapes reach the kernel
    monkeypatch.setattr(dispatch, "_FLASH_KERNEL_MIN_J", 0)
    for name in ("AF2_KERNEL_BACKEND", "AF2_KERNEL_BACKEND_FLASH_ATTENTION"):
        monkeypatch.delenv(name, raising=False)

    from alphafold2_tpu.ops.flash import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 16, 2, 8))
    k = jax.random.normal(ks[1], (2, 16, 2, 8))
    v = jax.random.normal(ks[2], (2, 16, 2, 8))

    # negative control: auto + "TPU" -> kernel dispatched
    flash_attention(q, k, v, use_kernel="auto")
    assert calls == ["kernel"]

    # off -> auto downgrades to XLA streaming, kernel untouched
    monkeypatch.setenv("AF2_KERNEL_BACKEND", "off")
    out = flash_attention(q, k, v, use_kernel="auto")
    assert calls == ["kernel"]
    assert np.isfinite(np.asarray(out)).all()

    # a per-op "auto" restores the heuristic under the global switch
    monkeypatch.setenv("AF2_KERNEL_BACKEND_FLASH_ATTENTION", "auto")
    flash_attention(q, k, v, use_kernel="auto")
    assert calls == ["kernel", "kernel"]


def test_kernel_auto_min_j_heuristic(monkeypatch):
    """auto-mode dispatch is shape-aware: "auto" takes the kernel from
    the lowest key length it was measured to win at (j = 1152, the
    whole-row form: 6.2 against 17.9 us a row, PERF.md section 5) and
    leaves the short crosses to XLA streaming. use_kernel=True still
    forces it."""
    import alphafold2_tpu.ops.flash as flash_mod
    from alphafold2_tpu.ops import dispatch, flash_kernel

    class FakeTpu:
        platform = "tpu"

    monkeypatch.setattr(flash_mod.jax, "devices", lambda: [FakeTpu()])
    monkeypatch.setattr(flash_kernel, "supported", lambda *a: True)
    # an inherited override must not leak into the heuristic's asserts
    for name in ("AF2_KERNEL_BACKEND", "AF2_KERNEL_BACKEND_FLASH_ATTENTION"):
        monkeypatch.delenv(name, raising=False)

    def takes_kernel(i, j, dh, use_kernel):
        return dispatch.resolve("flash_attention", request=use_kernel,
                                i=i, j=j, dh=dh) == dispatch.ARM_PALLAS_TPU

    # short-j auto -> streaming; from the pair stream's axial shape up
    # -> kernel
    min_j = dispatch._FLASH_KERNEL_MIN_J
    assert min_j == 1152
    assert not takes_kernel(128, 864, 64, "auto")
    assert not takes_kernel(3456, 32, 64, "auto")
    assert not takes_kernel(1152, min_j - 1, 64, "auto")
    assert takes_kernel(1152, min_j, 64, "auto")
    assert takes_kernel(1152, 4096, 64, "auto")
    # forcing bypasses the heuristic at any shape
    assert takes_kernel(16, 16, 8, True)
    # the override channel re-admits short-j
    monkeypatch.setenv("AF2_KERNEL_BACKEND_FLASH_ATTENTION", "pallas_tpu")
    assert takes_kernel(128, 864, 64, "auto")
    # a name that is no arm fails loudly, not silently-default
    monkeypatch.setenv("AF2_KERNEL_BACKEND_FLASH_ATTENTION", "many")
    with pytest.raises(ValueError):
        takes_kernel(128, 864, 64, "auto")
