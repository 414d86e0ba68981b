"""Fused-epilogue flash kernel (2-D pair-bias tiles + in-kernel sigmoid
output gate): interpret-mode parity matrix vs the dense einsum oracle and
the XLA streaming twin, forward and backward (including the real d_bias
and d_gate cotangents), across bias modes, masking, padding, and dtypes.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.ops.attention import (
    AttentionConfig,
    attention_apply,
    attention_init,
)
from alphafold2_tpu.ops.flash import flash_attention
from alphafold2_tpu.ops.flash_kernel import (
    flash_attention_fused,
    supported_fused,
)


def _dense(q, k, v, bias2d, gate, scale):
    """f32 oracle: full logits + softmax + optional sigmoid gate."""
    s = jnp.einsum(
        "bid,bjd->bij", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale + bias2d
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows -> zeros
    out = jnp.einsum("bij,bjd->bid", p, v.astype(jnp.float32))
    if gate is not None:
        out = out * jax.nn.sigmoid(gate.astype(jnp.float32))
    return out


def _inputs(BH, i, j, dh, dtype, seed=0, masked=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (BH, i, dh), dtype)
    k = jax.random.normal(ks[1], (BH, j, dh), dtype)
    v = jax.random.normal(ks[2], (BH, j, dh), dtype)
    bias = (jax.random.normal(ks[3], (BH, i, j)) * 0.5).astype(jnp.float32)
    if masked:
        # masked key columns + one FULLY-masked query row (zero attention
        # mass: out must be exact zeros, lse +inf internally)
        bias = bias.at[:, :, -3:].set(-jnp.inf).at[0, 1, :].set(-jnp.inf)
    gate = jax.random.normal(ks[4], (BH, i, dh), dtype)
    return q, k, v, bias, gate


def test_supported_fused_mirrors_plain_bounds():
    assert supported_fused(1024, 2048, 64)
    assert not supported_fused(16, 10 ** 7, 64)
    assert not supported_fused(16, 16, 7)


@pytest.mark.parametrize("gated", [False, True])
@pytest.mark.parametrize(
    "BH,i,j,qb,kb,dtype",
    [
        (2, 32, 32, 16, 16, jnp.float32),   # multiple blocks, no padding
        (1, 40, 56, 16, 16, jnp.float32),   # padding on BOTH axes
        (2, 16, 16, 16, 16, jnp.float32),   # single tile
        (2, 32, 32, 16, 16, jnp.bfloat16),  # the TPU operand dtype
    ],
)
def test_fused_2d_bias_matches_dense(BH, i, j, qb, kb, dtype, gated):
    q, k, v, bias, gate = _inputs(BH, i, j, 8, dtype)
    g = gate if gated else None
    got = flash_attention_fused(q, k, v, bias, 8 ** -0.5, gate=g, qb=qb, kb=kb)
    assert got.dtype == dtype
    want = _dense(q, k, v, bias, g, 8 ** -0.5)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=atol
    )


def test_fused_keyside_bias_plus_gate_matches_dense():
    # the (bias2d=False, gated=True) combination: the model's attn_gate
    # path — key-side mask bias stays row-resident, gate fuses
    BH, i, j, dh = 2, 24, 40, 8
    q, k, v, _, gate = _inputs(BH, i, j, dh, jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(7), 1)[0]
    key_bias = jnp.where(
        jax.random.bernoulli(ks, 0.8, (BH, j)), 0.0, -jnp.inf
    ).astype(jnp.float32)
    got = flash_attention_fused(
        q, k, v, key_bias, dh ** -0.5, gate=gate, qb=16, kb=16
    )
    want = _dense(
        q, k, v, jnp.broadcast_to(key_bias[:, None, :], (BH, i, j)),
        gate, dh ** -0.5,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize(
    "dtype",
    [jnp.float32, pytest.param(jnp.bfloat16, marks=pytest.mark.slow)],
)
def test_fused_gradients_match_dense(dtype):
    # full cotangent coverage: dq/dk/dv, the REAL d_bias (2-D mode — pair
    # biases are learned projections), and d_gate; padded blocks + masked
    # rows included
    BH, i, j, dh = 1, 40, 24, 8
    q, k, v, bias, gate = _inputs(BH, i, j, dh, dtype, seed=1)

    def loss_kernel(q, k, v, b, g):
        out = flash_attention_fused(
            q, k, v, b, dh ** -0.5, gate=g, qb=16, kb=16
        )
        return jnp.sum(jnp.cos(out.astype(jnp.float32)))

    def loss_dense(q, k, v, b, g):
        return jnp.sum(jnp.cos(_dense(q, k, v, b, g, dh ** -0.5)))

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2, 3, 4))(q, k, v, bias, gate)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2, 3, 4))(q, k, v, bias, gate)
    atol = 3e-5 if dtype == jnp.float32 else 5e-2
    for name, a, b in zip(("dq", "dk", "dv", "dbias", "dgate"), gk, gd):
        aa, bb = np.asarray(a, np.float32), np.asarray(b, np.float32)
        fin = np.isfinite(bb)  # dense oracle emits nan/inf on -inf bias
        np.testing.assert_allclose(
            np.where(fin, aa, 0.0), np.where(fin, bb, 0.0),
            atol=atol, err_msg=name,
        )


def test_flash_attention_dispatch_fused_kernel_vs_xla():
    # the public entry: pair_bias + gate through the forced kernel
    # (interpret mode) vs the XLA streaming twin — the dispatch-level
    # parity the dryrun fused_gate leg also pins
    B, i, j, h, dh = 2, 24, 40, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    q, k, v, gate = (
        jax.random.normal(kk, (B, n, h, dh))
        for kk, n in zip(ks[:4], (i, j, j, i))
    )
    key_bias = jnp.where(
        jax.random.bernoulli(ks[4], 0.85, (B, j)), 0.0, -jnp.inf
    ).astype(jnp.float32)
    pair_bias = jax.random.normal(ks[5], (B, h, i, j)) * 0.5
    for pb in (None, pair_bias):
        got = flash_attention(
            q, k, v, key_bias, pair_bias=pb, gate=gate, use_kernel=True
        )
        want = flash_attention(
            q, k, v, key_bias, pair_bias=pb, gate=gate, use_kernel=False
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5
        )


def test_gated_attention_apply_paths_agree():
    # cfg.gate at the attention-op level: dense, flash-XLA, and
    # batch-chunked paths agree on VALID rows (masked query rows keep the
    # documented dense-vs-flash divergence), and grads flow through the
    # gate projection on both paths
    cfg_dense = AttentionConfig(dim=16, heads=2, dim_head=8, gate=True,
                                flash=False)
    cfg_flash = dataclasses.replace(cfg_dense, flash=True)
    cfg_chunk = dataclasses.replace(cfg_flash, batch_chunk=2)
    params = attention_init(jax.random.PRNGKey(0), cfg_dense)
    assert "to_gate" in params
    # non-trivial gate weights (init is the near-open w=0, b=1)
    params["to_gate"]["w"] = (
        jax.random.normal(jax.random.PRNGKey(9), (16, 16)) * 0.3
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 12, 16))
    mask = jnp.ones((3, 12), bool).at[:, -2:].set(False)
    w = mask[..., None].astype(jnp.float32)

    outs = {
        name: attention_apply(params, cfg, x, mask=mask) * w
        for name, cfg in (
            ("dense", cfg_dense), ("flash", cfg_flash), ("chunk", cfg_chunk),
        )
    }
    np.testing.assert_allclose(
        np.asarray(outs["dense"]), np.asarray(outs["flash"]), atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(outs["flash"]), np.asarray(outs["chunk"]), atol=2e-5
    )

    def loss(cfg):
        return lambda p: jnp.sum(
            (attention_apply(p, cfg, x, mask=mask) * w) ** 2
        )

    gd = jax.grad(loss(cfg_dense))(params)
    gf = jax.grad(loss(cfg_flash))(params)
    assert float(jnp.abs(gd["to_gate"]["w"]).max()) > 0  # gate learns
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-4
        ),
        gd, gf,
    )


def test_gate_init_is_near_open():
    # w=0, b=1: a fresh gate multiplies by sigmoid(1) uniformly, so the
    # gated op is the ungated op scaled — enabling the flag on an
    # existing recipe starts from a benign point
    cfg = AttentionConfig(dim=16, heads=2, dim_head=8, gate=True)
    cfg_off = dataclasses.replace(cfg, gate=False)
    params = attention_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    got = attention_apply(params, cfg, x)
    # same params minus the gate, sigmoid(1)-scaled before to_out is NOT
    # representable post-hoc (to_out has a bias), so compare against the
    # gated op with the gate forced wide open instead
    open_params = dict(params)
    open_params["to_gate"] = {
        "w": params["to_gate"]["w"],
        "b": jnp.full_like(params["to_gate"]["b"], 20.0),  # sigmoid ~ 1
    }
    want_open = attention_apply(open_params, cfg, x)
    ungated = attention_apply(params, cfg_off, x)
    np.testing.assert_allclose(
        np.asarray(want_open), np.asarray(ungated), atol=1e-5
    )
    # and the default init sits between: strictly attenuated, same sign
    # structure as the open gate at sigmoid(1)
    assert float(jnp.abs(got - ungated).max()) > 0


def test_config_gate_excludes_sparse():
    from alphafold2_tpu.models import Alphafold2Config

    with pytest.raises(ValueError, match="attn_gate"):
        Alphafold2Config(dim=16, attn_gate=True, sparse_self_attn=True)


@pytest.mark.parametrize("mode", ["flat", "aligned"])
def test_sp_trunk_gated_matches_replicated(mode):
    # the SP trunk's MANUAL projection paths (tied-row sharded logits,
    # ring cross-attention) carry their own gate epilogues — parity with
    # the replicated gated trunk pins them, in both cross modes
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    from alphafold2_tpu.models import Alphafold2Config
    from alphafold2_tpu.models.trunk import (
        sequential_trunk_apply,
        trunk_layer_init,
    )
    from alphafold2_tpu.parallel import make_mesh, sp_trunk_apply

    cfg = Alphafold2Config(
        dim=16, depth=1, heads=2, dim_head=8, max_seq_len=64,
        msa_tie_row_attn=True, attn_gate=True, cross_attn_mode=mode,
        cross_attn_compress_ratio=2,
    )
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    layers = [trunk_layer_init(keys[2], cfg)]

    def randomize(p, salt=0):
        # non-trivial gate weights (the near-open init's w=0 would let a
        # dropped gate projection pass parity silently)
        for k, v in p.items():
            if k == "to_gate":
                v["w"] = jax.random.normal(
                    jax.random.PRNGKey(salt), v["w"].shape
                ) * 0.3
            elif isinstance(v, dict):
                randomize(v, salt + 1)

    for layer in layers:
        randomize(layer)
    x = jax.random.normal(keys[0], (1, 16, 16, 16))
    m = jax.random.normal(keys[1], (1, 8, 16, 16))
    mesh = make_mesh({"seq": 8})
    want = jax.jit(
        lambda ls, a, b: sequential_trunk_apply(ls, cfg, a, b)
    )(layers, x, m)
    got = jax.jit(
        lambda ls, a, b: sp_trunk_apply(ls, cfg, a, b, mesh)
    )(layers, x, m)
    for a, b in zip(want, got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
