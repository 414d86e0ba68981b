"""int8 weight-quantized inference arm (ops/quant.py + ops/quant_kernel.py):
interpret-mode parity matrix for the fused-dequant Pallas matmul vs the
XLA dequant reference (shapes x activation dtype x per-channel/per-tensor
scales, zero-scale and all-negative channels), PTQ tree transforms over
the real model trees (sequential AND depth-stacked reversible), dispatch
gating, the inference-only backward, training-entry rejection, and the
chip-free residency accounting the bench legs record.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.models import (
    Alphafold2Config,
    alphafold2_apply,
    alphafold2_init,
)
from alphafold2_tpu.ops.quant import (
    default_quant_select,
    dequantize_tree,
    dequantize_weight,
    is_quantized_linear,
    iter_linear_dicts,
    quant_matmul,
    quant_matmul_xla,
    quantize_tree,
    quantize_weight,
    quantized_path_bytes,
    reject_quant_training,
    tree_weight_bytes,
)
from alphafold2_tpu.ops.quant_kernel import supported_quant


def _rand_w(shape, seed=0, scale=1.0):
    return (np.random.RandomState(seed).randn(*shape) * scale).astype(
        np.float32
    )


# ---------------------------------------------------------------- PTQ math


def test_quantize_roundtrip_error_bound():
    w = _rand_w((48, 80))
    q, s = quantize_weight(w)
    assert q.dtype == jnp.int8 and s.shape == (80,)
    err = np.abs(np.asarray(dequantize_weight(q, s)) - w)
    # symmetric rounding grid: per-element error <= scale/2 per channel
    assert (err <= np.asarray(s)[None, :] / 2 + 1e-7).all()


def test_quantize_zero_channel_roundtrips_exact_zeros():
    w = _rand_w((32, 8))
    w[:, 3] = 0.0  # the near-open gate init w=0 case
    q, s = quantize_weight(w)
    assert float(np.asarray(s)[3]) == 0.0
    deq = np.asarray(dequantize_weight(q, s))
    np.testing.assert_array_equal(deq[:, 3], 0.0)


def test_quantize_all_negative_channel():
    w = _rand_w((32, 8))
    w[:, 5] = -np.abs(w[:, 5]) - 0.1
    q, s = quantize_weight(w)
    deq = np.asarray(dequantize_weight(q, s))
    assert (deq[:, 5] < 0).all()
    assert np.abs(deq[:, 5] - w[:, 5]).max() <= float(np.asarray(s)[5]) / 2 + 1e-7
    # extreme magnitudes hit the symmetric endpoints, never -128
    assert int(np.asarray(q).min()) >= -127


@pytest.mark.parametrize("per_channel", [True, False])
def test_quantize_stacked_matches_per_slice(per_channel):
    # the reversible trunk's (depth, d_in, d_out) layout: stacked
    # quantization must equal quantizing each slice independently, so
    # lax.scan slicing a quantized tree is exact
    w = _rand_w((3, 24, 16), seed=2)
    q, s = quantize_weight(w, per_channel=per_channel)
    for d in range(3):
        qd, sd = quantize_weight(w[d], per_channel=per_channel)
        np.testing.assert_array_equal(np.asarray(q[d]), np.asarray(qd))
        np.testing.assert_array_equal(np.asarray(s[d]), np.asarray(sd))
    np.testing.assert_allclose(
        np.asarray(dequantize_weight(q, s)), w,
        atol=float(np.abs(w).max()) / 254 + 1e-7,
    )


def test_quantize_rejects_vectors():
    with pytest.raises(ValueError, match="2-D dense weight"):
        quantize_weight(np.ones(8, np.float32))


# ------------------------------------------------- kernel parity matrix


@pytest.mark.parametrize("per_channel", [True, False])
@pytest.mark.parametrize(
    "m,k,n,dtype",
    [
        (16, 32, 16, jnp.float32),    # single tile
        (40, 48, 80, jnp.float32),    # padding on every axis
        (256, 128, 256, jnp.float32),  # multiple blocks, no padding
        (40, 48, 80, jnp.bfloat16),   # the TPU activation dtype
        (1, 256, 8, jnp.float32),     # degenerate rows/channels
    ],
)
def test_kernel_matches_xla_reference(m, k, n, dtype, per_channel):
    w = _rand_w((k, n), seed=m + n)
    w[:, n // 2] = 0.0  # a zero-scale channel inside the grid
    q, s = quantize_weight(w, per_channel=per_channel)
    x = jnp.asarray(_rand_w((m, k), seed=1), dtype)
    got = quant_matmul(x, q, s, use_kernel=True)
    want = quant_matmul(x, q, s, use_kernel=False)
    assert got.dtype == dtype and got.shape == (m, n)
    atol = 1e-4 * k if dtype == jnp.bfloat16 else 1e-5 * k
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol
    )


def test_xla_arm_is_the_dequant_math():
    # the reference arm IS x @ dequant(qw): pin it against the plain
    # einsum so both arms anchor to the same oracle
    w = _rand_w((48, 32), seed=9)
    q, s = quantize_weight(w)
    x = jnp.asarray(_rand_w((12, 48), seed=3))
    got = np.asarray(quant_matmul_xla(x, q, jnp.asarray(s)))
    want = np.asarray(x) @ np.asarray(dequantize_weight(q, s))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_quant_matmul_leading_batch_dims():
    w = _rand_w((24, 16), seed=4)
    q, s = quantize_weight(w)
    x = jnp.asarray(_rand_w((2, 5, 24), seed=5))
    got = quant_matmul(x, q, s, use_kernel=True)
    assert got.shape == (2, 5, 16)
    want = quant_matmul(x.reshape(10, 24), q, s, use_kernel=False)
    np.testing.assert_allclose(
        np.asarray(got).reshape(10, 16), np.asarray(want), atol=1e-4
    )


def test_quant_matmul_rejects_stacked_weights_loudly():
    w = _rand_w((2, 24, 16), seed=6)
    q, s = quantize_weight(w)
    with pytest.raises(ValueError, match="lax.scan"):
        quant_matmul(jnp.ones((4, 24)), q, s)


def test_quant_matmul_mismatched_features_raise():
    q, s = quantize_weight(_rand_w((24, 16)))
    with pytest.raises(ValueError, match="feature dim"):
        quant_matmul(jnp.ones((4, 23)), q, s)


def test_supported_quant_bounds():
    assert supported_quant(1024, 2048, 64)
    assert supported_quant(16, 16, 16, jnp.bfloat16)
    assert not supported_quant(16, 1 << 25, 64)
    assert not supported_quant(0, 16, 16)
    assert not supported_quant(16, 16, 16, jnp.int8)
    assert not supported_quant(16, 16, 16, jnp.float16)


def test_forced_kernel_on_unsupported_dtype_raises():
    q, s = quantize_weight(_rand_w((16, 16)))
    with pytest.raises(ValueError, match="quant kernel does not support"):
        quant_matmul(jnp.ones((4, 16), jnp.float16), q, s, use_kernel=True)


def test_env_overrides_route_auto_dispatch(monkeypatch):
    # AF2_KERNEL_BACKEND_QUANT_MATMUL=pallas_tpu must take the kernel even
    # off-TPU, "off" the XLA arm; both arms agree numerically, so the
    # route `quant_matmul` itself took is read from the dispatcher's tally
    from alphafold2_tpu.ops import dispatch

    q, s = quantize_weight(_rand_w((16, 8)))
    x = jnp.ones((8, 16))

    def route(**kw):
        dispatch.reset_decisions()
        quant_matmul(x, q, s, **kw)
        return [entry.split(" @ ")[0] for entry in dispatch.decisions()]

    for name in ("AF2_KERNEL_BACKEND", "AF2_KERNEL_BACKEND_QUANT_MATMUL"):
        monkeypatch.delenv(name, raising=False)
    assert route() == ["quant_matmul -> xla_ref"]  # this host's heuristic
    monkeypatch.setenv("AF2_KERNEL_BACKEND_QUANT_MATMUL", "pallas_tpu")
    assert route() == ["quant_matmul -> pallas_tpu"]
    monkeypatch.setenv("AF2_KERNEL_BACKEND_QUANT_MATMUL", "off")
    assert route() == ["quant_matmul -> xla_ref"]
    monkeypatch.setenv("AF2_KERNEL_BACKEND_QUANT_MATMUL", "bogus")
    with pytest.raises(ValueError, match="unknown backend arm 'bogus'"):
        route()
    # explicit use_kernel wins over the switch (forcing is loud) and is
    # no decision of the dispatcher's
    monkeypatch.setenv("AF2_KERNEL_BACKEND_QUANT_MATMUL", "off")
    assert route(use_kernel=True) == []
    assert dispatch.resolve("quant_matmul", request=True, m=8, k=16, n=8,
                            x_dtype=jnp.float32) == "pallas_tpu"


def test_backward_through_quant_matmul_raises():
    q, s = quantize_weight(_rand_w((16, 8)))

    def loss(x):
        return jnp.sum(quant_matmul(x, q, s, use_kernel=False))

    with pytest.raises(NotImplementedError, match="inference-only"):
        jax.grad(loss)(jnp.ones((4, 16)))


# ------------------------------------------------------- tree transforms


SEQ_CFG = Alphafold2Config(
    dim=32, depth=2, heads=2, dim_head=16, max_seq_len=32,
    msa_tie_row_attn=True, cross_attn_compress_ratio=2,
)
REV_CFG = dataclasses.replace(SEQ_CFG, reversible=True)


@pytest.fixture(scope="module", params=["sequential", "reversible"])
def model_arm(request):
    cfg = SEQ_CFG if request.param == "sequential" else REV_CFG
    params = alphafold2_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_quantize_tree_selects_trunk_only(model_arm):
    cfg, params = model_arm
    qp = quantize_tree(params)
    quantized = [p for p, d in iter_linear_dicts(qp) if is_quantized_linear(d)]
    assert quantized, "no trunk weight was quantized"
    for path in quantized:
        assert "trunk" in path.split("/")
        assert "compress" not in path.split("/")
    # everything outside the trunk keeps its fp32 "w"
    untouched = [
        p for p, d in iter_linear_dicts(qp)
        if "w" in d and "trunk" in p.split("/")
        and "compress" not in p.split("/") and d["w"].ndim >= 2
    ]
    assert untouched == []  # every selectable trunk weight was rewritten
    # the compress conv kernel stays a raw fp32 "w" (read directly by
    # ops/attention.py, never through linear())
    compress = [
        p for p, d in iter_linear_dicts(qp)
        if "compress" in p.split("/") and "w" in d
    ]
    assert compress


def test_quantize_tree_leaves_master_untouched(model_arm):
    cfg, params = model_arm
    before = jax.tree_util.tree_map(np.asarray, params)
    quantize_tree(params)
    jax.tree_util.tree_map(
        np.testing.assert_array_equal,
        before, jax.tree_util.tree_map(np.asarray, params),
    )


def test_int8_apply_equals_dequantized_reference(model_arm):
    cfg, params = model_arm
    qp = quantize_tree(params)
    rs = np.random.RandomState(0)
    seq = jnp.asarray(rs.randint(0, 21, (1, 16)))
    msa = jnp.asarray(rs.randint(0, 21, (1, 3, 16)))
    mask = jnp.ones((1, 16), bool)
    mmask = jnp.ones((1, 3, 16), bool)
    got = alphafold2_apply(qp, cfg, seq, msa, mask=mask, msa_mask=mmask)
    want = alphafold2_apply(
        dequantize_tree(qp), cfg, seq, msa, mask=mask, msa_mask=mmask
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5
    )
    # and the quantization error vs the fp32 master stays small
    ref = alphafold2_apply(params, cfg, seq, msa, mask=mask, msa_mask=mmask)
    assert float(np.abs(np.asarray(got) - np.asarray(ref)).max()) < 0.05


def test_int8_apply_under_jit(model_arm):
    # the serving engine AOT-compiles over the quantized tree: the whole
    # dispatch (including the kernel arm in interpret mode) must trace
    cfg, params = model_arm
    qp = quantize_tree(params)
    rs = np.random.RandomState(1)
    seq = jnp.asarray(rs.randint(0, 21, (1, 16)))
    msa = jnp.asarray(rs.randint(0, 21, (1, 3, 16)))
    eager = alphafold2_apply(qp, cfg, seq, msa)
    jitted = jax.jit(
        lambda p, s, m: alphafold2_apply(p, cfg, s, m)
    )(qp, seq, msa)
    np.testing.assert_allclose(
        np.asarray(jitted), np.asarray(eager), atol=2e-5
    )


def test_dequantize_tree_restores_structure(model_arm):
    cfg, params = model_arm
    restored = dequantize_tree(quantize_tree(params))
    assert jax.tree_util.tree_structure(
        restored
    ) == jax.tree_util.tree_structure(params)


def test_custom_select_overrides_default():
    params = alphafold2_init(jax.random.PRNGKey(0), SEQ_CFG)
    qp = quantize_tree(params, select=lambda path, w: False)
    assert not any(
        is_quantized_linear(d) for _, d in iter_linear_dicts(qp)
    )


def test_linear_dispatches_on_quantized_params():
    from alphafold2_tpu.ops.core import linear, linear_init

    params = linear_init(jax.random.PRNGKey(0), 24, 16)
    q, s = quantize_weight(params["w"])
    qparams = {"qw": q, "scale": s, "b": params["b"]}
    x = jnp.asarray(_rand_w((4, 24), seed=7))
    got = linear(qparams, x)
    want = x @ dequantize_weight(q, s) + params["b"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # compute-dtype contract: bf16 activations, bf16 out
    got16 = linear(qparams, x, dtype=jnp.bfloat16)
    assert got16.dtype == jnp.bfloat16


# ------------------------------------------ residency + training guard


def test_tree_weight_bytes_works_on_abstract_trees():
    shapes = jax.eval_shape(
        lambda k: alphafold2_init(k, REV_CFG), jax.random.PRNGKey(0)
    )
    concrete = alphafold2_init(jax.random.PRNGKey(0), REV_CFG)
    assert tree_weight_bytes(shapes) == tree_weight_bytes(concrete)
    qshapes = jax.eval_shape(quantize_tree, shapes)
    assert tree_weight_bytes(qshapes) < tree_weight_bytes(shapes)


def test_quantized_tensor_ratio_meets_acceptance_on_north_star():
    # ISSUE 8 acceptance: >= 3.5x byte reduction on the quantized tensors
    # for the north-star preset (int8 values + f32 per-channel scales vs
    # fp32), chip-free via eval_shape
    from alphafold2_tpu.training import north_star_e2e_config

    ecfg, _, _ = north_star_e2e_config(12)
    shapes = jax.eval_shape(
        lambda k: alphafold2_init(k, ecfg.model), jax.random.PRNGKey(0)
    )
    before, after = quantized_path_bytes(shapes)
    assert before / after >= 3.5
    # the post-PTQ accounting agrees with the pre-PTQ projection
    qshapes = jax.eval_shape(quantize_tree, shapes)
    b2, a2 = quantized_path_bytes(qshapes)
    assert a2 == after


def test_reject_quant_training_entry_points():
    from alphafold2_tpu.training import (
        TrainConfig,
        e2e_train_state_init,
        make_train_step,
        north_star_e2e_config,
        train_state_init,
    )

    int8_cfg = dataclasses.replace(SEQ_CFG, weight_dtype="int8")
    tcfg = TrainConfig(grad_accum=1)
    with pytest.raises(ValueError, match="inference-only"):
        train_state_init(jax.random.PRNGKey(0), int8_cfg, tcfg)
    with pytest.raises(ValueError, match="inference-only"):
        make_train_step(int8_cfg, tcfg)
    ecfg, _, _ = north_star_e2e_config(
        2, tier="smoke", model_overrides={"weight_dtype": "int8"}
    )
    with pytest.raises(ValueError, match="inference-only"):
        e2e_train_state_init(jax.random.PRNGKey(0), ecfg, tcfg)
    with pytest.raises(ValueError, match="inference-only"):
        make_train_step(ecfg, tcfg)  # E2EConfig unwraps to .model


def test_axis_accum_step_rejects_int8():
    from alphafold2_tpu.training import TrainConfig
    from alphafold2_tpu.training.harness import make_axis_accum_train_step

    int8_cfg = dataclasses.replace(SEQ_CFG, weight_dtype="int8")
    with pytest.raises(ValueError, match="inference-only"):
        make_axis_accum_train_step(
            int8_cfg, TrainConfig(grad_accum=1), lambda *a: 0.0, "data"
        )


def test_config_validates_weight_dtype():
    with pytest.raises(ValueError, match="weight_dtype"):
        Alphafold2Config(dim=16, weight_dtype="int4")
    assert Alphafold2Config(dim=16, weight_dtype="int8").weight_dtype == "int8"
