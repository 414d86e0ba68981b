"""The five BASELINE.md capability configs, exercised end to end (miniature
shapes): forward + gradients finite through every flag combination the
reference supports. Config-by-config artifact for the parity audit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.models import (
    Alphafold2Config,
    alphafold2_apply,
    alphafold2_front,
    alphafold2_head,
    alphafold2_init,
)


def _run(cfg, seq_len=16, rows=3, cols=8, templates_T=0):
    params = alphafold2_init(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(0)
    seq = jnp.asarray(rs.randint(0, 21, size=(1, seq_len)))
    msa = jnp.asarray(rs.randint(0, 21, size=(1, rows, cols)))
    kw = {}
    if templates_T:
        kw["templates"] = jnp.asarray(
            rs.randint(0, 37, size=(1, templates_T, seq_len, seq_len))
        )
        kw["templates_mask"] = jnp.ones((1, templates_T, seq_len, seq_len), bool)

    def loss(p):
        out = alphafold2_apply(p, cfg, seq, msa, **kw)
        return jnp.sum(jnp.square(out))

    # jit: eager per-primitive dispatch costs ~3x trace+compile+run for
    # these program sizes on the CPU test box (and production always jits).
    # EXCEPT reversible configs: their scanned custom_vjp body compiles
    # once as an eager scan but gets re-optimized inside an outer jit,
    # which measures ~2.5x slower here — keep those eager.
    grad_fn = jax.value_and_grad(loss)
    if not cfg.reversible:
        grad_fn = jax.jit(grad_fn)
    val, grads = grad_fn(params)
    assert np.isfinite(float(val))
    for leaf in jax.tree_util.tree_leaves(grads):
        assert np.isfinite(np.asarray(leaf)).all()


def test_front_trunk_head_composition_equals_apply():
    """alphafold2_front -> trunk -> alphafold2_head IS alphafold2_apply —
    the decomposition contract the segmented multi-execution step
    (training/segmented.py) is built on."""
    from alphafold2_tpu.models.reversible import reversible_trunk_apply

    cfg = Alphafold2Config(
        dim=32, depth=2, heads=2, dim_head=8, max_seq_len=64,
        reversible=True, msa_tie_row_attn=True,
    )
    params = alphafold2_init(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(0)
    seq = jnp.asarray(rs.randint(0, 21, size=(1, 12)))
    msa = jnp.asarray(rs.randint(0, 21, size=(1, 3, 12)))
    mask = jnp.ones((1, 12), bool)
    rng = jax.random.PRNGKey(5)

    whole = alphafold2_apply(params, cfg, seq, msa, mask=mask, rng=rng)

    x, m, x_mask, m_mask, rng_trunk = alphafold2_front(
        params, cfg, seq, msa, mask=mask, rng=rng
    )
    x, m = reversible_trunk_apply(
        params["trunk"], cfg, x, m, x_mask=x_mask, msa_mask=m_mask,
        rng=rng_trunk,
    )
    composed = alphafold2_head(params, cfg, x)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(composed))


@pytest.mark.slow
def test_config1_readme_toy():
    # BASELINE config 1: plain dense forward (reference README.md:17-48)
    _run(Alphafold2Config(dim=32, depth=2, heads=2, dim_head=8, max_seq_len=32))


@pytest.mark.slow
def test_config2_reversible_dense():
    # BASELINE config 2: reversible trunk, dense self+cross
    _run(Alphafold2Config(
        dim=32, depth=2, heads=2, dim_head=8, max_seq_len=32, reversible=True,
    ))


@pytest.mark.slow
def test_config3_sparse_interleaved():
    # BASELINE config 3: interleaved block-sparse self-attention
    _run(Alphafold2Config(
        dim=32, depth=2, heads=2, dim_head=8, max_seq_len=32,
        sparse_self_attn=(True, False),
        sparse_block_size=4, sparse_num_random_blocks=1,
        sparse_num_local_blocks=2, sparse_use_kernel=False,
    ))


@pytest.mark.slow
def test_config4_templates_compress_tied():
    # BASELINE config 4: template tower + KV-compressed cross-attention +
    # tied-row MSA attention, all together
    _run(
        Alphafold2Config(
            dim=32, depth=2, heads=2, dim_head=8, max_seq_len=32,
            cross_attn_compress_ratio=3, msa_tie_row_attn=True,
        ),
        templates_T=2,
    )


@pytest.mark.slow
def test_config5_e2e_miniature():
    # BASELINE config 5 in miniature: the full structure pipeline — covered
    # in depth by tests/test_e2e.py and the multichip dryrun; here the
    # trunk-flag combination it uses (reversible + tied + compressed +
    # aligned cross)
    _run(Alphafold2Config(
        dim=32, depth=2, heads=2, dim_head=8, max_seq_len=32,
        reversible=True, msa_tie_row_attn=True,
        cross_attn_compress_ratio=2, cross_attn_mode="aligned",
    ), seq_len=16, rows=3, cols=8)


@pytest.mark.slow
def test_scan_layers_matches_unrolled():
    """cfg.scan_layers (segmented lax.scan over depth) must be numerically
    identical to the unrolled trunk — including mixed sparse flags and
    per-layer dropout keys."""
    from alphafold2_tpu.models.trunk import sequential_trunk_apply, trunk_layer_init

    base = dict(
        dim=16, depth=3, heads=2, dim_head=8, max_seq_len=32,
        sparse_self_attn=(True, False, False),
        sparse_block_size=4, sparse_num_random_blocks=1,
        sparse_num_local_blocks=2, sparse_use_kernel=False,
        attn_dropout=0.1, ff_dropout=0.1,
    )
    cfg_u = Alphafold2Config(**base, scan_layers=False)
    cfg_s = Alphafold2Config(**base, scan_layers=True)
    keys = jax.random.split(jax.random.PRNGKey(0), 2 + cfg_u.depth)
    layers = [trunk_layer_init(k, cfg_u) for k in keys[2:]]
    x = jax.random.normal(keys[0], (1, 8, 8, 16))
    m = jax.random.normal(keys[1], (1, 2, 8, 16))
    rng = jax.random.PRNGKey(7)

    want = sequential_trunk_apply(layers, cfg_u, x, m, rng=rng)
    got = sequential_trunk_apply(layers, cfg_s, x, m, rng=rng)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


@pytest.mark.slow
def test_raw_distance_templates_match_prebinned():
    """Float templates (raw Angstrom distances) are binned internally with
    the library thresholds — the model output must equal passing the same
    distances pre-binned by geometry.bucketize_distances semantics
    (completes the reference README.md:158 TODO)."""
    from alphafold2_tpu.constants import DISTANCE_THRESHOLDS

    cfg = Alphafold2Config(dim=32, depth=1, heads=2, dim_head=8, max_seq_len=32)
    params = alphafold2_init(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(0)
    seq = jnp.asarray(rs.randint(0, 21, (1, 12)))
    msa = jnp.asarray(rs.randint(0, 21, (1, 3, 12)))
    # raw distances spanning below/inside/above the [2, 20] threshold range
    raw = jnp.asarray(rs.uniform(0.0, 25.0, (1, 2, 12, 12)).astype(np.float32))
    tmask = jnp.ones((1, 2, 12, 12), bool)

    bins = np.asarray(DISTANCE_THRESHOLDS, np.float32)
    prebinned = jnp.asarray(
        np.searchsorted(bins[:-1], np.asarray(raw)).astype(np.int32)
    )
    assert int(prebinned.max()) == cfg.num_buckets - 1  # top bucket exercised

    # jit each variant (separate programs: template dtype differs)
    out_raw = jax.jit(
        lambda p, t: alphafold2_apply(
            p, cfg, seq, msa, templates=t, templates_mask=tmask
        )
    )(params, raw)
    out_pre = jax.jit(
        lambda p, t: alphafold2_apply(
            p, cfg, seq, msa, templates=t, templates_mask=tmask
        )
    )(params, prebinned)
    np.testing.assert_array_equal(np.asarray(out_raw), np.asarray(out_pre))


@pytest.mark.slow
@pytest.mark.parametrize("policy", [None, "dots", "dots_no_batch"])
def test_remat_policies_match_no_remat(policy):
    """Remat with any save policy is a pure memory/FLOP trade: outputs and
    gradients must equal the non-remat trunk exactly."""
    from alphafold2_tpu.models.trunk import sequential_trunk_apply, trunk_layer_init

    base = dict(dim=16, depth=1, heads=2, dim_head=8, max_seq_len=32)
    cfg_plain = Alphafold2Config(**base)
    cfg_remat = Alphafold2Config(**base, remat=True, remat_policy=policy)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    layers = [trunk_layer_init(keys[2], cfg_plain)]
    x = jax.random.normal(keys[0], (1, 6, 6, 16))
    m = jax.random.normal(keys[1], (1, 2, 6, 16))

    def loss(cfg, x):
        ox, om = sequential_trunk_apply(layers, cfg, x, m)
        return jnp.sum(ox ** 2) + jnp.sum(om ** 2)

    v1, g1 = jax.value_and_grad(lambda t: loss(cfg_plain, t))(x)
    v2, g2 = jax.value_and_grad(lambda t: loss(cfg_remat, t))(x)
    np.testing.assert_allclose(float(v1), float(v2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)


def test_remat_policy_unknown_raises():
    # validated eagerly at config construction (fails fast even when the
    # typo'd policy would otherwise be silently ignored with remat=False)
    with pytest.raises(ValueError, match="remat_policy"):
        Alphafold2Config(dim=16, remat_policy="bogus")
