"""Pipeline-parallel trunk: parity vs the replicated sequential trunk on
the 8-device CPU mesh (the last absent SURVEY §2.2 strategy)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.models import Alphafold2Config
from alphafold2_tpu.models.trunk import sequential_trunk_apply, trunk_layer_init
from alphafold2_tpu.parallel import make_mesh
from alphafold2_tpu.parallel.pipeline import pipeline_trunk_apply

N_DEV = 8


@pytest.fixture
def full_opt():
    """Compile at full XLA optimization for one test: the conftest
    compile shortcut (jax_disable_most_optimizations) miscompiles the
    PP x SP composed program on older XLA (observed on jax 0.4.37:
    outputs off by ~100x; correct at full opt on the same jax). The flag
    is read at compile time, so toggling around the test is sufficient."""
    old = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", old)


def _setup(cfg, b, n, rows, cols, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2 + cfg.depth)
    layers = [trunk_layer_init(k, cfg) for k in keys[2:]]
    x = jax.random.normal(keys[0], (b, n, n, cfg.dim))
    m = jax.random.normal(keys[1], (b, rows, cols, cfg.dim))
    return layers, x, m


@pytest.mark.parametrize(
    "stages,microbatches,tie,depth",
    [
        (2, 2, False, 2),  # cheap fast-tier parity case
        pytest.param(4, 4, False, 4, marks=pytest.mark.slow),
        pytest.param(2, 4, True, 4, marks=pytest.mark.slow),
        # drain ticks (S>=3) ACTIVE together with multi-slot drip (M/S>=2):
        # the most intricate scheduling regime
        pytest.param(4, 8, False, 4, marks=pytest.mark.slow),
    ],
)
def test_pipeline_matches_sequential(stages, microbatches, tie, depth):
    if len(jax.devices()) < N_DEV:
        pytest.skip("needs the 8-device CPU mesh")
    cfg = Alphafold2Config(
        dim=16, depth=depth, heads=2, dim_head=8, max_seq_len=32,
        msa_tie_row_attn=tie,
    )
    layers, x, m = _setup(cfg, b=microbatches, n=8, rows=3, cols=8)
    mesh = make_mesh({"pipe": stages})

    # jit both paths: eager dispatch is ~3x trace+compile+run here
    want_x, want_m = jax.jit(
        lambda ls, a, b: sequential_trunk_apply(ls, cfg, a, b)
    )(layers, x, m)
    got_x, got_m = jax.jit(
        lambda ls, a, b: pipeline_trunk_apply(
            ls, cfg, a, b, mesh, microbatches=microbatches
        )
    )(layers, x, m)
    np.testing.assert_allclose(np.asarray(got_x), np.asarray(want_x), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_m), np.asarray(want_m), atol=1e-5)


@pytest.mark.slow
def test_pipeline_with_broadcast_masks():
    if len(jax.devices()) < N_DEV:
        pytest.skip("needs the 8-device CPU mesh")
    cfg = Alphafold2Config(dim=16, depth=2, heads=2, dim_head=8, max_seq_len=32)
    layers, x, m = _setup(cfg, b=2, n=8, rows=3, cols=8)
    mesh = make_mesh({"pipe": 2})
    x_mask = jnp.ones((1, 8, 8), bool).at[:, :, -2:].set(False)
    msa_mask = jnp.ones((1, 3, 8), bool)

    want = sequential_trunk_apply(
        layers, cfg, x, m,
        # the dense oracle folds masks into batch, so give it full-batch
        # copies of the same broadcast masks
        x_mask=jnp.tile(x_mask, (2, 1, 1)),
        msa_mask=jnp.tile(msa_mask, (2, 1, 1)),
    )
    got = pipeline_trunk_apply(
        layers, cfg, x, m, mesh, microbatches=2, x_mask=x_mask, msa_mask=msa_mask
    )
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


@pytest.mark.parametrize(
    "stages,microbatches",
    [
        (2, 2),  # cheap fast-tier case
        # drain + multi-slot drip active: the intricate scheduling regime
        pytest.param(4, 8, marks=pytest.mark.slow),
    ],
)
def test_pipeline_per_example_masks(stages, microbatches):
    """Per-example masks (padded variable-length batches, reference
    alphafold2.py:156-161) travel with their microbatches through the
    feed/forward rings — parity vs the sequential trunk given the same
    per-example masks (VERDICT r3 weak #6 / next #8)."""
    if len(jax.devices()) < N_DEV:
        pytest.skip("needs the 8-device CPU mesh")
    cfg = Alphafold2Config(dim=16, depth=stages, heads=2, dim_head=8,
                           max_seq_len=32)
    b, n, rows, cols = microbatches, 8, 3, 8
    layers, x, m = _setup(cfg, b=b, n=n, rows=rows, cols=cols)
    mesh = make_mesh({"pipe": stages})

    # a DIFFERENT valid length per example — exactly what training/data.py
    # padding produces; microbatch i's mask must reach every stage with it
    rs = np.random.RandomState(3)
    lens = rs.randint(n // 2, n + 1, size=b)
    seq_valid = np.arange(n)[None, :] < lens[:, None]
    x_mask = jnp.asarray(seq_valid[:, :, None] & seq_valid[:, None, :])
    msa_lens = rs.randint(cols // 2, cols + 1, size=b)
    msa_mask = jnp.asarray(
        np.broadcast_to(
            (np.arange(cols)[None, :] < msa_lens[:, None])[:, None, :],
            (b, rows, cols),
        )
    )

    want = jax.jit(
        lambda ls, a, bb: sequential_trunk_apply(
            ls, cfg, a, bb, x_mask=x_mask, msa_mask=msa_mask
        )
    )(layers, x, m)
    got = jax.jit(
        lambda ls, a, bb: pipeline_trunk_apply(
            ls, cfg, a, bb, mesh, microbatches=microbatches,
            x_mask=x_mask, msa_mask=msa_mask,
        )
    )(layers, x, m)
    # both paths run the same dense layer body, so even masked positions
    # agree — full comparison
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


@pytest.mark.parametrize(
    "tie,mode",
    [
        (False, "flat"),  # fast-tier composition proof
        # the north-star configuration: aligned cross + tied rows
        pytest.param(True, "aligned", marks=pytest.mark.slow),
    ],
)
def test_pipeline_composes_with_sp(tie, mode, full_opt):
    """PP x SP: the pipeline over mesh axis 'pipe' with the SEQUENCE-
    PARALLEL layer body over inner axis 'seq' (the promise at the top of
    parallel/pipeline.py — VERDICT r3 next #7). Parity vs the replicated
    sequential trunk on a 2x4 CPU mesh."""
    if len(jax.devices()) < N_DEV:
        pytest.skip("needs the 8-device CPU mesh")
    cfg = Alphafold2Config(
        dim=16, depth=2, heads=2, dim_head=8, max_seq_len=32,
        msa_tie_row_attn=tie, cross_attn_mode=mode,
    )
    # n and MSA rows divisible by the seq axis (4)
    layers, x, m = _setup(cfg, b=2, n=8, rows=4, cols=8)
    mesh = make_mesh({"pipe": 2, "seq": 4})

    want = jax.jit(
        lambda ls, a, b: sequential_trunk_apply(ls, cfg, a, b)
    )(layers, x, m)
    got = jax.jit(
        lambda ls, a, b: pipeline_trunk_apply(
            ls, cfg, a, b, mesh, microbatches=2, seq_axis="seq"
        )
    )(layers, x, m)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


@pytest.mark.slow
def test_pipeline_sp_with_masks():
    """PP x SP with BOTH mask kinds at once: broadcast pair mask (enters
    as a row-sharded shard_map arg) + per-example MSA mask (travels the
    rings seq-sharded) — the fully-general configuration."""
    if len(jax.devices()) < N_DEV:
        pytest.skip("needs the 8-device CPU mesh")
    cfg = Alphafold2Config(dim=16, depth=2, heads=2, dim_head=8,
                           max_seq_len=32)
    b, n, rows, cols = 2, 8, 4, 8
    layers, x, m = _setup(cfg, b=b, n=n, rows=rows, cols=cols)
    mesh = make_mesh({"pipe": 2, "seq": 4})

    x_mask = jnp.ones((1, n, n), bool).at[:, :, -2:].set(False)
    rs = np.random.RandomState(5)
    msa_lens = rs.randint(cols // 2, cols + 1, size=b)
    msa_mask = jnp.asarray(
        np.broadcast_to(
            (np.arange(cols)[None, :] < msa_lens[:, None])[:, None, :],
            (b, rows, cols),
        )
    )

    want = sequential_trunk_apply(
        layers, cfg, x, m,
        x_mask=jnp.tile(x_mask, (b, 1, 1)), msa_mask=msa_mask,
    )
    got = pipeline_trunk_apply(
        layers, cfg, x, m, mesh, microbatches=2, seq_axis="seq",
        x_mask=x_mask, msa_mask=msa_mask,
    )
    # compare at VALID positions only (sp_trunk test convention: masked
    # positions hold path-dependent garbage in both implementations)
    for g, w, mk in zip(got, want,
                        (np.asarray(jnp.tile(x_mask, (b, 1, 1))),
                         np.asarray(msa_mask))):
        g, w = np.asarray(g), np.asarray(w)
        np.testing.assert_allclose(g[mk], w[mk], atol=1e-5)


def test_pipeline_gradient_matches_sequential():
    """Training through the pipeline: autodiff of the shard_map ring
    schedule (ppermute transposes to the reverse permutation, scan to the
    reverse-order scan) must reproduce the sequential trunk's gradients —
    the backward is itself a pipelined schedule, for free."""
    if len(jax.devices()) < N_DEV:
        pytest.skip("needs the 8-device CPU mesh")
    cfg = Alphafold2Config(dim=16, depth=2, heads=2, dim_head=8,
                           max_seq_len=32)
    layers, x, m = _setup(cfg, b=2, n=8, rows=3, cols=8)
    mesh = make_mesh({"pipe": 2})

    def loss(apply_fn):
        def f(ls):
            ox, om = apply_fn(ls)
            return jnp.mean(jnp.square(ox)) + jnp.mean(jnp.square(om))
        return f

    gp = jax.jit(jax.grad(loss(
        lambda ls: pipeline_trunk_apply(ls, cfg, x, m, mesh,
                                        microbatches=2))))(layers)
    gs = jax.jit(jax.grad(loss(
        lambda ls: sequential_trunk_apply(ls, cfg, x, m))))(layers)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.slow
def test_pipeline_sp_gradient_matches_sequential():
    """PP x SP gradients: the composed shard_map (pipe rings + seq
    collectives) differentiates to the sequential trunk's gradients."""
    if len(jax.devices()) < N_DEV:
        pytest.skip("needs the 8-device CPU mesh")
    cfg = Alphafold2Config(dim=16, depth=2, heads=2, dim_head=8,
                           max_seq_len=32)
    layers, x, m = _setup(cfg, b=2, n=8, rows=4, cols=8)
    mesh = make_mesh({"pipe": 2, "seq": 4})

    def loss(apply_fn):
        def f(ls):
            ox, om = apply_fn(ls)
            return jnp.mean(jnp.square(ox)) + jnp.mean(jnp.square(om))
        return f

    gp = jax.jit(jax.grad(loss(
        lambda ls: pipeline_trunk_apply(ls, cfg, x, m, mesh,
                                        microbatches=2,
                                        seq_axis="seq"))))(layers)
    gs = jax.jit(jax.grad(loss(
        lambda ls: sequential_trunk_apply(ls, cfg, x, m))))(layers)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_full_model_pp_matches_replicated():
    """FULL-model parity: embeddings + trunk + head, trunk pipelined over
    the mesh via the trunk_fn hook (the front's masks are per-example —
    this integration exists because masks travel the rings)."""
    from alphafold2_tpu.models import alphafold2_apply, alphafold2_init
    from alphafold2_tpu.parallel import alphafold2_apply_pp

    if len(jax.devices()) < N_DEV:
        pytest.skip("needs the 8-device CPU mesh")
    cfg = Alphafold2Config(
        dim=16, depth=2, heads=2, dim_head=8, max_seq_len=32,
        msa_tie_row_attn=True,
    )
    params = alphafold2_init(jax.random.PRNGKey(0), cfg)
    rs = jax.random.PRNGKey(1)
    seq = jax.random.randint(jax.random.fold_in(rs, 0), (2, 16), 0, 21)
    msa = jax.random.randint(jax.random.fold_in(rs, 1), (2, 8, 16), 0, 21)
    # per-example masks through the whole model
    mask = jnp.asarray(np.arange(16)[None, :] < np.array([[16], [12]]))
    mesh = make_mesh({"pipe": 2})

    want = alphafold2_apply(params, cfg, seq, msa, mask=mask)
    got = alphafold2_apply_pp(params, cfg, seq, msa, mesh, microbatches=2,
                              mask=mask)
    sel = np.asarray(mask[:, :, None] & mask[:, None, :])
    np.testing.assert_allclose(np.asarray(got)[sel], np.asarray(want)[sel],
                               atol=5e-4)


@pytest.mark.slow
def test_full_model_pp_sp_matches_replicated():
    """FULL-model PP x SP: trunk pipelined over 'pipe' with the SP layer
    body over 'seq', everything else replicated."""
    from alphafold2_tpu.models import alphafold2_apply, alphafold2_init
    from alphafold2_tpu.parallel import alphafold2_apply_pp

    if len(jax.devices()) < N_DEV:
        pytest.skip("needs the 8-device CPU mesh")
    cfg = Alphafold2Config(
        dim=16, depth=2, heads=2, dim_head=8, max_seq_len=32,
        msa_tie_row_attn=True,
    )
    params = alphafold2_init(jax.random.PRNGKey(0), cfg)
    rs = jax.random.PRNGKey(1)
    seq = jax.random.randint(jax.random.fold_in(rs, 0), (2, 16), 0, 21)
    msa = jax.random.randint(jax.random.fold_in(rs, 1), (2, 8, 16), 0, 21)
    mesh = make_mesh({"pipe": 2, "seq": 4})

    want = alphafold2_apply(params, cfg, seq, msa)
    got = alphafold2_apply_pp(params, cfg, seq, msa, mesh, microbatches=2,
                              seq_axis="seq")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-4)


def test_pipeline_interleaved_sparse_matches_sequential():
    """Interleaved block-sparse layers (reference BASELINE config 3) in
    the pipeline: the sparse flag rides as per-stage DATA with lax.cond
    selecting the body per layer (an SPMD stage program cannot branch on
    the stage index in Python). Parity vs the sequential trunk."""
    if len(jax.devices()) < N_DEV:
        pytest.skip("needs the 8-device CPU mesh")
    # n=16 with block 4 -> 4 blocks: local 2 + global 1 + random 1 leaves
    # the layout GENUINELY sparse (at 2 blocks it degenerates to all-True
    # and sparse==dense, which would let a mis-routed flag pass parity)
    cfg = Alphafold2Config(
        dim=16, depth=2, heads=2, dim_head=8, max_seq_len=32,
        sparse_self_attn=(True, False), sparse_block_size=4,
        sparse_num_random_blocks=1, sparse_num_local_blocks=2,
        sparse_use_kernel=False,
    )
    layers, x, m = _setup(cfg, b=2, n=16, rows=3, cols=8)
    mesh = make_mesh({"pipe": 2})
    # guard the guard: dense output must DIFFER, else this parity test
    # cannot catch flag-routing bugs
    dense_cfg = Alphafold2Config(
        dim=16, depth=2, heads=2, dim_head=8, max_seq_len=32,
    )
    dense = jax.jit(
        lambda ls, a, b: sequential_trunk_apply(ls, dense_cfg, a, b)
    )(layers, x, m)

    want = jax.jit(
        lambda ls, a, b: sequential_trunk_apply(ls, cfg, a, b)
    )(layers, x, m)
    got = jax.jit(
        lambda ls, a, b: pipeline_trunk_apply(
            ls, cfg, a, b, mesh, microbatches=2
        )
    )(layers, x, m)
    assert not np.allclose(np.asarray(want[0]), np.asarray(dense[0]),
                           atol=1e-5), "sparse degenerated to dense"
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)

    # SP composition keeps the rejection: the block layout spans the
    # full row axis
    with pytest.raises(ValueError, match="not sequence-parallel"):
        pipeline_trunk_apply(layers, cfg, x, m,
                             make_mesh({"pipe": 2, "seq": 4}),
                             microbatches=2, seq_axis="seq")


def test_pp_train_step_matches_replicated():
    """One distogram-pretrain optimizer step with the trunk pipelined
    (make_pp_train_step) must match the replicated step — loss and
    updated params equal. The pipeline is the depth-48 single-step
    alternative to the reversible trunk: params/optimizer state shard
    1/S per stage, activations stay O(batch/S)."""
    from alphafold2_tpu.parallel import make_pp_train_step
    from alphafold2_tpu.training import (
        DataConfig,
        TrainConfig,
        make_train_step,
        stack_microbatches,
        synthetic_batches,
        train_state_init,
    )

    if len(jax.devices()) < N_DEV:
        pytest.skip("needs the 8-device CPU mesh")
    cfg = Alphafold2Config(dim=16, depth=2, heads=2, dim_head=8,
                           max_seq_len=32)
    tcfg = TrainConfig(learning_rate=1e-3, grad_accum=1)
    dcfg = DataConfig(batch_size=2, max_len=8, seed=0)
    batch = next(stack_microbatches(synthetic_batches(dcfg), 1))
    mesh = make_mesh({"pipe": 2})

    state = train_state_init(jax.random.PRNGKey(0), cfg, tcfg)
    step = jax.jit(make_train_step(cfg, tcfg))
    pp_state = train_state_init(jax.random.PRNGKey(0), cfg, tcfg)
    pp_step = make_pp_train_step(cfg, tcfg, mesh, donate_state=False)

    rng = jax.random.PRNGKey(3)
    state, m1 = step(state, batch, rng)
    pp_state, m2 = pp_step(pp_state, batch, rng)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(state["params"]),
                    jax.tree_util.tree_leaves(pp_state["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_pp_sharded_state_train_step():
    """pp_train_state_init delivers the pipeline's PERSISTENT-memory
    promise: trunk params AND Adam moments live depth-stacked, sharded
    1/S over the pipe axis (each device holds depth/S layers), and one
    step through make_pp_train_step with those shardings matches the
    replicated step."""
    from alphafold2_tpu.models.reversible import stack_layers
    from alphafold2_tpu.parallel import make_pp_train_step, pp_train_state_init
    from alphafold2_tpu.training import (
        DataConfig,
        TrainConfig,
        make_train_step,
        stack_microbatches,
        synthetic_batches,
        train_state_init,
    )

    if len(jax.devices()) < N_DEV:
        pytest.skip("needs the 8-device CPU mesh")
    cfg = Alphafold2Config(dim=16, depth=8, heads=2, dim_head=8,
                           max_seq_len=32)
    tcfg = TrainConfig(learning_rate=1e-3, grad_accum=1)
    dcfg = DataConfig(batch_size=8, max_len=8, seed=0)
    batch = next(stack_microbatches(synthetic_batches(dcfg), 1))
    mesh = make_mesh({"pipe": N_DEV})

    pp_state, shardings = pp_train_state_init(
        jax.random.PRNGKey(0), cfg, tcfg, mesh)
    # 1/S for real: every stacked trunk leaf is sharded over pipe, and
    # each device's addressable shard holds depth/S layers
    for leaf in jax.tree_util.tree_leaves(pp_state["params"]["trunk"]):
        assert leaf.shape[0] == cfg.depth
        shard = leaf.addressable_shards[0]
        assert shard.data.shape[0] == cfg.depth // N_DEV, (
            leaf.shape, shard.data.shape)
    # Adam moments mirror the layout
    mu = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda t: t, pp_state["opt_state"]))
    assert any(
        getattr(l, "addressable_shards", None)
        and l.ndim >= 1 and l.addressable_shards[0].data.shape != l.shape
        for l in mu
    ), "no optimizer leaf is actually sharded"

    pp_step = make_pp_train_step(cfg, tcfg, mesh, donate_state=False,
                                 state_shardings=shardings)
    state = train_state_init(jax.random.PRNGKey(0), cfg, tcfg)
    step = jax.jit(make_train_step(cfg, tcfg))

    rng = jax.random.PRNGKey(3)
    state, m1 = step(state, batch, rng)
    pp_state, m2 = pp_step(pp_state, batch, rng)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=2e-5)
    # compare the stacked trunk against the replicated list stacked
    want_trunk = stack_layers(list(state["params"]["trunk"]))
    for a, b in zip(
        jax.tree_util.tree_leaves(pp_state["params"]["trunk"]),
        jax.tree_util.tree_leaves(want_trunk),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)

    # unstack_layers: the bridge back to the sequential apply (e.g. to
    # predict with a pipeline-sharded train state) — layer-list roundtrip
    from alphafold2_tpu.models.reversible import unstack_layers

    back = unstack_layers(pp_state["params"]["trunk"])
    assert len(back) == cfg.depth
    for a, b in zip(jax.tree_util.tree_leaves(back[3]),
                    jax.tree_util.tree_leaves(state["params"]["trunk"][3])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)

    # reversible configs must be rejected with the clear contract error,
    # not a cryptic stack failure
    rcfg = Alphafold2Config(dim=16, depth=2, heads=2, dim_head=8,
                            max_seq_len=32, reversible=True)
    with pytest.raises(ValueError, match="reversible=False"):
        pp_train_state_init(jax.random.PRNGKey(0), rcfg, tcfg, mesh)
    # schedule kwargs alongside a custom loss_fn are a silent-mismatch
    # trap — rejected
    with pytest.raises(ValueError, match="only apply to the default"):
        make_pp_train_step(cfg, tcfg, mesh, microbatches=4,
                           loss_fn=lambda *a: 0.0)


@pytest.mark.slow
def test_pp_e2e_train_step_matches_replicated():
    """The FULL structure workload (distogram -> MDS -> sidechain ->
    refiner -> Kabsch loss) trained with the trunk pipelined: one step of
    make_pp_train_step(loss_fn=pp_e2e_loss_fn) matches the replicated e2e
    step."""
    from alphafold2_tpu.models import RefinerConfig
    from alphafold2_tpu.parallel import make_pp_train_step, pp_e2e_loss_fn
    from alphafold2_tpu.training import (
        DataConfig,
        E2EConfig,
        TrainConfig,
        e2e_loss_fn,
        e2e_train_state_init,
        make_train_step,
        stack_microbatches,
        synthetic_structure_batches,
    )

    if len(jax.devices()) < N_DEV:
        pytest.skip("needs the 8-device CPU mesh")
    ecfg = E2EConfig(
        model=Alphafold2Config(
            dim=16, depth=2, heads=2, dim_head=8, max_seq_len=64,
            msa_tie_row_attn=True, cross_attn_mode="aligned",
        ),
        refiner=RefinerConfig(num_tokens=14, dim=16, depth=1, msg_dim=16),
        mds_iters=3,
    )
    tcfg = TrainConfig(learning_rate=1e-3, grad_accum=1)
    # batch 2: the pipeline schedules over batch microbatches (>= stages)
    dcfg = DataConfig(batch_size=2, max_len=8, msa_rows=4, seed=0)
    batch = next(stack_microbatches(synthetic_structure_batches(dcfg), 1))
    mesh = make_mesh({"pipe": 2})

    state = e2e_train_state_init(jax.random.PRNGKey(0), ecfg, tcfg)
    step = jax.jit(make_train_step(ecfg, tcfg, loss_fn=e2e_loss_fn))
    pp_state = e2e_train_state_init(jax.random.PRNGKey(0), ecfg, tcfg)
    pp_step = make_pp_train_step(
        ecfg, tcfg, mesh, donate_state=False, loss_fn=pp_e2e_loss_fn(mesh)
    )

    rng = jax.random.PRNGKey(3)
    state, m1 = step(state, batch, rng)
    pp_state, m2 = pp_step(pp_state, batch, rng)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(state["params"]),
                    jax.tree_util.tree_leaves(pp_state["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_pipeline_validates_shapes():
    if len(jax.devices()) < N_DEV:
        pytest.skip("needs the 8-device CPU mesh")
    cfg = Alphafold2Config(dim=16, depth=3, heads=2, dim_head=8, max_seq_len=32)
    layers, x, m = _setup(cfg, b=2, n=8, rows=3, cols=8)
    mesh = make_mesh({"pipe": 2})
    with pytest.raises(ValueError, match="divide into"):
        pipeline_trunk_apply(layers, cfg, x, m, mesh)
    cfg4 = Alphafold2Config(dim=16, depth=4, heads=2, dim_head=8, max_seq_len=32)
    layers4, x6, m6 = _setup(cfg4, b=6, n=8, rows=3, cols=8)
    mesh4 = make_mesh({"pipe": 4})
    with pytest.raises(ValueError, match="divide by the stage count"):
        pipeline_trunk_apply(layers4, cfg4, x6, m6, mesh4, microbatches=6)


def test_round_robin_layout_roundtrip():
    """Microbatch i must live at [stage i % S, slot i // S] and come back in
    order — the contract the feed/return rings are scheduled against."""
    from alphafold2_tpu.parallel.pipeline import _round_robin, _un_round_robin

    M, S = 8, 4
    t = jnp.arange(M)[:, None] * jnp.ones((1, 3))  # (M, mb=3)
    rr = _round_robin(t, M, S)
    assert rr.shape == (S, M // S, 3)
    for i in range(M):
        np.testing.assert_array_equal(np.asarray(rr[i % S, i // S]), i)
    np.testing.assert_array_equal(np.asarray(_un_round_robin(rr, M)), np.asarray(t))


@pytest.mark.slow
def test_pipeline_activation_memory_bounded():
    """The reason to pipeline depth 48: in-flight activation memory must
    NOT grow with the microbatch count (VERDICT r2 weak #6 — the old
    scheme replicated the whole input/output stacks on every stage).
    XLA's memory analysis of the compiled program proves it: temp bytes
    (in-flight buffers + compute scratch) stay ~flat when M doubles, and
    the input/output stacks live in (stage-sharded) args/outputs, not
    temps."""
    if len(jax.devices()) < N_DEV:
        pytest.skip("needs the 8-device CPU mesh")
    cfg = Alphafold2Config(dim=16, depth=8, heads=2, dim_head=8, max_seq_len=32)
    keys = jax.random.split(jax.random.PRNGKey(0), 10)
    layers = [trunk_layer_init(k, cfg) for k in keys[2:]]
    mesh = make_mesh({"pipe": 8})

    def temp_bytes(M):
        x = jax.random.normal(keys[0], (M, 16, 16, cfg.dim))
        m = jax.random.normal(keys[1], (M, 4, 8, cfg.dim))
        c = (
            jax.jit(
                lambda ls, a, b: pipeline_trunk_apply(
                    ls, cfg, a, b, mesh, microbatches=M
                )
            )
            .lower(layers, x, m)
            .compile()
        )
        return c.memory_analysis().temp_size_in_bytes

    t8, t16 = temp_bytes(8), temp_bytes(16)
    # 10% slack for scan/bookkeeping noise; the old replicated scheme
    # scaled temp with M (the whole output stack lived in the carry)
    assert t16 <= t8 * 1.10, (t8, t16)
