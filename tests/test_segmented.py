"""Segmented (multi-execution) train step vs the monolithic jitted step.

The segmented step runs the north-star e2e step as several short device
executions (training/segmented.py). Its whole value rests on being the SAME
optimizer step — these tests pin loss, grad-norm, and updated-parameter
parity against make_train_step(e2e_loss_fn), plus the segment-planning
rules.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.training import (
    DataConfig,
    TrainConfig,
    e2e_loss_fn,
    e2e_train_state_init,
    make_train_step,
    north_star_e2e_config,
    stack_microbatches,
    synthetic_structure_batches,
)
from alphafold2_tpu.training.segmented import (
    make_segmented_train_step,
    plan_segments,
)


def test_plan_segments_respects_runs_and_target():
    # uniform flags: plain chunking
    assert plan_segments((False,) * 6, 2) == [(0, 3, False), (3, 6, False)]
    assert plan_segments((False,) * 5, 2) == [(0, 3, False), (3, 5, False)]
    # mixed flags: boundaries never cross a flag change
    flags = (True, True, False, False, False, False)
    assert plan_segments(flags, 2) == [
        (0, 2, True), (2, 5, False), (5, 6, False),
    ]
    # degenerate requests
    assert plan_segments((False,) * 3, 1) == [(0, 3, False)]
    assert plan_segments((False,) * 2, 8) == [(0, 1, False), (1, 2, False)]


def _setup(depth, accum, seed=0):
    ecfg, crop, msa_rows = north_star_e2e_config(depth, smoke=True)
    tcfg = TrainConfig(learning_rate=3e-4, grad_accum=accum)
    dcfg = DataConfig(batch_size=1, max_len=crop, msa_rows=msa_rows,
                      seed=seed)
    batch = next(
        stack_microbatches(synthetic_structure_batches(dcfg), accum)
    )
    state = e2e_train_state_init(jax.random.PRNGKey(seed), ecfg, tcfg)
    return ecfg, tcfg, batch, state


# slow tier: the segmented chain jits ~7 separate e2e-sized programs
# (front/seg fwd/tail vjp/seg bwd/front bwd/opt), ~50 s cold regardless of
# depth — the fast tier keeps the structural tests below, and the chain's
# execution parity is pinned here plus exercised on-chip by bench.py
@pytest.mark.slow
@pytest.mark.parametrize("accum", [1, 2])
def test_segmented_matches_monolithic(accum):
    ecfg, tcfg, batch, state = _setup(depth=4, accum=accum)
    rng = jax.random.PRNGKey(7)

    mono = make_train_step(ecfg, tcfg, loss_fn=e2e_loss_fn)
    seg = make_segmented_train_step(ecfg, tcfg, trunk_segments=2)

    s_mono, m_mono = mono(state, batch, rng)
    s_seg, m_seg = seg(state, batch, rng)

    np.testing.assert_allclose(
        float(m_mono["loss"]), float(m_seg["loss"]), rtol=1e-5
    )
    np.testing.assert_allclose(
        float(m_mono["grad_norm"]), float(m_seg["grad_norm"]), rtol=1e-4
    )
    assert int(s_seg["step"]) == int(s_mono["step"]) == 1

    flat_mono = jax.tree_util.tree_leaves_with_path(s_mono["params"])
    flat_seg = dict(jax.tree_util.tree_leaves_with_path(s_seg["params"]))
    assert len(flat_mono) == len(flat_seg)
    for path, leaf in flat_mono:
        np.testing.assert_allclose(
            np.asarray(leaf, np.float32),
            np.asarray(flat_seg[path], np.float32),
            rtol=2e-4, atol=2e-6,
            err_msg=jax.tree_util.keystr(path),
        )


@pytest.mark.slow
def test_segmented_matches_monolithic_mixed_sparse():
    """Segment boundaries must align with sparse-flag runs; parity over a
    (True, True, False, False) trunk exercises that path end-to-end."""
    import dataclasses

    ecfg, tcfg, batch, _ = _setup(depth=4, accum=1)
    ecfg = dataclasses.replace(
        ecfg,
        model=dataclasses.replace(
            ecfg.model,
            sparse_self_attn=(True, True, False, False),
            sparse_block_size=8,
            max_seq_len=2048,
        ),
    )
    state = e2e_train_state_init(jax.random.PRNGKey(0), ecfg, tcfg)
    rng = jax.random.PRNGKey(9)

    mono = make_train_step(ecfg, tcfg, loss_fn=e2e_loss_fn)
    seg = make_segmented_train_step(ecfg, tcfg, trunk_segments=3)

    s_mono, m_mono = mono(state, batch, rng)
    s_seg, m_seg = seg(state, batch, rng)
    np.testing.assert_allclose(
        float(m_mono["loss"]), float(m_seg["loss"]), rtol=1e-5
    )
    flat_mono = jax.tree_util.tree_leaves_with_path(s_mono["params"])
    flat_seg = dict(jax.tree_util.tree_leaves_with_path(s_seg["params"]))
    for path, leaf in flat_mono:
        np.testing.assert_allclose(
            np.asarray(leaf, np.float32),
            np.asarray(flat_seg[path], np.float32),
            rtol=2e-4, atol=2e-6,
            err_msg=jax.tree_util.keystr(path),
        )


@pytest.mark.slow
def test_segmented_checkpoint_interchange(tmp_path):
    """Segmented and monolithic training are interchangeable mid-run: a
    state saved from a segmented step restores into the monolithic step
    (identical pytree structure) and keeps training with a finite loss."""
    from alphafold2_tpu.training.checkpoint import (
        CheckpointManager,
        abstract_like,
    )

    ecfg, tcfg, batch, state = _setup(depth=2, accum=1)
    rng = jax.random.PRNGKey(3)
    seg = make_segmented_train_step(ecfg, tcfg, trunk_segments=2)
    state, _ = seg(state, batch, rng)

    with CheckpointManager(str(tmp_path / "ckpt")) as mgr:
        mgr.save(state, force=True)
        mgr.wait()
        restored = mgr.restore(abstract_like(state))

    mono = make_train_step(ecfg, tcfg, loss_fn=e2e_loss_fn)
    s2, metrics = mono(restored, batch, jax.random.PRNGKey(4))
    assert np.isfinite(float(metrics["loss"]))
    assert int(s2["step"]) == 2


def test_segmented_rejects_non_reversible():
    ecfg, _, _ = north_star_e2e_config(2, smoke=True)
    import dataclasses

    ecfg = dataclasses.replace(
        ecfg, model=dataclasses.replace(ecfg.model, reversible=False)
    )
    with pytest.raises(ValueError, match="reversible"):
        make_segmented_train_step(
            ecfg, TrainConfig(learning_rate=3e-4, grad_accum=1), 2
        )
