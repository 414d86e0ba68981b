"""The training feed: the same (seed, index) gives the same example, two
indices differ. The decoder cells' feed: a traffic file's `weights_seed`
fixes the cell's weights and rank -> id map, `--seed` the batches; without
the key both come from `--seed`, as before the key."""
import numpy as np
import pytest

import common
import traffic_gen
from kinds import lm_train_steps

LM_CELLS = ("train_lm_moe_8k", "train_lm_cca_moe_8k", "train_lm_swa_moe_8k")


def test_train_examples_differ_and_repeat():
    shape = {"crop": 8, "msa_rows": 2, "atoms_per_residue": 3}
    a = traffic_gen.train_batch(shape, 2**31 + 3, 0)
    b = traffic_gen.train_batch(shape, 2**31 + 3, 1)
    again = traffic_gen.train_batch(shape, 2**31 + 3, 0)
    assert a["seq"].shape == (1, 8) and a["coords"].shape == (1, 8, 14, 3)
    assert a["msa"].shape == (1, 2, 8) and a["mask"].all() and a["msa_mask"].all()
    assert not np.array_equal(a["msa"], b["msa"])
    assert not np.array_equal(a["coords"], b["coords"])
    assert all(np.array_equal(a[k], again[k]) for k in a)
    other = traffic_gen.train_batch(shape, 4, 0)
    assert not np.array_equal(a["seq"], other["seq"])


def _drawn(vocab, batch, length, seed, index, exponent, map_seed):
    """The feed as a plain loop would write it: Zipf ranks from (seed,
    index), ids through the permutation drawn from `map_seed` (before the
    key, `map_seed` was the seed)."""
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -exponent
    cdf = np.cumsum(p / p.sum())
    ids = np.random.default_rng([map_seed, 11]).permutation(vocab)
    u = np.random.default_rng([seed, 12, index]).random((batch, length))
    return ids[np.minimum(np.searchsorted(cdf, u), vocab - 1)].astype(np.int32)


def _ctx(cell, seed, weights_seed=None):
    """A dry run's context of `cell`: its traffic file with `weights_seed`
    set, or taken out where None."""
    _, _, config, traffic = common.load_cell(cell)
    traffic = {k: v for k, v in traffic.items() if k != "weights_seed"}
    if weights_seed is not None:
        traffic["weights_seed"] = weights_seed
    built = common.module("builders", config["builder"]).build(config, True)
    return {"seed": seed, "traffic": traffic, "config": config, "built": built, "dry": True}


def _weights(ctx):
    import jax

    kind = common.module("kinds", ctx["traffic"]["kind"])
    return jax.tree_util.tree_leaves(kind.Weights(ctx)())


@pytest.mark.parametrize("cell", LM_CELLS)
def test_one_weights_seed_fixes_the_model_and_the_seed_the_batches(cell):
    a, b = _ctx(cell, 2**31 + 11, 40), _ctx(cell, 12, 40)
    assert common.weights_seed(a) == common.weights_seed(b) == 40
    leaves_a, leaves_b = _weights(a), _weights(b)
    assert len(leaves_a) == len(leaves_b) > 4
    for x, y in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    vocab = a["built"]["cfg"].vocab_size
    np.testing.assert_array_equal(lm_train_steps.id_map(vocab, common.weights_seed(a)),
                                  lm_train_steps.id_map(vocab, common.weights_seed(b)))
    batch, length = lm_train_steps.shape_of(a)
    exponent = a["traffic"]["zipf_exponent"]
    for ctx in (a, b):
        for index in (0, 1):
            np.testing.assert_array_equal(
                lm_train_steps.cell_batch(ctx, index),
                _drawn(vocab, batch, length, ctx["seed"], index, exponent, 40))
    assert not np.array_equal(lm_train_steps.cell_batch(a, 0), lm_train_steps.cell_batch(b, 0))
    assert not np.array_equal(lm_train_steps.cell_batch(a, 0), lm_train_steps.cell_batch(a, 1))
    # another weights_seed is another model
    other = _weights(_ctx(cell, 12, 41))
    assert any(not np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(leaves_b, other))


@pytest.mark.parametrize("cell", LM_CELLS)
def test_without_the_key_the_seed_draws_as_before(cell):
    seed = 2**31 + 13
    ctx = _ctx(cell, seed)
    assert common.weights_seed(ctx) == seed
    kind = common.module("kinds", ctx["traffic"]["kind"])
    np.testing.assert_array_equal(np.asarray(kind.Weights(ctx).key),
                                  np.asarray(common.seed_key(seed)))
    vocab = ctx["built"]["cfg"].vocab_size
    batch, length = lm_train_steps.shape_of(ctx)
    for index in (0, 3):
        np.testing.assert_array_equal(
            lm_train_steps.cell_batch(ctx, index),
            _drawn(vocab, batch, length, seed, index, ctx["traffic"]["zipf_exponent"], seed))
    # the key changes the weights and the map, not the ranks
    keyed = _ctx(cell, seed, 40)
    assert not np.array_equal(lm_train_steps.cell_batch(ctx, 0),
                              lm_train_steps.cell_batch(keyed, 0))
    back = np.argsort(lm_train_steps.id_map(vocab, seed))
    back_keyed = np.argsort(lm_train_steps.id_map(vocab, 40))
    np.testing.assert_array_equal(back[lm_train_steps.cell_batch(ctx, 0)],
                                  back_keyed[lm_train_steps.cell_batch(keyed, 0)])
