"""How `correct` is decided, at a size a test run can hold.

- the reference's block-by-block gradients equal plain reverse-mode;
- the control (the reference in 8-bit floating point) fails the limits
  every cell ships with;
- a run driven past the look for a chip, with the timed path broken
  underneath, reports `correct` false.
"""
import json
import os
import subprocess
import sys

import pytest

import common
import compare
import traffic_gen

RUN = os.path.join(common.HERE, "run.py")


def _dry(workload, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=common.ROOT)
    proc = subprocess.run([sys.executable, RUN, "--workload", workload, "--seed", "5",
                           "--dry", *extra], capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def small_training():
    import jax

    from alphafold2_tpu.training import TrainConfig, north_star_e2e_config

    ecfg, crop, rows = north_star_e2e_config(
        depth=2, tier="smoke", e2e_overrides={"mds_init": "classical"},
        model_overrides={"cross_attn_compress_ratio": 2})
    built = {"ecfg": ecfg, "tcfg": TrainConfig(learning_rate=3e-4, grad_accum=1),
             "crop": crop, "msa_rows": rows}
    return built


def test_blockwise_gradients_equal_plain_autodiff(small_training):
    import jax
    import jax.numpy as jnp

    from alphafold2_tpu.training.e2e import e2e_loss_fn
    from reference import af2, e2e_tail

    built = small_training
    loss = common.module("losses", "e2e")
    prog = loss.program(built)
    params = common.make_params(prog["param_shapes"], common.seed_key(2**31 + 3),
                                stacked=prog["stacked"])
    shape = {"crop": built["crop"], "msa_rows": built["msa_rows"], "atoms_per_residue": 3}
    batch = {k: jnp.asarray(v) for k, v in traffic_gen.train_batch(shape, 9, 0).items()}
    hp = loss.reference_hp(built, {"attn_block": 8, "ff_block": 64, "cross_block": 4,
                                   "atom_block": 32})
    value, grads = loss.reference_value_and_grad(params, batch, hp)

    def plain(p):  # one plain forward of the same reference, plain reverse-mode
        logits = af2.forward_reversible(p["model"], jnp.repeat(batch["seq"], 3, axis=-1),
                                        batch["msa"], hp)
        return e2e_tail.structure_loss(logits, p["refiner"], batch, hp)

    plain_v, plain_g = jax.value_and_grad(plain)(params)
    assert float(value) == pytest.approx(float(plain_v), rel=1e-6)
    gap, _ = compare.worst_leaf_gap(compare.norms(grads), compare.norms(plain_g))
    assert gap < 1e-5
    # and the program at float32 agrees with the reference
    step_loss, step_grads = jax.value_and_grad(
        lambda p: e2e_loss_fn(p, built["ecfg"], batch, None))(params)
    assert float(value) == pytest.approx(float(step_loss), rel=1e-5)
    gap, _ = compare.worst_leaf_gap(compare.norms(step_grads), compare.norms(grads))
    assert gap < 1e-3


@pytest.mark.parametrize("workload", ["train_e2e"])
def test_control_fails_a_training_cell(small_training, workload):
    from kinds import train_steps
    from reference import lowprec

    _, cell, config, traffic = common.load_cell(workload)
    limits = common.load_json("limits", workload + ".json")["limits"]
    built = small_training
    loss = common.module("losses", traffic["loss"])
    prog = loss.program(built)
    params0 = common.make_params(prog["param_shapes"], common.seed_key(11),
                                 stacked=prog["stacked"])
    shape = {"crop": built["crop"], "msa_rows": built["msa_rows"],
             "atoms_per_residue": 3}
    batches = [traffic_gen.train_batch(shape, 11, i) for i in range(2)]
    ctx = {"built": built, "dry": True, "config": config}
    ref = train_steps.follow_reference(ctx, loss, params0, batches)
    ctl = train_steps.follow_reference(ctx, loss, params0, batches, lowprec.fp8)
    values = train_steps.compared_numbers(ctl, ref, compare.leaf_paths(params0))
    failed = [k for k, v in values.items() if k in limits and v > limits[k]]
    assert failed, values


def test_control_on_the_chip_failed_the_shipped_limits():
    """The control's readings on the chip at the cell's own size
    (records/control_<cell>.jsonl, written by tools/control.py), put through
    the comparison with the limits the cell ships with: not correct."""
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    for cell in cells:
        limits = common.load_json("limits", cell + ".json")["limits"]
        with open(os.path.join(common.HERE, "records", f"control_{cell}.jsonl")) as f:
            records = [json.loads(line) for line in f if line.strip()]
        assert len({r["seed"] for r in records}) >= 3
        for r in records:
            correct, _ = common.judge({k: (v, limits[k]) for k, v in r["numbers"].items()
                                       if k in limits})
            assert correct is False, r


@pytest.mark.parametrize("workload,fault", [("train_e2e", "state_unchanged")])
def test_broken_timed_path_is_not_correct(workload, fault):
    sound = _dry(workload)
    assert sound["correct"] is True, sound
    broken = _dry(workload, "--fault", fault)
    assert broken["correct"] is False, broken


def test_measured_path_needs_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, RUN, "--workload", "train_e2e",
                           "--seed", "1", "--seconds", "1", "--trace", "0"],
                          capture_output=True, text=True, env=env)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
