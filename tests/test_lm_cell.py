"""The benchmark's language-model cell (`train_lm_moe_8k`) rehearsed at toy
shapes on the CPU: a sound run is `correct`, a timed path that returns
its state unchanged or trains on half its batch is not, the controls read
on the chip fail the limits the cell ships with, and the weights the
harness draws are what the program's own init would give."""
import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "train_lm_moe_8k"


def _dry(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000005", "--dry", *extra],
        capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault,correct", [
    (None, True), ("state_unchanged", False), ("half_batch", False)])
def test_dry_run_decides_correct(fault, correct):
    result = _dry(*(("--fault", fault) if fault else ()))
    assert result["correct"] is correct, result
    compared = result["compared"]
    assert {"loss2_gap", "grad_gap", "change_gap",
            "route_mismatch_share"} <= set(compared)
    if fault:
        assert compared["change_gap"]["value"] > compared["change_gap"]["limit"]


def test_program_init_is_the_harness_draw(monkeypatch):
    """The harness draws the seed's weights itself, by leaf, from the
    configuration file's `assumed_values`; the program's own init has to
    be that distribution: the same tree, norms at 1, the selection bias
    at 0, every weight at the assumed scale (the residual branches' last
    projections at the narrowed one)."""
    import jax

    from alphafold2_tpu.models.decoder import decoder_init

    monkeypatch.syspath_prepend(BENCH)
    common = importlib.import_module("common")
    kind = importlib.import_module("kinds.lm_train_steps")
    _, _, config, _ = common.load_cell(CELL)
    assumed = config["assumed_values"]
    cfg = common.module("builders", config["builder"]).build(config, True)["cfg"]
    assert cfg.initializer_range == assumed["initializer_range"]
    assert cfg.scaled_init_layers == assumed["scaled_init_layers"]
    key = jax.random.PRNGKey(3)
    prog = decoder_init(key, cfg)
    drawn = kind.param_maker(jax.eval_shape(lambda k: decoder_init(k, cfg), key),
                             assumed)(key)
    assert jax.tree_util.tree_structure(prog) == jax.tree_util.tree_structure(drawn)
    std = assumed["initializer_range"]
    narrow = std / (2.0 * assumed["scaled_init_layers"]) ** 0.5
    seen = set()
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(prog),
                            jax.tree_util.tree_leaves(drawn)):
        names = [common.key_name(k) for k in path]
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype, names
        if names[-1] in ("scale", "bias"):
            np.testing.assert_array_equal(a, b)
            continue
        want = narrow if names[-1] == "w" and names[-2] in ("o", "down") else std
        seen.add(want)
        for leaf in (a, b):
            assert abs(leaf.std() / want - 1.0) < 0.1, (names, leaf.std(), want)
            assert abs(leaf.mean()) < 0.1 * want, (names, leaf.mean())
    assert seen == {std, narrow}


def test_limits_lie_between_the_sound_runs_and_the_control():
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        limits = json.load(f)["limits"]
    with open(os.path.join(BENCH, "records", f"control_{CELL}.jsonl")) as f:
        controls = [json.loads(line) for line in f if line.strip()]
    assert len({c["seed"] for c in controls}) >= 6
    for c in controls:
        over = [k for k, v in c["numbers"].items() if k in limits and not v <= limits[k]]
        assert over, c  # the control is not correct on any seed
    with open(os.path.join(BENCH, "records", "runs_pr27.jsonl")) as f:
        runs = [json.loads(line) for line in f if line.strip()]
    sound = [r for r in runs if r.get("workload") == CELL and r.get("final")]
    assert len({r["seed"] for r in sound}) >= 6
    for r in sound:
        assert r["result"]["correct"] is True, r["seed"]
        for name, row in r["result"]["compared"].items():
            if name in limits and limits[name] > 0:
                assert row["value"] * 3 <= limits[name], (r["seed"], name, row)
