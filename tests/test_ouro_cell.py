"""The benchmark's `ouro` cell (`train_lm_loop_8k`) rehearsed at toy shapes
on the CPU: a sound run is `correct`, a timed path that returns its state
unchanged, trains on half its batch or runs its stack one pass too few is
not; the weights the harness draws are what the program's own init would
give; `train_lm.py --config` builds the configuration the builder builds;
the file's cut is the count of parameters and bytes its deployment states;
the benchmark's copy of the FLOP count equals the program's; the limits
stand clear of every recorded run."""
import dataclasses
import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "train_lm_loop_8k"
CONFIG = os.path.join(BENCH, "configs", "ouro_2p6b_l5.json")


def _last_line(cmd):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    proc = subprocess.run([sys.executable, *cmd], capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault,correct", [
    (None, True), ("state_unchanged", False), ("half_batch", False)])
def test_dry_run_decides_correct(fault, correct):
    result = _last_line([os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed",
                         "3000000043", "--dry", *(("--fault", fault) if fault else ())])
    assert result["correct"] is correct, result
    compared = result["compared"]
    assert {"loss1_gap", "loss2_gap", "grad_gap", "change_gap", "pass_loss_gap",
            "exit_gap"} <= set(compared)
    assert "route_mismatch_share" not in compared
    if fault:
        assert compared["change_gap"]["value"] > compared["change_gap"]["limit"]


def test_a_stack_run_one_pass_too_few_is_not_correct():
    """`tools/fault_loop_passes.py --dry`: the cell's own kind with the
    program's passes one fewer and the reference's kept; the loop's own
    numbers catch it, and so does the first loss, which the exit
    distribution of three passes moves (the chip's readings of this fault
    at the cell's size are held below)."""
    result = _last_line([os.path.join(BENCH, "tools", "fault_loop_passes.py"), CELL,
                         "43000011", "--dry"])
    assert result["fault"] == "loop_passes" and result["correct"] is False, result
    compared = result["compared"]
    assert compared["pass_loss_gap"]["value"] == 1.0
    for name in ("loss1_gap", "pass_loss_gap", "exit_gap"):
        assert compared[name]["value"] >= 1.5 * compared[name]["limit"], name


@pytest.fixture()
def bench(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    return importlib.import_module("common")


def _shipped(bench, dry=False):
    _, _, config, _ = bench.load_cell(CELL)
    return config, bench.module("builders", config["builder"]).build(config, dry)


def test_the_fault_breaks_the_program_and_not_the_reference(bench):
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    fault = importlib.import_module("fault_loop_passes")
    _, built = _shipped(bench, dry=True)
    broken = fault.drop_a_pass(built)
    assert broken["cfg"] == dataclasses.replace(built["cfg"], total_ut_steps=3)
    hp = broken["reference_hp"](broken["cfg"], broken["tcfg"])
    assert hp == built["reference_hp"](built["cfg"], built["tcfg"]) and hp["passes"] == 4
    one = {**built, "cfg": dataclasses.replace(built["cfg"], total_ut_steps=1)}
    with pytest.raises(SystemExit, match="no second pass"):
        fault.drop_a_pass(one)


def test_program_init_is_the_harness_draw(bench):
    """The harness draws the seed's weights itself, leaf by leaf, by the
    builder's rule; the program's own init has to be that distribution:
    the same tree, the constants equal, every weight at the assumed scale
    (the branch ends narrowed)."""
    import jax

    from alphafold2_tpu.models.decoder import decoder_init

    kind = importlib.import_module("kinds.lm_train_steps_by_builder")
    config, built = _shipped(bench, dry=True)
    cfg, assumed = built["cfg"], config["assumed_values"]
    key = jax.random.PRNGKey(3)
    prog = decoder_init(key, cfg)
    drawn = kind.param_maker(jax.eval_shape(lambda k: decoder_init(k, cfg), key),
                             assumed, built["leaf_rule"])(key)
    assert jax.tree_util.tree_structure(prog) == jax.tree_util.tree_structure(drawn)
    seen = set()
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(prog),
                            jax.tree_util.tree_leaves(drawn)):
        names = [bench.key_name(k) for k in path]
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype, names
        how, want = built["leaf_rule"](names, assumed)
        if how == "constant":
            np.testing.assert_array_equal(a, b)
            assert float(a.flat[0]) == want
            continue
        seen.add(want)
        # the gate's 64 weights are few: a wider band
        band = 0.35 if names[0] == "exit_gate" else 0.15
        for leaf in (a, b):
            assert abs(leaf.std() / want - 1.0) < band, (names, leaf.std(), want)
    std = assumed["initializer_range"]
    assert seen == {std, std / (2.0 * assumed["scaled_init_layers"]) ** 0.5}


def test_train_lm_builds_the_builders_configuration(bench):
    """`train_lm.py --config <the cell's file>` and the benchmark's builder
    give the same OuroConfig: one entry point, no side script."""
    sys.path.insert(0, ROOT)
    train_lm = importlib.import_module("train_lm")
    from alphafold2_tpu.models.decoder import OuroConfig

    _, built = _shipped(bench)
    cfg = train_lm.config_from_file(CONFIG, "bfloat16")
    assert isinstance(cfg, OuroConfig) and cfg == built["cfg"]
    assert (cfg.layers, cfg.num_hidden_layers, cfg.total_ut_steps) == (5, 48, 4)
    assert (cfg.vocab_size, cfg.hidden_size, cfg.intermediate_size) == (49152, 2048, 5632)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (16, 16, 128)
    assert (cfg.rope_theta, cfg.rms_norm_eps, cfg.tie_word_embeddings) == (1000000, 1e-6, False)
    assert (cfg.scaled_init_layers, cfg.beta, cfg.initializer_range) == (192, 0.1, 0.02)


def test_the_file_states_the_cut(bench):
    """Every published width and T as published; `layers` the one key cut,
    with 48 beside it; the twelve points argued; the count of what the
    chip holds: 51.39 M a layer, 458.3 M in all, 7.33 GB at 16 bytes a
    parameter."""
    config, built = _shipped(bench)
    assert config["reduced"] == ["layers"] and config["published"] == {"layers": 48}
    assert config["layers"] == 5 and config["deployment"]
    for width, value in (("hidden_size", 2048), ("num_attention_heads", 16),
                         ("num_key_value_heads", 16), ("head_dim", 128),
                         ("intermediate_size", 5632), ("num_hidden_layers", 48),
                         ("vocab_size", 49152), ("total_ut_steps", 4),
                         ("early_exit_threshold", 1), ("rope_theta", 1000000),
                         ("max_position_embeddings", 65536)):
        assert config[width] == value
    assert config["layer_types"] == ["full_attention"] * 48
    assert config["rope_scaling"] is None and config["sliding_window"] is None
    assert len(config["assumed"]) == 12
    import jax

    from alphafold2_tpu.models.decoder import decoder_init

    shapes = jax.eval_shape(lambda k: decoder_init(k, built["cfg"]), jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(t.shape)) for t in jax.tree_util.tree_leaves(tree))  # noqa: E731
    assert abs(count(shapes["dense"]["attn"]) / 5 / 16.78e6 - 1) < 1e-3
    assert abs(count(shapes["dense"]["mlp"]) / 5 / 34.60e6 - 1) < 1e-3
    assert abs(count(shapes["dense"]) / 5 / 51.39e6 - 1) < 1e-3
    assert abs((count(shapes["embed"]) + count(shapes["head"])) / 201.33e6 - 1) < 1e-3
    assert count(shapes["exit_gate"]) + count(shapes["final_norm"]) == 2048 + 1 + 2048
    assert abs(count(shapes) / 458.3e6 - 1) < 1e-3 and 7.32e9 < 16 * count(shapes) < 7.34e9


@pytest.mark.parametrize("case", ["toy", "shipped"])
def test_flops_copy_equals_the_programs(bench, case):
    from alphafold2_tpu.utils import flops as original

    flops_ouro = importlib.import_module("flops_ouro")
    _, built = _shipped(bench, dry=(case == "toy"))
    shape = (2, 64) if case == "toy" else (2, 8192)
    for name in ("decoder_fwd_op_flops", "decoder_fwd_flops",
                 "decoder_required_train_flops"):
        assert (getattr(flops_ouro, name)(built["cfg"], *shape)
                == getattr(original, name)(built["cfg"], *shape))


def test_shipped_configuration_counts(bench):
    """One forward of 2 x 8192 tokens: the layers' products 33.7 TF over 20
    applications, the causal cores 11.0 TF, the four heads 13.2 TF, 57.9
    TF in all (58%, 19%, 23%); a step REQUIRES three forwards, 174 TF, of
    which "6 x parameters x tokens" sees 45 TF. In the published 48-layer
    model the heads are 3% and the cores 24%."""
    flops_ouro = importlib.import_module("flops_ouro")
    _, built = _shipped(bench)
    cfg = built["cfg"]
    ops = flops_ouro.decoder_fwd_op_flops(cfg, 2, 8192)
    layers = ops["attn_proj"] + ops["dense_mlp"]
    total = sum(ops.values())
    assert abs(layers / 33.68e12 - 1) < 1e-3
    assert abs(ops["attn_core"] / 11.00e12 - 1) < 2e-3
    assert abs(ops["head"] / 13.19e12 - 1) < 1e-3
    assert abs(total / 57.9e12 - 1) < 2e-3
    assert (round(layers / total, 2), round(ops["attn_core"] / total, 2),
            round(ops["head"] / total, 2)) == (0.58, 0.19, 0.23)
    required = flops_ouro.decoder_required_train_flops(cfg, 2, 8192)
    assert required == 3.0 * total and 173e12 < required < 175e12
    assert 44e12 < 6 * 458.27e6 * 16384 < 46e12
    whole = flops_ouro.decoder_fwd_op_flops(dataclasses.replace(cfg, layers=48), 2, 8192)
    whole_total = sum(whole.values())
    assert round(whole["head"] / whole_total, 2) == 0.03
    assert round(whole["attn_core"] / whole_total, 2) == 0.24
    # the core's bytes: q, k, v, out at 16 heads of 128, 20 applications, 3 passes
    assert (flops_ouro.attn_core_full_train_bytes(cfg, 2, 8192)
            == 3.0 * 20 * 16384 * 4 * 2048 * 2)
    assert (flops_ouro.dense_mlp_train_bytes(cfg, 2, 8192)
            == 3.0 * 20 * (3 * 2048 * 5632 * 2 + 2 * 16384 * 2048 * 2))


def _records(name):
    path = os.path.join(BENCH, "records", name)
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _read(run):
    """{name: value} of a run's compared numbers, and of those it read but
    did not hold (logged as `read, not held: <name> = <value>`)."""
    values = {k: row["value"] for k, row in run["result"]["compared"].items()}
    for line in run["said"]:
        if line.startswith("read, not held: "):
            name, value = line[len("read, not held: "):].split(" = ")
            values[name] = float(value)
    return values


def test_limits_stand_clear_of_every_recorded_run():
    """Every held limit stands at least 2.5 times over the worst sound
    reading; every recorded control and fault passes at least one limit,
    and the limit it passes stands at least 1.5 times under its reading;
    the stack run one pass too few is caught by `exit_gap`'s value, not
    only by the count of passes. The chip read thirteen sound seeds (call
    b's worst three again, alike), four controls, three half batches and
    two one-pass-short faults at the cell's size."""
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        limits = json.load(f)["limits"]
    runs = [r for r in _records("runs_pr43.jsonl") if r["workload"] == CELL and r["result"]]
    sound = [r for r in runs if not r["extra"]]
    half = [r["result"] for r in runs if r["extra"]]
    assert len({r["seed"] for r in sound}) >= 12
    assert sum(r["seed"] >= 10_000_000 for r in sound) >= 4
    for r in sound:
        assert r["result"]["correct"] is True, r["seed"]
        for name, value in _read(r).items():
            if limits.get(name, 0) > 0:
                assert value * 2.5 <= limits[name], (r["seed"], name, value)
    controls = _records(f"control_{CELL}.jsonl")
    passes = _records(f"fault_loop_passes_{CELL}.jsonl")
    assert len({c["seed"] for c in controls}) >= 4 and len(half) >= 3
    assert len({p["seed"] for p in passes}) >= 2
    for fault in controls + half + passes:
        assert fault["correct"] is False, fault
        caught = [k for k, row in fault["compared"].items()
                  if k in limits and limits[k] > 0 and row["value"] >= 1.5 * limits[k]]
        assert caught, fault
    for fault in passes:
        assert not fault["dry"] and fault["compared"]["pass_loss_gap"]["value"] == 1.0
        assert fault["read_not_held"]["exit_gap"] >= 1.5 * limits["exit_gap"], fault
