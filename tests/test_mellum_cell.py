"""The benchmark's `mellum` cell (`train_lm_swa_moe_8k`) rehearsed at toy
shapes on the CPU: a sound run is `correct`, a timed path that returns its
state unchanged, trains on half its batch or lets its window layers see
the whole prefix is not; the weights the harness draws are what the
program's own init would give; `train_lm.py --config` builds the
configuration the builder builds; the file's cut is ISSUE 35's
arithmetic; the benchmark's copy of the FLOP count equals the program's;
the limits stand clear of every recorded run."""
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
CELL = "train_lm_swa_moe_8k"
CONFIG = os.path.join(BENCH, "configs", "mellum2_12b_a2p5b_ep4_l4.json")


def _last_line(cmd):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    proc = subprocess.run([sys.executable, *cmd], capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault,correct", [
    (None, True), ("state_unchanged", False), ("half_batch", False)])
def test_dry_run_decides_correct(fault, correct):
    result = _last_line([os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed",
                         "3000000005", "--dry", *(("--fault", fault) if fault else ())])
    assert result["correct"] is correct, result
    compared = result["compared"]
    assert {"loss2_gap", "grad_gap", "change_gap",
            "route_mismatch_share"} <= set(compared)
    if fault:
        assert compared["change_gap"]["value"] > compared["change_gap"]["limit"]


def test_window_layers_that_see_the_whole_prefix_are_not_correct():
    """`tools/fault_full_window.py --dry`: the cell's own kind with the
    program's window taken away and the reference's kept."""
    result = _last_line([os.path.join(BENCH, "tools", "fault_full_window.py"), CELL,
                         "12772342", "--dry"])
    assert result["fault"] == "full_window" and result["correct"] is False, result
    over = [k for k, row in result["compared"].items() if row["value"] > row["limit"]]
    assert over, result


@pytest.fixture()
def bench(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    return importlib.import_module("common")


def _shipped(bench, dry=False):
    _, _, config, _ = bench.load_cell(CELL)
    return config, bench.module("builders", config["builder"]).build(config, dry)


def test_the_fault_breaks_the_program_and_not_the_reference(bench):
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    fault = importlib.import_module("fault_full_window")
    _, built = _shipped(bench, dry=True)
    broken = fault.break_window(built)
    assert broken["cfg"].sliding_window is None and built["cfg"].sliding_window == 8
    assert broken["cfg"] == dataclasses.replace(built["cfg"], sliding_window=None)
    hp = broken["reference_hp"](broken["cfg"], broken["tcfg"])
    assert hp == built["reference_hp"](built["cfg"], built["tcfg"]) and hp["window"] == 8
    with pytest.raises(SystemExit, match="no sliding window"):
        fault.break_window(broken)


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
        for leaf in (a, b):
            assert abs(leaf.std() / want - 1.0) < 0.15, (names, leaf.std(), want)
    std = assumed["initializer_range"]
    assert seen == {std, std / (2.0 * assumed["scaled_init_layers"]) ** 0.5}


def test_train_lm_builds_the_builders_configuration(bench):
    """`train_lm.py --config <the cell's file>` and the benchmark's builder
    give the same MellumConfig: one entry point, no side script."""
    sys.path.insert(0, ROOT)
    train_lm = importlib.import_module("train_lm")
    from alphafold2_tpu.models.decoder import FULL, SLIDING, MellumConfig

    _, built = _shipped(bench)
    cfg = train_lm.config_from_file(CONFIG, "bfloat16")
    assert isinstance(cfg, MellumConfig) and cfg == built["cfg"]
    assert (cfg.num_experts, cfg.held, cfg.num_hidden_layers) == (64, (0, 16), 4)
    assert cfg.layer_types == (SLIDING, SLIDING, SLIDING, FULL) == cfg.period
    assert (cfg.vocab_size, cfg.sliding_window, cfg.num_experts_per_tok) == (24576, 1024, 8)
    assert cfg.rope_of(FULL)["attention_factor"] == 1.2772588722239782
    assert cfg.rope_of(SLIDING) == {"rope_type": "default", "rope_theta": 500000}
    assert (cfg.scaled_init_layers, cfg.norm_topk_prob, cfg.rms_norm_eps) == (28, True, 1e-6)


def test_the_file_states_the_cut(bench):
    config, built = _shipped(bench)
    assert config["reduced"] == ["layers", "num_experts", "vocab_size"]
    assert config["published"] == {"layers": 28, "num_experts": 64, "vocab_size": 98304}
    assert config["experts_held"] == [0, 16] and config["layers"] == 4
    assert config["vocab_size"] * 4 == config["published"]["vocab_size"]
    assert config["num_experts"] * 4 == config["published"]["num_experts"]
    for width, value in (("hidden_size", 2304), ("num_attention_heads", 32),
                         ("num_key_value_heads", 4), ("head_dim", 128),
                         ("moe_intermediate_size", 896), ("intermediate_size", 7168),
                         ("num_experts_per_tok", 8), ("num_hidden_layers", 28),
                         ("sliding_window", 1024), ("max_position_embeddings", 131072)):
        assert config[width] == value
    assert len(config["layer_types"]) == len(config["mlp_layer_types"]) == 28
    assert config["layer_types"][:4] * 7 == config["layer_types"]
    for key in ("qk_norm", "router", "mtp", "rope", "masking", "initializer",
                "balancing", "precision", "optimizer"):
        assert config["assumed"][key]
    # ISSUE 35's count of what the chip holds, at 16 bytes a parameter
    import jax

    from alphafold2_tpu.models.decoder import decoder_init

    shapes = jax.eval_shape(lambda k: decoder_init(k, built["cfg"]), jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(t.shape)) for t in jax.tree_util.tree_leaves(tree))  # noqa: E731
    layer = count(shapes["moe"]) / 4
    assert abs(count(shapes["moe"]["attn"]) / 4 / 21.2e6 - 1) < 2e-3
    assert abs(count(shapes["moe"]["mlp"]["experts"]) / 4 / 99.1e6 - 1) < 1e-3
    assert abs(layer / 120.5e6 - 1) < 1e-3
    assert abs((count(shapes["embed"]) + count(shapes["head"])) / 113.2e6 - 1) < 1e-3
    assert abs(count(shapes) / 595.2e6 - 1) < 1e-3 and 9.4e9 < 16 * count(shapes) < 9.6e9


@pytest.mark.parametrize("case", ["toy", "shipped", "shipped_counted"])
def test_flops_copy_equals_the_programs(bench, case):
    from alphafold2_tpu.utils import flops as original

    flops_mellum = importlib.import_module("flops_mellum")
    _, built = _shipped(bench, dry=(case == "toy"))
    shape = (2, 64) if case == "toy" else (2, 8192)
    extra = {"assignments": 7777.0} if case == "shipped_counted" else {}
    for name in ("decoder_fwd_op_flops", "decoder_fwd_flops",
                 "decoder_required_train_flops"):
        assert (getattr(flops_mellum, name)(built["cfg"], *shape, **extra)
                == getattr(original, name)(built["cfg"], *shape, **extra))
    assert flops_mellum.band_pairs(8192, 1024) == original.band_pairs(8192, 1024)


def test_shipped_configuration_counts(bench):
    """ISSUE 35's arithmetic, one forward of 2 x 8192 tokens: projections
    0.69 TF a layer, a window layer's core 0.26 (7.86 M in-band pairs a
    head a sequence, 23% of the triangle's 33.6 M), the full layer's 1.10,
    the held experts 0.41 a layer, the head 1.86; 8.1 TF in all, of which
    the four cores are 23%; the whole triangle in the window layers would
    add 2.5 TF."""
    flops_mellum = importlib.import_module("flops_mellum")
    _, built = _shipped(bench)
    cfg = built["cfg"]
    ops = flops_mellum.decoder_fwd_op_flops(cfg, 2, 8192)
    assert flops_mellum.band_pairs(8192, 1024) == 7864832
    assert flops_mellum.band_pairs(8192, None) == 8192 * 8193 / 2
    assert abs(ops["gqa_proj"] / 4 / 0.696e12 - 1) < 2e-3
    assert abs(ops["attn_core_window"] / 3 / 0.2577e12 - 1) < 1e-3
    assert abs(ops["attn_core"] / 1.0996e12 - 1) < 1e-3
    assert abs(ops["experts"] / 4 / 0.4058e12 - 1) < 1e-3
    assert abs(ops["head"] / 1.855e12 - 1) < 1e-3
    total = sum(ops.values())
    assert 8.1e12 < total < 8.2e12
    assert 0.22 < (ops["attn_core_window"] + ops["attn_core"]) / total < 0.24
    assert 2.4e12 < 3 * ops["attn_core"] - ops["attn_core_window"] < 2.6e12
    assert flops_mellum.decoder_required_train_flops(cfg, 2, 8192) == 3.0 * total
    # the cores' bytes count k and v at the 4 key heads, by kind of layer
    n = 2 * 8192
    assert (flops_mellum.attn_core_window_train_bytes(cfg, 2, 8192)
            == 3.0 * 3 * n * (2 * 4096 + 2 * 512) * 2)
    assert (flops_mellum.attn_core_full_train_bytes(cfg, 2, 8192)
            == 3.0 * 1 * n * (2 * 4096 + 2 * 512) * 2)
    # no drop: the loop's blocks reach 8 picks a token over 16 held of 64;
    # a load of 35 100 rows (the cell's most by seed) is one block of 40 960
    from alphafold2_tpu.ops import moe
    block = moe.block_rows_for(n, 8, 16, 64)
    assert block == 40960
    assert float(moe.rows_walked(n * 8, block)) >= n * 8
    assert float(moe.rows_walked(35100, block)) == block < 1.25 * 35100


def _records(name):
    with open(os.path.join(BENCH, "records", name)) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_limits_stand_clear_of_every_recorded_run():
    """Every held limit stands at least 2.5 times over the worst sound
    reading; every recorded control, half-batch fault and full-window
    fault passes at least one limit, and the limit it passes stands at
    least 1.5 times under its reading."""
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        limits = json.load(f)["limits"]
    runs = [r for r in _records("runs_pr35.jsonl") if r["workload"] == CELL and r["result"]]
    sound = [r for r in runs if not r["extra"]]
    half = [r["result"] for r in runs if r["extra"]]
    assert len({r["seed"] for r in sound}) >= 12
    assert sum(r["seed"] >= 10_000_000 for r in sound) >= 4
    for r in sound:
        assert r["result"]["correct"] is True, r["seed"]
        for name, row in r["result"]["compared"].items():
            if limits.get(name, 0) > 0:
                assert row["value"] * 2.5 <= limits[name], (r["seed"], name, row)
    controls = _records(f"control_{CELL}.jsonl")
    windows = _records(f"fault_full_window_{CELL}.jsonl")
    assert len({c["seed"] for c in controls}) >= 4 and len(half) >= 3 and len(windows) >= 2
    for fault in controls + half + windows:
        assert fault["correct"] is False, fault
        caught = [k for k, row in fault["compared"].items()
                  if k in limits and limits[k] > 0 and row["value"] >= 1.5 * limits[k]]
        assert caught, fault
