"""The benchmark's `zaya` cell (`train_lm_cca_moe_8k`) rehearsed at toy
shapes on the CPU: a sound run is `correct`, a timed path that returns its
state unchanged or trains on half its batch is not; the weights the
harness draws are what the program's own init would give; `train_lm.py
--config` builds the configuration the builder builds; the benchmark's
copy of the FLOP count equals the program's and is the arithmetic ISSUE 32
states."""
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
CELL = "train_lm_cca_moe_8k"
CONFIG = os.path.join(BENCH, "configs", "zaya1_8b_ep2_l5.json")


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


@pytest.fixture()
def bench(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    return importlib.import_module("common")


def _shipped(bench, dry=False):
    _, _, config, _ = bench.load_cell(CELL)
    return config, bench.module("builders", config["builder"]).build(config, dry)


def test_program_init_is_the_harness_draw(bench):
    """The harness draws the seed's weights itself, leaf by leaf, by the
    builder's rule; the program's own init has to be that distribution:
    the same tree, the constants equal, every weight at the assumed scale
    (the branch ends narrowed, the router MLP at its own)."""
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
    assert seen == {std, std / (2.0 * assumed["scaled_init_layers"]) ** 0.5,
                    assumed["router_mlp_std"]}
    from alphafold2_tpu.models.decoder import ROUTER_MLP_STD
    assert assumed["router_mlp_std"] == ROUTER_MLP_STD


def test_train_lm_builds_the_builders_configuration(bench):
    """`train_lm.py --config <the cell's file>` and the benchmark's builder
    give the same ZayaConfig: one entry point, no side script."""
    sys.path.insert(0, ROOT)
    train_lm = importlib.import_module("train_lm")
    from alphafold2_tpu.models.decoder import DecoderConfig, ZayaConfig

    _, built = _shipped(bench)
    cfg = train_lm.config_from_file(CONFIG, "bfloat16")
    assert isinstance(cfg, ZayaConfig) and cfg == built["cfg"]
    assert (cfg.num_experts, cfg.held, cfg.num_hidden_layers) == (16, (0, 8), 5)
    assert (cfg.vocab_size, cfg.rope_theta, cfg.rotary_dim) == (32784, 5e6, 64)
    kanana = train_lm.config_from_file(
        os.path.join(BENCH, "configs", "kanana2_30b_a3b_ep8_l5.json"), "bfloat16")
    assert isinstance(kanana, DecoderConfig)


def test_the_file_states_the_cut(bench):
    config, _ = _shipped(bench)
    assert config["reduced"] == ["layers", "num_experts", "vocab_size"]
    assert config["published"] == {"layers": 40, "num_experts": 16,
                                   "vocab_size": 262272}
    assert config["experts_held"] == [0, 8] and config["layers"] == 5
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    for width, value in (("hidden_size", 2048), ("num_attention_heads", 8),
                         ("num_key_value_heads", 2), ("head_dim", 128),
                         ("moe_intermediate_size", 2048), ("router_hidden_size", 256),
                         ("num_experts_per_tok", 1), ("num_hidden_layers", 40)):
        assert config[width] == value
    for key in ("convolutions", "qk_norm", "router", "residual_scaling",
                "mixture_of_depths", "bias_update_rate", "initializer", "masking",
                "precision", "optimizer"):
        assert config["assumed"][key]


@pytest.mark.parametrize("case", ["toy", "shipped", "shipped_counted"])
def test_flops_copy_equals_the_programs(bench, case):
    from alphafold2_tpu.utils import flops as original

    flops_zaya = importlib.import_module("flops_zaya")
    _, built = _shipped(bench, dry=(case == "toy"))
    shape = (2, 64) if case == "toy" else (2, 8192)
    extra = {"assignments": 7777.0} if case == "shipped_counted" else {}
    for name in ("decoder_fwd_op_flops", "decoder_fwd_flops",
                 "decoder_required_train_flops"):
        assert (getattr(flops_zaya, name)(built["cfg"], *shape, **extra)
                == getattr(original, name)(built["cfg"], *shape, **extra))


def test_shipped_configuration_counts(bench):
    """ISSUE 32's arithmetic, forward a token at L = 8192: projections
    10.5M, Conv_b 0.7M, the core 16.8M, router 1.3M, the held half of the
    experts 12.6M a layer; the head 134M; a step about 16.9 TFLOP with the
    head at 39%."""
    flops_zaya = importlib.import_module("flops_zaya")
    _, built = _shipped(bench)
    cfg, n = built["cfg"], 2 * 8192
    ops = {k: v / n / cfg.num_hidden_layers
           for k, v in flops_zaya.decoder_fwd_op_flops(cfg, 2, 8192).items()}
    assert abs(ops["cca_proj"] / 10.49e6 - 1) < 1e-3
    assert abs(ops["cca_conv"] / 0.655e6 - 1) < 1e-3
    assert abs(ops["attn_core"] / 16.78e6 - 1) < 1e-3
    assert abs(ops["router"] / 1.319e6 - 1) < 1e-3
    assert abs(ops["experts"] / 12.58e6 - 1) < 1e-3
    assert abs(ops["head"] * cfg.num_hidden_layers / 134.3e6 - 1) < 1e-3
    step = flops_zaya.decoder_required_train_flops(cfg, 2, 8192)
    assert 16.7e12 < step < 17.0e12
    head = 3.0 * flops_zaya.decoder_fwd_op_flops(cfg, 2, 8192)["head"] / step
    assert 0.38 < head < 0.40
    # the core's bytes count k and v at the 2 key heads
    moved = flops_zaya.attn_core_train_bytes(cfg, 2, 8192)
    assert moved == 3.0 * 5 * n * (2 * 1024 + 2 * 256) * 2
    assert dataclasses.replace(cfg, experts_held=None).held == (0, 16)


def test_limits_lie_between_the_sound_runs_and_the_control():
    """Every recorded sound run reads each held number at a third of its
    limit or less; every recorded control and the half-batch fault pass at
    least one limit."""
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        limits = json.load(f)["limits"]
    with open(os.path.join(BENCH, "records", f"control_{CELL}.jsonl")) as f:
        controls = [json.loads(line) for line in f if line.strip()]
    assert len({c["seed"] for c in controls}) >= 3
    for c in controls:
        assert [k for k, v in c["numbers"].items() if k in limits and not v <= limits[k]], c
    with open(os.path.join(BENCH, "records", "runs_pr32.jsonl")) as f:
        runs = [json.loads(line) for line in f if line.strip()]
    runs = [r for r in runs if r["workload"] == CELL and r["result"]]
    sound = [r for r in runs if not r["extra"]]
    faulted = [r for r in runs if r["extra"]]
    assert len({r["seed"] for r in sound}) >= 4 and faulted
    for r in sound:
        assert r["result"]["correct"] is True, r["seed"]
        for name, row in r["result"]["compared"].items():
            if limits.get(name, 0) > 0:
                assert row["value"] * 3 <= limits[name], (r["seed"], name, row)
    for r in faulted:
        assert r["result"]["correct"] is False, r["seed"]
        # a half batch shows in the first loss already, wherever it was compared
        first = r["result"]["compared"].get("loss1_gap")
        assert first is None or first["value"] > 2.5 * limits["loss1_gap"], r["seed"]
