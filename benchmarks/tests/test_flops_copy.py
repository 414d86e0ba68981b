"""The benchmark's copy of the FLOP count equals the program's on the day
of the copy (PR 24), at the shipped configuration's shapes and at the
serving shapes of the README widths; `mfu_required` counts 3 x forward."""
import common
import flops


def _cfgs():
    from alphafold2_tpu.models import Alphafold2Config
    from alphafold2_tpu.training import north_star_e2e_config

    ecfg, crop, rows = north_star_e2e_config(depth=2)
    serve = Alphafold2Config(dim=256, depth=6, heads=8, dim_head=64, max_seq_len=384)
    return (ecfg.model, (3 * crop, rows, crop)), (serve, (384, 0, 0)), (serve, (128, 0, 0))


def test_copy_equals_original():
    from alphafold2_tpu.utils import flops as original

    for cfg, (n, r, c) in _cfgs():
        assert flops.model_fwd_flops(cfg, n, r, c) == original.model_fwd_flops(cfg, n, r, c)
        assert flops.train_step_flops(cfg, n, r, c) == original.train_step_flops(cfg, n, r, c)
        assert flops.trunk_layer_op_flops(cfg, n, r, c) == original.trunk_layer_op_flops(cfg, n, r, c)


def test_required_is_three_forwards():
    (cfg, (n, r, c)), *_ = _cfgs()
    fwd = flops.model_fwd_flops(cfg, n, r, c)
    assert flops.required_train_flops(cfg, n, r, c) == 3.0 * fwd
    # the program's own count multiplies by 4 (recompute counted)
    assert flops.train_step_flops(cfg, n, r, c) == 4.0 * fwd
    assert 91e12 < 3.0 * fwd < 93e12  # 92.0 TFLOP (PERF.md, PR 21)


def test_mfu_reader_uses_required_and_peak():
    reader = common.module("readers", "mfu_required_train")
    (cfg, grid), *_ = _cfgs()
    facts = {"train_step_s": 2.0, "grid": grid, "model_cfg": cfg,
             "device_kind": "TPU v5 lite"}
    want = 100.0 * flops.required_train_flops(cfg, *grid) / 2.0 / 197e12
    assert reader.read(facts, {}) == want
    assert reader.read({}, {}) is None
