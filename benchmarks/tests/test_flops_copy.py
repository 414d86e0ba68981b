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


# --- the pair axial core's required work (PR 31) ------------------------------

def test_attn_core_count_is_a_function_of_the_shapes_alone():
    import inspect

    (cfg, (n, r, c)), *_ = _cfgs()
    for fn in (flops.attn_core_train_flops, flops.attn_core_train_bytes):
        names = list(inspect.signature(fn).parameters)
        assert names[:4] == ["cfg", "n", "r", "c"]
        # nothing names an arm, a kernel, a block or a padded width
        assert not [p for p in names if p not in ("cfg", "n", "r", "c", "itemsize")]
    # QK^T + AV of both axial passes, every layer, forward once and backward twice
    per_pass = 4.0 * (n * n) * n * cfg.heads * cfg.dim_head
    assert flops.attn_core_train_flops(cfg, n, r, c) == 3.0 * cfg.depth * 2 * per_pass
    # it is the `attn` term of the program's own per-op count, not its projections
    pair = flops.trunk_layer_op_flops(cfg, n, r, c)["pair_axial"]
    proj = 2 * 8.0 * (n * n) * cfg.dim * cfg.heads * cfg.dim_head
    assert flops.attn_core_train_flops(cfg, n, r, c) == 3.0 * cfg.depth * (pair - proj)
    # q, k, v in and out once a pass at two bytes an element
    assert flops.attn_core_train_bytes(cfg, n, r, c) == (
        3.0 * cfg.depth * 2 * 4 * n * n * cfg.heads * cfg.dim_head * 2)
    assert 37e12 < flops.attn_core_train_flops(cfg, n, r, c) < 38e12  # 37.6 TFLOP a step


def test_attn_core_roofline_is_under_100_at_the_mxu_floor():
    """The kernel's own floor on the v5e (PERF.md section 5: 3.4 us a
    (batch, head) row forward and 8.6 backward once dim_head 64 is padded
    to the array's 128) reads under 100% of the roofline, which counts the
    unpadded work: a share above 100% would mean the count is too high."""
    reader = common.module("readers", "roofline_share_lm")
    (cfg, grid), *_ = _cfgs()
    n = grid[0]
    rows = 2 * cfg.depth * n * cfg.heads           # (batch, head) rows a pass, a step
    floor_s = rows * (3.4e-6 + 8.6e-6)             # forward once, backward once
    args = {"scope": "seq_attn/attn_core", "work": "attn_core", "module": "flops",
            "shape": "grid"}

    def share(seconds_a_step):
        cell = {"forward": seconds_a_step * 3, "backward": 0.0}  # three traced steps
        facts = {"scopes": {"scopes": {"seq_attn/attn_core": cell}}, "trace_steps": 3,
                 "grid": grid, "model_cfg": cfg, "device_kind": "TPU v5 lite"}
        return reader.read(facts, args)

    assert 40 < share(floor_s) < 100
    # PR 26's table: 2.961 s of the scope over three steps, all phases
    assert 19 < share(2.961 / 3) < 20
    assert reader.read({"scopes": None}, args) is None
    # the decoder's metrics read as before (module and shape default to its own)
    assert reader.read({"scopes": {"scopes": {}}, "trace_steps": 3}, {
        "scope": "mla_attn/attn_core", "work": "attn_core"}) is None
