"""training/presets.py — the single source of the north-star bench config.

bench.py, scripts/bench_sweep.py, and scripts/bench_decompose.py all time
the SAME workload through this preset; these tests pin the invariants the
scripts (and cross-session measurement comparability) depend on.
"""

import dataclasses

import jax.numpy as jnp
import pytest

from alphafold2_tpu.training import north_star_e2e_config
from alphafold2_tpu.training.presets import (
    NORTH_STAR_CROP,
    NORTH_STAR_MSA_ROWS,
    SMOKE_CROP,
    SMOKE_MSA_ROWS,
)


def test_north_star_shapes_and_dtypes():
    ecfg, crop, msa_rows = north_star_e2e_config(48)
    assert (crop, msa_rows) == (NORTH_STAR_CROP, NORTH_STAR_MSA_ROWS) == (384, 128)
    m = ecfg.model
    # BASELINE.md config 5: the values every measured number is quoted at
    assert m.depth == 48 and m.dim == 256 and m.heads == 8 and m.dim_head == 64
    assert m.dtype == jnp.bfloat16 and ecfg.refiner.dtype == jnp.bfloat16
    assert m.reversible and m.msa_tie_row_attn
    assert m.cross_attn_mode == "aligned" and m.cross_attn_compress_ratio == 4
    # the promoted MDS cut (PR 7): 25 iterations off the classical
    # Torgerson warm start — reference parity (200, random) stays
    # reachable via overrides / --mds-reference
    assert ecfg.mds_iters == 25 and ecfg.mds_init == "classical"
    # memory-bounding chunks must be ON at north-star scale
    assert m.attn_batch_chunk > 0 and m.ff_chunk_size > 0
    assert ecfg.refiner.atom_chunk > 0


def test_depth_aware_attn_knob_resolver():
    # PERF.md item 1: depth <= 24 has ~2 GB of headroom to spend on
    # bigger chunks/tiles; depth 48 keeps the proven-to-fit values
    deep, _, _ = north_star_e2e_config(48)
    assert deep.model.attn_batch_chunk == 32
    assert deep.model.attn_flash_tile_elems == 1 << 25
    shallow, _, _ = north_star_e2e_config(12)
    assert shallow.model.attn_batch_chunk == 96
    assert shallow.model.attn_flash_tile_elems == 1 << 26
    # boundary: 24 is still headroom tier
    edge, _, _ = north_star_e2e_config(24)
    assert edge.model.attn_batch_chunk == 96
    # explicit overrides still win (the sweep's A/B legs)
    back, _, _ = north_star_e2e_config(
        12, model_overrides=dict(attn_batch_chunk=32)
    )
    assert back.model.attn_batch_chunk == 32


def test_smoke_is_cpu_safe_and_distinct():
    ecfg, crop, msa_rows = north_star_e2e_config(2, smoke=True)
    assert (crop, msa_rows) == (SMOKE_CROP, SMOKE_MSA_ROWS)
    m = ecfg.model
    assert m.dtype == jnp.float32  # bf16 on CPU would mask numeric issues
    assert ecfg.mds_iters < 50  # smoke must stay fast on one core
    # chunking off: tiny shapes, and unchunked is the reference semantics
    assert m.attn_batch_chunk == 0 and m.ff_chunk_size == 0


def test_overrides_patch_the_right_configs():
    ecfg, _, _ = north_star_e2e_config(
        12,
        model_overrides=dict(attn_batch_chunk=96, ff_chunk_size=131072),
        e2e_overrides=dict(mds_bwd_iters=25, mds_unroll=8),
    )
    assert ecfg.model.attn_batch_chunk == 96
    assert ecfg.model.ff_chunk_size == 131072
    assert ecfg.mds_bwd_iters == 25 and ecfg.mds_unroll == 8
    # overrides must not leak into unrelated fields
    base, _, _ = north_star_e2e_config(12)
    assert dataclasses.replace(
        ecfg,
        model=dataclasses.replace(ecfg.model, attn_batch_chunk=base.model.attn_batch_chunk,
                                  ff_chunk_size=base.model.ff_chunk_size),
        mds_bwd_iters=None, mds_unroll=1,
    ) == base


def test_unknown_override_fails_loudly():
    # a renamed knob must break the sweep at config build, not mid-trace
    with pytest.raises(TypeError):
        north_star_e2e_config(12, model_overrides=dict(no_such_knob=1))


def test_sweep_aliases_branch_parallel_off_to_e2e_auto(tmp_path, monkeypatch):
    # serial is the preset default, so branch_parallel_off's measured
    # configuration IS e2e_auto's: the sweep must record an alias row
    # (copying e2e_auto's TPU number) instead of paying a second
    # multi-minute compile+measure — and must NOT alias a CPU e2e_auto
    # number into a require_tpu leg
    import importlib
    import json
    import sys

    sys.path.insert(0, "scripts")
    bench_sweep = importlib.import_module("bench_sweep")

    def drive(prior_rows):
        out = tmp_path / f"sweep_{len(prior_rows)}.jsonl"
        out.write_text(
            "".join(json.dumps(r) + "\n" for r in prior_rows))
        monkeypatch.setattr(bench_sweep, "OUT", str(out))
        launched = []

        def fake_run(name, code_or_path, argv, timeout, extra=None):
            launched.append(name)
            bench_sweep.record({"bench": name, **(extra or {}),
                                "result": {"skipped": "fake"}, "error": None})
            return {"skipped": "fake"}

        monkeypatch.setattr(bench_sweep, "run_and_record", fake_run)
        monkeypatch.setattr(sys, "argv", ["bench_sweep.py", "--skip-micro"])
        bench_sweep.main()
        rows = [json.loads(l) for l in out.read_text().splitlines()]
        return launched, rows

    base = dict(depth=12, kernel="auto")
    tpu_row = {"bench": "e2e_auto", "spec": base,
               "result": {"sec_per_step": 24.4, "loss": 3.2,
                          "platform": "tpu"}, "error": None}
    launched, rows = drive([tpu_row])
    assert "branch_parallel_off" not in launched  # aliased, not run
    alias = [r for r in rows if r.get("bench") == "branch_parallel_off"]
    assert len(alias) == 1 and alias[0]["alias_of"] == "e2e_auto"
    assert alias[0]["result"] == tpu_row["result"]

    # CPU source (or a pre-platform-field row): falls through to a real
    # run, which is an error off-TPU
    cpu_row = {"bench": "e2e_auto", "spec": base,
               "result": {"sec_per_step": 99.0, "platform": "cpu"},
               "error": None}
    launched, rows = drive([cpu_row])
    assert "branch_parallel_off" in launched
    assert not any(r.get("alias_of") for r in rows)

    # neither an error row (a require_tpu leg that found no TPU) nor a
    # structured skip is a measurement: the leg must run again
    spec_on = {**base, "trunk_schedule": "branch_parallel",
               "require_tpu": True}
    for result, error in (
        (None, "leg requires a TPU device, JAX found cpu"),
        ({"skipped": "single-device host"}, None),
    ):
        launched, rows = drive([{"bench": "branch_parallel_on",
                                 "spec": spec_on, "result": result,
                                 "error": error}])
        assert "branch_parallel_on" in launched  # re-attempted, not silenced


def test_sweep_leg_without_tpu_is_an_error_and_fails_the_sweep(
        tmp_path, monkeypatch):
    # a require_tpu worker on this CPU host exits non-zero: the sweep
    # records an error row (never a number, never a skip) and its own exit
    # code says so
    import importlib
    import json
    import sys

    sys.path.insert(0, "scripts")
    bench_sweep = importlib.import_module("bench_sweep")
    out = tmp_path / "sweep.jsonl"
    monkeypatch.setattr(bench_sweep, "OUT", str(out))
    spec = {"op": "quant_matmul", "arm": "pallas_tpu",
            "require_platform": "tpu"}
    res = bench_sweep.run_and_record(
        "disp_quant_matmul_pallas_tpu", bench_sweep.DISPATCH_WORKER,
        [json.dumps(spec)], timeout=300, extra={"spec": spec})
    assert res is None
    (row,) = [json.loads(l) for l in out.read_text().splitlines()]
    assert row["result"] is None
    assert "requires a TPU" in row["error"] and "cpu" in row["error"]

    # ... and the sweep's own exit code says so
    monkeypatch.setattr(bench_sweep, "run_and_record",
                        lambda name, *a, **kw: None)
    monkeypatch.setattr(sys, "argv", ["bench_sweep.py", "--dispatch-only"])
    with pytest.raises(SystemExit, match="leg\\(s\\) failed"):
        bench_sweep.main()
