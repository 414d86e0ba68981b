"""training/presets.py — the single source of the north-star bench config.

The benchmark's builder (benchmarks/builders), bench.py and chip_smoke.py
all time the SAME workload through this preset; these tests pin the
invariants they (and cross-session measurement comparability) depend on.
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
