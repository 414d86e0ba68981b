"""The north-star benchmark configuration, built in exactly one place.

BASELINE.md's operational target is defined over ONE workload (config 5):
the full end-to-end structure train step — reversible tied-row trunk on
the (3*384)^2 pair grid, MSA 128 rows, aligned cross-attention, distogram
-> 200-iter MDS -> sidechain lift -> EGNN refiner -> weighted Kabsch RMSD
loss — dim 256, heads 8, bf16 compute. The benchmark's builder
(benchmarks/builders), bench.py and chip_smoke.py time it, and their
numbers are only comparable if they run the SAME program, so the config
lives here and they import it instead of hand-copying kwargs.

Three tiers exist: "north_star" (the real target), "smoke" (tiny
CPU-safe shapes for rehearsing a code path end-to-end — chip_smoke.py
--dry; its timings mean nothing and are never recorded as
measurements), and "proportional" (1/8-crop shapes
preserving the north star's structural ratios — what the multichip
dryrun's scaled leg and MULTICHIP_r0N.json run). `smoke=True` is the
legacy spelling of tier="smoke".
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from alphafold2_tpu.models import Alphafold2Config, RefinerConfig
from alphafold2_tpu.models.config import depth_aware_attn_defaults
from alphafold2_tpu.training.e2e import E2EConfig

NORTH_STAR_CROP = 384
NORTH_STAR_MSA_ROWS = 128
SMOKE_CROP = 16
SMOKE_MSA_ROWS = 4
# the PROPORTIONAL tier keeps the north star's structural ratios —
# crop : MSA rows = 3:1, compress ratio 4, aligned cross, reversible
# tied-row trunk — at 1/8 the crop so an 8-device CPU mesh can
# compile AND execute it in minutes (the multichip dryrun's scaled
# config, VERDICT r2 weak #5)
PROPORTIONAL_CROP = 48
PROPORTIONAL_MSA_ROWS = 16


def north_star_e2e_config(
    depth: int,
    *,
    smoke: bool = False,
    tier: str | None = None,
    model_overrides: dict | None = None,
    e2e_overrides: dict | None = None,
):
    """Build the north-star E2EConfig (BASELINE.md config 5).

    Returns (ecfg, crop, msa_rows). model_overrides / e2e_overrides are
    dataclasses.replace patches on the model / e2e config respectively —
    the sweep's tuning knobs go through here so a knob rename breaks
    loudly in every script at once. `tier` selects "north_star"
    (default), "smoke" (tiny CPU validation shapes), or "proportional"
    (scaled-down-but-ratio-preserving, for the multichip dryrun);
    smoke=True is the legacy spelling of tier="smoke".
    """
    if smoke and tier not in (None, "smoke"):
        raise ValueError(f"smoke=True conflicts with tier={tier!r}")
    tier = tier or ("smoke" if smoke else "north_star")
    smoke = tier == "smoke"
    # one row per tier: crop, msa_rows, dim, dim_head, compress, rdim,
    # mds iters, mds init. The north-star MDS cut (25 iterations off a
    # classical Torgerson warm start) is the PROMOTED default since PR 7:
    # classical init reaches the random-init stress floor in ~1 iteration
    # on exact and distogram-censored inputs, and e2e smoke training with
    # (25, classical) tracks (200, random) at equal-or-lower loss
    # (PERF.md round 4). The retired reference arm (200, random —
    # reference train_end2end.py:157) stays reachable via e2e_overrides /
    # train_end2end.py --mds-reference for parity runs, and the
    # `e2e_mds200random` sweep leg measures it against this default.
    crop, msa_rows, dim, dim_head, compress, rdim, mds_iters, mds_init = {
        "north_star": (NORTH_STAR_CROP, NORTH_STAR_MSA_ROWS, 256, 64, 4, 64,
                       25, "classical"),
        "smoke": (SMOKE_CROP, SMOKE_MSA_ROWS, 32, 16, 1, 16, 5, "random"),
        "proportional": (PROPORTIONAL_CROP, PROPORTIONAL_MSA_ROWS, 64, 16, 4,
                         32, 25, "random"),
    }[tier]
    dtype = jnp.bfloat16 if tier == "north_star" else jnp.float32
    # measured-headroom attention knobs, resolved by depth (PERF.md item
    # 1): depth <= 24 raises chunk/tile, depth 48 keeps the proven values
    attn_knobs = (
        depth_aware_attn_defaults(depth)
        if tier == "north_star"
        else {"attn_batch_chunk": 0, "attn_flash_tile_elems": 1 << 25}
    )

    model = Alphafold2Config(
        dim=dim,
        depth=depth,
        heads=8,
        dim_head=dim_head,
        max_seq_len=2048,
        max_num_msa=max(msa_rows, 20),
        dtype=dtype,
        # O(1) trunk activation memory in depth — mandatory at depth 48
        reversible=True,
        msa_tie_row_attn=True,
        cross_attn_compress_ratio=compress,
        # column-aligned cross-attention: the O(n^2 * r) redesign that makes
        # this workload tractable (flat mode is O(n^2 * r*c) — ~100x more)
        cross_attn_mode="aligned",
        attn_flash="auto",
        # chunk attention ops over the folded-batch axis so QKV/out
        # projections never materialize over all 1.3M pair tokens (only
        # needed at north-star scale; chunking tiny shapes just adds
        # lax.map dispatch). Chunk and tile sizes are depth-aware
        # (models/config.py depth_aware_attn_defaults)
        # bound the 2048-wide GEGLU intermediate on the pair stream where
        # the XLA arm runs it (the kernel arm keeps it in VMEM, unchunked)
        ff_chunk_size=32768 if tier == "north_star" else 0,
        **attn_knobs,
    )
    if model_overrides:
        model = dataclasses.replace(model, **model_overrides)

    ecfg = E2EConfig(
        model=model,
        refiner=RefinerConfig(
            num_tokens=14, dim=rdim, depth=2, msg_dim=rdim, dtype=dtype,
            # bound the (A, A, msg) pair-message tensor at 5376 atoms
            atom_chunk=256 if tier == "north_star" else 0,
        ),
        mds_iters=mds_iters,
        mds_init=mds_init,
    )
    if e2e_overrides:
        ecfg = dataclasses.replace(ecfg, **e2e_overrides)
    return ecfg, crop, msa_rows
