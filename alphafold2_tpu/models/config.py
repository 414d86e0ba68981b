"""Model configuration.

Replaces the reference's `Alphafold2.__init__` kwarg soup
(reference alphafold2_pytorch/alphafold2.py:329-346) with a frozen dataclass
that is hashable (safe as a jit static argument) and explicit about every
capability flag.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple, Union

import jax.numpy as jnp

from alphafold2_tpu.constants import (
    DISTOGRAM_BUCKETS,
    MAX_NUM_MSA,
    NUM_AMINO_ACIDS,
    NUM_EMBEDDS_TR,
)
from alphafold2_tpu.ops.attention import AttentionConfig


# depth threshold below which the smaller parameter/optimizer state leaves
# ~2 GB of HBM headroom on a 16G chip (PERF.md "where the next factors come
# from" item 1): shallow trunks trade that headroom for fewer, larger
# attention chunks and bigger streaming tiles
_ATTN_HEADROOM_MAX_DEPTH = 24


def depth_aware_attn_defaults(depth: int) -> dict:
    """Measured-headroom attention-knob defaults for the north-star preset.

    At depth <= 24 the trunk's parameter + optimizer state is small enough
    that the memory-bounding chunks can be raised (PERF.md item 1):
    `attn_batch_chunk` 32 -> 96 (3x fewer, 3x larger attention programs
    per pass) and `attn_flash_tile_elems` 2^25 -> 2^26 (halves the
    sequential tile count of the XLA streaming path). Depth 48 keeps the
    proven-to-fit values — the deep config has no headroom to spend.

    This is THE resolver for the two knobs: the training preset
    (training/presets.py) routes through it, so everything that inherits
    preset defaults (the benchmark's builder, bench.py) measures against
    it. Both values stay config fields until ROADMAP S5 re-measures them:
    the benchmark cell's configuration file names them under `assumed`.
    """
    if depth <= _ATTN_HEADROOM_MAX_DEPTH:
        return {"attn_batch_chunk": 96, "attn_flash_tile_elems": 1 << 26}
    return {"attn_batch_chunk": 32, "attn_flash_tile_elems": 1 << 25}


@dataclasses.dataclass(frozen=True)
class Alphafold2Config:
    dim: int
    depth: int = 6
    heads: int = 8
    dim_head: int = 64
    max_seq_len: int = 2048
    num_tokens: int = NUM_AMINO_ACIDS
    num_embedds: int = NUM_EMBEDDS_TR
    max_num_msa: int = MAX_NUM_MSA
    num_buckets: int = DISTOGRAM_BUCKETS
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    reversible: bool = False
    # jax.checkpoint each trunk layer: O(1) activation memory in depth at
    # ~33% extra FLOPs — the remat sibling of the reversible trunk; works
    # with or without an MSA stream (reversible requires one)
    remat: bool = False
    # rematerialization policy: what the per-layer checkpoint SAVES instead
    # of recomputing. None = save nothing (maximum recompute, minimum
    # memory); "dots" = save all matmul outputs (recompute only elementwise
    # — much cheaper backward, higher residency); "dots_no_batch" = save
    # matmuls without batch dims only (the usual TPU sweet spot). Ignored
    # unless remat=True.
    remat_policy: Optional[str] = None
    # lax.scan the sequential trunk over depth (uniform-sparse-flag runs
    # scan as segments): ONE compiled layer body instead of depth copies —
    # at depth 48 this is the difference between minutes and seconds of
    # XLA compile time. The reversible trunk always scans.
    scan_layers: bool = False
    # bool, or a per-layer tuple of bools (reference cast_tuple semantics,
    # alphafold2.py:25-26,349 — the reference ignores the per-layer value at
    # alphafold2.py:392, a bug; we apply it per layer)
    sparse_self_attn: Union[bool, Tuple[bool, ...]] = False
    sparse_block_size: int = 16
    sparse_num_random_blocks: Optional[int] = None  # None: max_seq_len//block//4
    sparse_num_local_blocks: int = 4
    sparse_num_global_blocks: int = 1
    sparse_layout_seed: int = 0
    # Pallas TPU kernel fast path: True / False / "auto" (kernel for long
    # sequences, XLA block-gather for short — see ops/sparse.py)
    sparse_use_kernel: Union[bool, str] = "auto"
    cross_attn_compress_ratio: int = 1
    # "flat": cross-attention between the fully-flattened pair and MSA
    # streams (reference alphafold2.py:316-317 semantics — O(n^2 * r*c)
    # logits, streamed blockwise at scale). "aligned": column-aligned
    # cross-attention — each pair token attends only the MSA column its grid
    # column maps to, and each MSA token attends only its column's pair-grid
    # block. O(n^2 * r) total: the TPU-first redesign that makes the
    # crop-384 / MSA-128 workload tractable (the pattern the reference built
    # as per-axis context broadcast but never used, alphafold2.py:269-273).
    cross_attn_mode: str = "flat"
    msa_tie_row_attn: bool = False
    # blockwise flash streaming for dense attention: True / False / "auto"
    # (see ops/attention.py AttentionConfig.flash)
    attn_flash: Union[bool, str] = "auto"
    # chunk the folded-batch axis of every dense attention op (QKV/out
    # projections included) into blocks of this many elements (0 = off; see
    # ops/attention.py AttentionConfig.batch_chunk)
    attn_batch_chunk: int = 0
    # XLA flash-streaming tile knobs (AttentionConfig.flash_tile_elems /
    # flash_kv_block): target logit-tile elements and K/V streaming block.
    # Bigger tiles = better MXU utilization, more live memory
    attn_flash_tile_elems: int = 1 << 25
    attn_flash_kv_block: int = 2048
    # sigmoid output gating on every attention op: out = sigmoid(W_g x) *
    # attn(x) before the output projection (the AF2-style gate the
    # reference omits). Gate weights init to (w=0, b=1) so a freshly
    # gated model starts at sigmoid(1) ~ 0.73 * the ungated output. On
    # the TPU kernel path the gate is applied INSIDE the Pallas flash
    # kernel's finish step (ops/flash_kernel.py fused epilogue — no extra
    # HBM round-trip); off-kernel paths apply it as an epilogue. Changes
    # numerics and the parameter tree: part of the serving config tag.
    attn_gate: bool = False
    # intra-layer trunk schedule (models/trunk.py):
    #   "serial"          — the reference op order, one op after another;
    #   "branch_parallel" — the pair track (self-attn + FF) and MSA track
    #     (self-attn + FF) are expressed as two data-independent branches
    #     that JOIN only at the cross-attention exchange (Parallel
    #     Evoformer, arXiv 2211.00235), marked by an optimization-barrier
    #     join the scheduler (and analysis/schedule_lint.py) can see.
    # Same math either way — branch_parallel only re-groups ops that were
    # already independent — so the arms are allclose fwd + grads; still
    # part of the serving config tag (schedules may differ in fusion-level
    # float association, and bit-exactness pins must not alias).
    trunk_schedule: str = "serial"
    # chunk feed-forward token axes into blocks of this many tokens (0 =
    # off): bounds the GEGLU 8*dim intermediate, which at crop 384 is the
    # largest single activation in the trunk. Only the XLA arm's: the
    # kernel arm (ops/geglu_kernel.py) keeps it in VMEM over the whole
    # token axis and ignores the chunk
    ff_chunk_size: int = 0
    template_attn_depth: int = 2
    dtype: Any = jnp.float32
    # weight residency/precision arm (INFERENCE-ONLY):
    #   "f32"  — fp32 master weights, the training/default arm;
    #   "int8" — per-channel symmetric post-training quantization of the
    #     trunk's dense/projection weights (ops/quant.py quantize_tree):
    #     int8 values + f32 per-output-channel scales, dequant fused into
    #     the matmul epilogue on the TPU kernel path
    #     (ops/quant_kernel.py) so no fp32 weight copy ever crosses HBM.
    #     The serving tier quantizes at engine build (keyed by config
    #     tag, serving/quant_residency.py); training entry points reject
    #     this value loudly (ops/quant.py reject_quant_training). Changes
    #     numerics: part of the serving config tag by repr construction.
    weight_dtype: str = "f32"

    def __post_init__(self):
        if self.reversible and self.remat:
            raise ValueError(
                "reversible=True and remat=True are mutually exclusive "
                "activation-memory strategies; pick one"
            )
        if self.cross_attn_mode not in ("flat", "aligned"):
            raise ValueError(
                f"cross_attn_mode must be 'flat' or 'aligned', "
                f"got {self.cross_attn_mode!r}"
            )
        if self.remat_policy not in (None, "dots", "dots_no_batch"):
            raise ValueError(
                f"remat_policy must be None, 'dots', or 'dots_no_batch', "
                f"got {self.remat_policy!r}"
            )
        if self.trunk_schedule not in ("serial", "branch_parallel"):
            raise ValueError(
                f"trunk_schedule must be 'serial' or 'branch_parallel', "
                f"got {self.trunk_schedule!r}"
            )
        if self.weight_dtype not in ("f32", "int8"):
            raise ValueError(
                f"weight_dtype must be 'f32' or 'int8', "
                f"got {self.weight_dtype!r}"
            )
        if self.attn_gate and (
            self.sparse_self_attn is True
            or (isinstance(self.sparse_self_attn, tuple)
                and any(self.sparse_self_attn))
        ):
            raise ValueError(
                "attn_gate is not supported with sparse self-attention "
                "(the block-sparse path has no gate projection)"
            )

    @property
    def layer_sparse(self) -> Tuple[bool, ...]:
        v = self.sparse_self_attn
        return v if isinstance(v, tuple) else (bool(v),) * self.depth

    def sparse_config(self):
        from alphafold2_tpu.ops.sparse import SparseConfig

        return SparseConfig(
            block_size=self.sparse_block_size,
            num_random_blocks=self.sparse_num_random_blocks,
            num_local_blocks=self.sparse_num_local_blocks,
            num_global_blocks=self.sparse_num_global_blocks,
            layout_seed=self.sparse_layout_seed,
            max_seq_len=self.max_seq_len,
        )

    def self_attn_config(self) -> AttentionConfig:
        return AttentionConfig(
            dim=self.dim,
            heads=self.heads,
            dim_head=self.dim_head,
            dropout=self.attn_dropout,
            dtype=self.dtype,
            flash=self.attn_flash,
            batch_chunk=self.attn_batch_chunk,
            flash_tile_elems=self.attn_flash_tile_elems,
            flash_kv_block=self.attn_flash_kv_block,
            gate=self.attn_gate,
        )

    def cross_attn_config(self) -> AttentionConfig:
        return AttentionConfig(
            dim=self.dim,
            heads=self.heads,
            dim_head=self.dim_head,
            dropout=self.attn_dropout,
            compress_ratio=self.cross_attn_compress_ratio,
            dtype=self.dtype,
            flash=self.attn_flash,
            batch_chunk=self.attn_batch_chunk,
            flash_tile_elems=self.attn_flash_tile_elems,
            flash_kv_block=self.attn_flash_kv_block,
            gate=self.attn_gate,
        )
