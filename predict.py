"""Inference entry point: amino-acid sequence -> 3D structure -> PDB.

The reference documents this flow in its README (reference README.md:17-48:
model forward -> distogram -> center_distogram_torch -> MDScaling) but ships
no runnable entry point for it. This CLI runs the whole pipeline on TPU:
trunk forward (optionally with an MSA), distogram centering, MDS with
chirality fix, optional geometric relaxation, and writes a PDB.

Usage:
  python predict.py --seq ACDEFGHIKLMNPQRSTVWY --out structure.pdb
  python predict.py --seq ... --ckpt-dir runs/pre --dim 256 --depth 12
  python predict.py --seq ... --full-atom --ckpt-dir runs/e2e   # model+refiner

--full-atom runs the complete structure pipeline (trunk -> distogram ->
MDS with chirality fix -> sidechain lift -> SE(3) refiner) from an
end-to-end checkpoint (train_end2end.py --ckpt-dir) and writes an
N/CA/C/O backbone PDB that scripts/refinement.py can relax.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", required=True, help="one-letter amino-acid sequence")
    ap.add_argument("--out", default="prediction.pdb")
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dim-head", type=int, default=64)
    ap.add_argument("--mds-iters", type=int, default=200)
    ap.add_argument("--mds-init", choices=("random", "classical"),
                    default="classical",
                    help="MDS starting point. 'classical' (Torgerson "
                         "eigendecomposition, the default) reaches the "
                         "random-init stress floor in ~1 Guttman iteration "
                         "— pair with a small --mds-iters for fast "
                         "inference; 'random' is reference parity")
    ap.add_argument("--msa-file", default=None,
                    help="FASTA/A3M alignment for the MSA track (first "
                         "record = query; lowercase a3m insertions are "
                         "stripped; rows capped at --max-msa-rows)")
    ap.add_argument("--max-msa-rows", type=int, default=20,
                    help="MSA row cap (reference MAX_NUM_MSA)")
    ap.add_argument("--max-num-msa", type=int, default=None,
                    help="MSA row-position-table size; MUST match the "
                         "training config when restoring a checkpoint "
                         "(default: derived from the loaded MSA, min 20 — "
                         "like --max-seq-len for sequence positions)")
    ap.add_argument("--embedds-file", default=None,
                    help=".npz with 'embedds' (1, L, 1280) or (L, 1280): "
                         "precomputed ESM-1b residue embeddings as the MSA "
                         "substitute (reference train_end2end.py:54-59). "
                         "For --full-atom the L axis is the RESIDUE axis; "
                         "it is elongated x3 internally. Unsupported with "
                         "--sp-shards")
    ap.add_argument("--templates-file", default=None,
                    help=".npz with 'templates' (1, T, N, N) int distogram "
                         "buckets in [0, 37) and optional 'templates_mask' "
                         "(1, T, N, N) bool: template conditioning "
                         "(reference README.md:118-150). N must equal the "
                         "model's pair-grid length (L, or 3L for "
                         "--full-atom)")
    ap.add_argument("--ckpt-dir", default=None, help="restore trained params")
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-seq-len", type=int, default=None,
                    help="positional-table size; MUST match the training "
                         "config when restoring a checkpoint (default: "
                         "derived from the input sequence)")
    ap.add_argument("--full-atom", action="store_true",
                    help="full structure pipeline incl. SE(3) refiner from "
                         "an end-to-end checkpoint; writes N/CA/C/O backbone")
    ap.add_argument("--refiner-depth", type=int, default=2)
    ap.add_argument("--sp-shards", type=int, default=0,
                    help="run the trunk sequence-parallel over this many "
                         "devices (sequence length must be a multiple of "
                         "it; 0 = single-device)")
    from alphafold2_tpu.telemetry import (
        add_telemetry_args,
        finish_trace,
        tracer_from_args,
    )

    add_telemetry_args(ap)  # --trace-out / --trace-max-spans
    args = ap.parse_args()

    # persistent compile cache, placed before the first compile
    # (alphafold2_tpu/compile_cache.py: JAX_COMPILATION_CACHE_DIR if set,
    # else <checkout>/.jax_cache)
    from alphafold2_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()

    # multi-host entry: no-op unless the AF2_COORDINATOR/... contract is
    # configured; must run BEFORE the first backend-initializing JAX call
    # (the shared startup errors loudly otherwise; parallel/distributed.py)
    from alphafold2_tpu.parallel.distributed import distributed_startup

    distributed_startup("predict")

    import jax.numpy as jnp

    from alphafold2_tpu.constants import aa_to_tokens
    from alphafold2_tpu.geometry.pdb import coords_to_pdb
    from alphafold2_tpu.models import Alphafold2Config
    from alphafold2_tpu.training import TrainConfig, train_state_init

    seq_str = args.seq.strip().upper()
    # strict tokenization at the CLI boundary: unknown residue letters
    # must fail fast, not silently predict a structure for padding
    try:
        tokens_np = aa_to_tokens(seq_str, strict=True)
    except ValueError as e:
        ap.error(str(e))
    tokens = jnp.asarray(tokens_np)[None]  # (1, L)
    L = tokens.shape[1]

    msa_tokens = msa_mask = None
    if args.msa_file is not None:
        from alphafold2_tpu.utils.msa import load_msa

        msa_np, msa_mask_np = load_msa(
            args.msa_file, query=seq_str, max_rows=args.max_msa_rows
        )
        msa_tokens = jnp.asarray(msa_np)
        msa_mask = jnp.asarray(msa_mask_np)
        print(f"MSA: {msa_tokens.shape[1]} rows x {msa_tokens.shape[2]} "
              f"cols from {args.msa_file}")

    embedds = None
    if args.embedds_file is not None:
        if args.msa_file is not None:
            ap.error("--embedds-file and --msa-file are exclusive (the "
                     "embedds path is the MSA substitute)")
        if args.sp_shards:
            ap.error("--embedds-file is unsupported with --sp-shards (the "
                     "substitute stream has no row axis to shard)")
        raw = np.load(args.embedds_file)
        arr = raw["embedds"] if hasattr(raw, "files") else raw
        if arr.ndim == 2:
            arr = arr[None]
        if arr.shape[1] != L:
            ap.error(f"--embedds-file has {arr.shape[1]} residues; --seq "
                     f"has {L}")
        embedds = np.asarray(arr, np.float32)
        print(f"embedds: {embedds.shape[1]} residues x {embedds.shape[2]} "
              f"dims from {args.embedds_file}")

    templates = templates_mask = None
    if args.templates_file is not None:
        raw = np.load(args.templates_file)
        tarr = np.asarray(raw["templates"])
        # preserve dtype: int arrays are distogram BUCKETS, float arrays are
        # raw Angstrom distances binned by the model itself
        # (models/alphafold2.py templates path) — an unconditional int cast
        # would silently truncate distances into nonsense bucket ids
        if np.issubdtype(tarr.dtype, np.integer):
            if tarr.min() < 0 or tarr.max() >= 37:
                ap.error(f"--templates-file int buckets must be in [0, 37); "
                         f"got range [{tarr.min()}, {tarr.max()}] — pass "
                         f"float distances to have the model bin them")
            templates = jnp.asarray(tarr.astype(np.int32))
        else:
            templates = jnp.asarray(tarr.astype(np.float32))
        if templates.ndim == 3:
            templates = templates[None]
        templates_mask = (
            jnp.asarray(np.asarray(raw["templates_mask"], bool))
            if "templates_mask" in getattr(raw, "files", ())
            else jnp.ones(templates.shape, bool)  # (b, T, N, N) per-position
        )
        if templates_mask.ndim == 3:
            templates_mask = templates_mask[None]
        if templates_mask.shape != templates.shape:
            ap.error(f"--templates-file 'templates_mask' shape "
                     f"{tuple(templates_mask.shape)} does not match "
                     f"'templates' shape {tuple(templates.shape)}")
        grid = 3 * L if args.full_atom else L
        if templates.shape[-2:] != (grid, grid):
            ap.error(f"--templates-file pair grid is "
                     f"{templates.shape[-2]}x{templates.shape[-1]}; the "
                     f"model's is {grid}x{grid} "
                     f"({'3L, elongated' if args.full_atom else 'L'})")
        print(f"templates: {templates.shape[1]} x {templates.shape[-1]}^2 "
              f"grids from {args.templates_file}")

    cfg = Alphafold2Config(
        dim=args.dim,
        depth=args.depth,
        heads=args.heads,
        dim_head=args.dim_head,
        # full-atom mode elongates x3 (one token per backbone atom);
        # --max-seq-len pins the table to the training value for restore
        max_seq_len=args.max_seq_len
        or max(64, 3 * L if args.full_atom else L),
        max_num_msa=args.max_num_msa
        or max(20, msa_tokens.shape[1] if msa_tokens is not None else 0),
        **({"num_embedds": embedds.shape[-1]} if embedds is not None else {}),
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
    )

    tracer = tracer_from_args(args)  # NULL_TRACER unless --trace-out

    # export-in-finally: a crashed prediction keeps its trace (same
    # stance as the trainer loops)
    try:
        if args.full_atom:
            _predict_full_atom(args, cfg, tokens, seq_str, msa_tokens,
                               msa_mask, embedds, templates,
                               templates_mask, tracer=tracer)
            return
        _predict_ca(args, cfg, tokens, seq_str, msa_tokens, msa_mask,
                    embedds, templates, templates_mask, tracer)
    finally:
        finish_trace(tracer, args)


def _predict_ca(args, cfg, tokens, seq_str, msa_tokens, msa_mask,
                embedds, templates, templates_mask, tracer):
    """sequence -> CA trace PDB (the reference README flow)."""
    from alphafold2_tpu.geometry.pdb import coords_to_pdb
    from alphafold2_tpu.training import TrainConfig, train_state_init

    L = tokens.shape[1]
    from alphafold2_tpu.models import alphafold2_init
    from alphafold2_tpu.training import restore_params_for_inference

    params, _, _ = restore_params_for_inference(
        args.ckpt_dir, train_state_init, jax.random.PRNGKey(0), cfg,
        TrainConfig(),
        cold_params_fn=lambda: alphafold2_init(jax.random.PRNGKey(0), cfg),
    )

    # the pipeline body lives in serving/pipeline.py — one pure function
    # shared by this CLI and the batching serving engine (serve.py)
    from alphafold2_tpu.serving.pipeline import predict_structure

    model_apply_fn = None
    if args.sp_shards:
        # trunk sequence-parallel over the mesh; embeddings/head replicated
        from alphafold2_tpu.parallel import alphafold2_apply_sp, make_mesh

        mesh = make_mesh({"seq": args.sp_shards})

        def model_apply_fn(p, c, s, m, *, mask=None, msa_mask=None,
                           embedds=None, templates=None, templates_mask=None):
            del embedds  # CLI already rejects --embedds-file with --sp-shards
            return alphafold2_apply_sp(
                p, c, s, m, mesh, mask=mask, msa_mask=msa_mask,
                templates=templates, templates_mask=templates_mask,
            )

    def run(p, t, m, mm, e, tp, tpm):
        out = predict_structure(
            p, cfg, t, msa=m, msa_mask=mm, embedds=e,
            templates=tp, templates_mask=tpm,
            rng=jax.random.PRNGKey(args.seed),
            mds_iters=args.mds_iters, mds_init=args.mds_init,
            model_apply_fn=model_apply_fn,
        )
        # the (1, L, L, 37) distogram logits stay on device — nothing
        # below reads them (same stance as serving/engine.py)
        return {k: out[k] for k in ("coords", "confidence", "stress")}

    # one span per one-shot phase: compile+forward dominates, and the
    # fetch (np.asarray) is what actually waits on the device
    with tracer.span("predict.forward", cat="predict", length=L):
        out = jax.jit(run)(params, tokens, msa_tokens, msa_mask, embedds,
                           templates, templates_mask)
        trace = np.asarray(out["coords"][0])  # (L, 3)
    print(f"MDS final stress: {float(out['stress'][0]):.4f}")

    # per-residue confidence from distogram entropy, written as B-factors
    # (x100, pLDDT-style; the reference exposes no confidence signal)
    conf = np.asarray(out["confidence"])[0]
    print(f"mean confidence: {100 * conf.mean():.1f}/100")

    # NOTE: geometric relaxation (scripts/refinement.py) operates on full
    # N/CA/C backbones; a CA-only trace has no bond structure to relax
    with tracer.span("predict.write_pdb", cat="predict", length=L):
        coords_to_pdb(args.out, trace, sequence=seq_str, atom_names=("CA",),
                      bfactors=100.0 * conf)
    print(f"wrote {args.out} ({L} residues)")


def _predict_full_atom(args, cfg, tokens, seq_str, msa_tokens=None,
                       msa_mask=None, embedds=None, templates=None,
                       templates_mask=None, tracer=None):
    """sequence -> refined 14-atom cloud -> N/CA/C/O backbone PDB."""
    import jax.numpy as jnp

    from alphafold2_tpu.telemetry import NULL_TRACER

    tracer = tracer if tracer is not None else NULL_TRACER

    from alphafold2_tpu.geometry.pdb import coords_to_pdb
    from alphafold2_tpu.models import RefinerConfig
    from alphafold2_tpu.training import (
        E2EConfig,
        TrainConfig,
        e2e_train_state_init,
        predict_structure,
    )

    ecfg = E2EConfig(
        model=cfg,
        refiner=RefinerConfig(num_tokens=14, dim=64, depth=args.refiner_depth),
        mds_iters=args.mds_iters,
        mds_init=args.mds_init,
    )
    from alphafold2_tpu.training import restore_params_for_inference
    from alphafold2_tpu.training.e2e import e2e_params_init

    params, _, _ = restore_params_for_inference(
        args.ckpt_dir, e2e_train_state_init, jax.random.PRNGKey(0), ecfg,
        TrainConfig(),
        cold_params_fn=lambda: e2e_params_init(jax.random.PRNGKey(0), ecfg),
    )

    model_apply_fn = None
    if args.sp_shards:
        from alphafold2_tpu.parallel import make_mesh, sp_model_apply

        model_apply_fn = sp_model_apply(make_mesh({"seq": args.sp_shards}))

    if embedds is not None:
        # per-RESIDUE embeddings -> per-backbone-atom (x3 elongation), the
        # same host-side repeat training applies (train_end2end.py)
        embedds = np.repeat(np.asarray(embedds), 3, axis=1)

    with tracer.span("predict.forward", cat="predict",
                     length=int(tokens.shape[1]), full_atom=True):
        out = jax.jit(
            lambda p, t, m, mm, e, tp, tpm: predict_structure(
                p, ecfg, t, rng=jax.random.PRNGKey(args.seed),
                msa=m, msa_mask=mm, embedds=e, templates=tp,
                templates_mask=tpm, model_apply_fn=model_apply_fn,
            )
        )(params, tokens, msa_tokens, msa_mask, embedds, templates,
          templates_mask)
        backbone = np.asarray(out["refined"])[0, :, :4]  # N, CA, C, O slots

    # per-residue confidence from distogram entropy -> B-factors (x100,
    # pLDDT-style). The distogram is over the 3x-elongated backbone-atom
    # axis (one token per N/CA/C atom); average the three atoms per residue.
    from alphafold2_tpu.geometry import distogram_confidence

    probs = jax.nn.softmax(
        jnp.asarray(out["distogram_logits"]).astype(jnp.float32), axis=-1
    )
    conf3 = np.asarray(distogram_confidence(probs))[0]  # (3L,)
    conf = conf3.reshape(-1, 3).mean(axis=1)
    print(f"mean confidence: {100 * conf.mean():.1f}/100")

    coords_to_pdb(
        args.out, backbone.reshape(-1, 3), sequence=seq_str,
        atom_names=("N", "CA", "C", "O"), bfactors=100.0 * conf,
    )
    print(f"wrote {args.out} ({tokens.shape[1]} residues, full pipeline)")


if __name__ == "__main__":
    main()
