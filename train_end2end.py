"""End-to-end structure training entry point.

The reference's `train_end2end.py` is a non-runnable specification (SURVEY.md
§3.2 lists its defects: unbound names, wrong kwargs, missing imports). This
is the working TPU-native realization of its *intended* pipeline
(reference train_end2end.py:104-183): trunk -> distogram -> MDS + mirror
fix -> sidechain lift -> SE(3)-equivariant refiner -> Kabsch RMSD +
dispersion loss, all inside ONE jitted train step with scanned gradient
accumulation.

Usage: python train_end2end.py [--steps N] [--dim 64] [--depth 2] [--len 16]
"""

from __future__ import annotations

import argparse

import jax

from alphafold2_tpu.models import Alphafold2Config, RefinerConfig
from alphafold2_tpu.telemetry import (
    MetricRegistry,
    add_observability_args,
    add_telemetry_args,
    build_train_telemetry,
    compile_record,
    finish_trace,
    observability_enabled,
    per_process_metrics_path,
    tracer_from_args,
)
from alphafold2_tpu.training import (
    DataConfig,
    E2EConfig,
    TrainConfig,
    add_resilience_args,
    add_train_args,
    chaos_from_args,
    tcfg_from_args,
    e2e_loss_fn,
    e2e_train_state_init,
    finish,
    make_train_step,
    open_or_init,
    resilient_batches,
    resilient_mode,
    run_resilient,
    stack_microbatches,
    synthetic_microbatch_fn,
    synthetic_structure_batches,
    with_fault_injection,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--dim-head", type=int, default=16)
    ap.add_argument("--len", dest="max_len", type=int, default=16)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--accum", type=int, default=2)
    ap.add_argument("--mds-iters", type=int, default=20)
    ap.add_argument("--mds-init", choices=["classical", "random"],
                    default="classical",
                    help="MDS warm start: 'classical' (Torgerson "
                         "eigendecomposition — the promoted training "
                         "default, reaches the random-init stress floor "
                         "in ~1 iteration) or 'random' (reference parity)")
    ap.add_argument("--mds-reference", action="store_true",
                    help="restore the retired reference MDS arm for "
                         "parity runs: 200 iterations from a random init "
                         "(reference train_end2end.py:157), overriding "
                         "--mds-iters/--mds-init")
    ap.add_argument("--mds-bwd-iters", type=int, default=None,
                    help="truncate MDS backprop to the last K iterations "
                         "(implicit-diff approximation; None = full unroll)")
    ap.add_argument("--refiner-depth", type=int, default=2)
    ap.add_argument("--sp-shards", type=int, default=0,
                    help="shard the trunk sequence-parallel over this many "
                         "devices (3*--len and MSA rows must be multiples "
                         "of it; deterministic path; 0 = replicated)")
    ap.add_argument("--reversible", action="store_true",
                    help="reversible trunk: O(1) activation memory in "
                         "depth (the north-star depth-48 config, "
                         "BASELINE.md config 5)")
    ap.add_argument("--trunk-segments", type=int, default=0,
                    help="run each step as this many reversible-trunk "
                         "segments in SEPARATE device executions "
                         "(training/segmented.py) — for runtimes that "
                         "bound single-execution device time; requires "
                         "--reversible; identical numerics to the "
                         "monolithic step; 0 = one jitted step")
    add_train_args(ap)
    ap.add_argument("--bf16", action="store_true", help="bfloat16 compute")
    # the reference's FEATURES switch (reference train_end2end.py:20-28):
    # msa = synthetic MSA stream, esm = ESM residue embeddings through the
    # model's `embedds` path, none = sequence only
    ap.add_argument("--features", choices=["msa", "esm", "none"], default="msa")
    ap.add_argument("--msa-rows", type=int, default=4)
    ap.add_argument("--esm-dim", type=int, default=128,
                    help="embedder width (1280 = real ESM-1b)")
    ap.add_argument("--esm-layers", type=int, default=2,
                    help="embedder depth (33 = real ESM-1b)")
    ap.add_argument("--esm-heads", type=int, default=4,
                    help="attention heads (20 = real ESM-1b)")
    ap.add_argument("--esm-ckpt", default=None,
                    help="npz of a torch ESM state dict to convert+load "
                         "(random init otherwise)")
    ap.add_argument("--esm-token-dropout", type=int, default=1,
                    help="1 = real ESM-1b inference semantics (mask-"
                         "dropout rescale; the reference's hub model "
                         "applies it); 0 reproduces pre-round-4 "
                         "embeddings")
    ap.add_argument("--data", choices=["synthetic", "sidechainnet"],
                    default="synthetic")
    ap.add_argument("--ckpt-dir", default=None, help="checkpoint/resume directory")
    ap.add_argument("--ckpt-every", type=int, default=25)
    add_resilience_args(ap)  # --max-restarts / --ckpt-verify / --fault-plan
    add_telemetry_args(ap)   # --trace-out / --trace-max-spans
    add_observability_args(ap)  # --ops-port / --flight-dir / --federate-every
    ap.add_argument("--eval-every", type=int, default=0, help="0 = no eval")
    ap.add_argument("--metrics-jsonl", default=None, help="JSONL metrics stream")
    ap.add_argument("--profile-dir", default=None, help="jax.profiler trace dir")
    ap.add_argument(
        "--profile-steps", type=int, default=10,
        help="trace this many steps (starting after compile at step start+1)",
    )
    args = ap.parse_args()

    # persistent compile cache, placed before the first compile
    # (alphafold2_tpu/compile_cache.py: JAX_COMPILATION_CACHE_DIR if set,
    # else <checkout>/.jax_cache)
    from alphafold2_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()

    # multi-host entry: no-op unless AF2_COORDINATOR/AF2_NUM_PROCESSES/
    # AF2_PROCESS_ID (or AF2_AUTO_INIT=1 on TPU pods) are set — one command
    # per host, BEFORE the first backend-initializing JAX call (the shared
    # startup errors loudly otherwise; parallel/distributed.py)
    from alphafold2_tpu.parallel.distributed import distributed_startup

    distributed_startup("train_end2end")
    procs = jax.process_count()
    if procs > 1:
        # validate the pod contract BEFORE any manager/state is built
        bad = None
        if args.sp_shards:
            bad = "--sp-shards shards the grid single-process; pods shard the batch (DP)"
        elif args.trunk_segments:
            bad = "--trunk-segments is a single-device execution chain"
        elif args.data != "synthetic" or args.features == "esm":
            bad = ("multi-host training runs --data synthetic with msa/none "
                   "features (no per-process contract for stateful sources)")
        elif args.fault_plan:
            bad = "--fault-plan is single-process chaos tooling"
        elif args.batch % jax.device_count():
            bad = (f"--batch {args.batch} is the GLOBAL batch and must "
                   f"divide across jax.device_count()="
                   f"{jax.device_count()} devices ({procs} processes x "
                   f"{jax.local_device_count()} local) — the DP mesh "
                   "spans every chip of the pod")
        elif args.ckpt_dir and not args.ckpt_verify:
            bad = ("multi-host checkpointing needs the verified manager — "
                   "add --ckpt-verify")
        elif args.profile_dir:
            bad = "--profile-dir is single-process tooling"
        if bad:
            raise SystemExit(bad)

    import jax.numpy as jnp

    ecfg = E2EConfig(
        model=Alphafold2Config(
            dim=args.dim,
            depth=args.depth,
            heads=args.heads,
            dim_head=args.dim_head,
            # the trunk sees the x3-elongated backbone sequence
            max_seq_len=max(64, 3 * args.max_len),
            max_num_msa=max(20, args.msa_rows),
            # only the esm features mode resizes the embedds projection;
            # other modes keep the default so checkpoints stay resumable
            # regardless of the (unused) --esm-dim flag
            **({"num_embedds": args.esm_dim} if args.features == "esm" else {}),
            reversible=args.reversible,
            dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        ),
        refiner=RefinerConfig(num_tokens=14, dim=64, depth=args.refiner_depth),
        mds_iters=200 if args.mds_reference else args.mds_iters,
        mds_init="random" if args.mds_reference else args.mds_init,
        mds_bwd_iters=args.mds_bwd_iters,
    )
    tcfg = tcfg_from_args(args, grad_accum=args.accum)
    dcfg = DataConfig(
        batch_size=args.batch,
        max_len=args.max_len,
        msa_rows=args.msa_rows if args.features == "msa" else 0,
        seed=args.seed,
    )

    resilient = resilient_mode(args)
    injector, ckpt_fault_hook, max_restarts = chaos_from_args(args)
    mgr, state, resumed = open_or_init(
        args.ckpt_dir, e2e_train_state_init, jax.random.PRNGKey(args.seed), ecfg, tcfg,
        save_every=args.ckpt_every, verify=args.ckpt_verify,
        fault_hook=ckpt_fault_hook,
    )

    it = None
    if args.data == "sidechainnet":
        from alphafold2_tpu.training import sidechainnet_structure_batches

        it = sidechainnet_structure_batches(dcfg)
        if it is None:
            print("sidechainnet unavailable; falling back to synthetic data")
        elif resumed:
            print("note: sidechainnet stream restarts from its top on resume "
                  "(only synthetic data is positionally resumable)")
    if it is None:
        # synthetic batches are a pure function of their index: a resumed
        # run jumps the stream to its exact position in O(1), no replay
        it = synthetic_structure_batches(
            dcfg, start_index=int(state["step"]) * tcfg.grad_accum
        )

    if args.features == "esm":
        # ESM residue embeddings -> the model's `embedds` path (reference
        # train_end2end.py:37-43,54-59,125-126): embed per residue, then
        # repeat x3 so every backbone-atom token carries its residue's
        # embedding (the reference's elongation, train_end2end.py:136-146)
        import numpy as np

        from alphafold2_tpu.models.embedder import (
            EmbedderConfig,
            convert_esm_state_dict,
            convert_hf_esm_state_dict,
            embed_sequences,
            embedder_init,
        )

        e_cfg = EmbedderConfig(
            num_layers=args.esm_layers, dim=args.esm_dim, heads=args.esm_heads,
            max_len=max(1024, args.max_len + 2),
            # default ON = the torch.hub ESM-1b inference semantics the
            # reference feeds (0.88x mask-dropout rescale); the flag
            # exists to reproduce embeddings from runs predating it
            token_dropout=bool(args.esm_token_dropout),
        )
        if args.esm_ckpt:
            sd = dict(np.load(args.esm_ckpt, allow_pickle=True))
            # both published formats load: fair-esm torch.hub state dicts
            # and transformers EsmModel state dicts (detected by key style)
            hf_style = any(
                k.startswith(("esm.", "encoder.layer.", "embeddings."))
                for k in sd
            )
            convert = convert_hf_esm_state_dict if hf_style else convert_esm_state_dict
            e_params = convert(sd, e_cfg)
            print(f"loaded converted ESM weights from {args.esm_ckpt} "
                  f"({'transformers' if hf_style else 'fair-esm'} layout)")
        else:
            e_params = embedder_init(jax.random.PRNGKey(42), e_cfg)
            print("esm features with RANDOM embedder weights (pass "
                  "--esm-ckpt for real ESM-1b)")
        embed = jax.jit(
            lambda seq, mask: embed_sequences(e_params, e_cfg, seq, mask)
        )

        def with_embedds(src):
            for b in src:
                reps = embed(jnp.asarray(b["seq"]), jnp.asarray(b["mask"]))
                b = dict(b)
                b["embedds"] = np.repeat(np.asarray(reps), 3, axis=1)
                yield b

        it = with_embedds(it)

    batches = stack_microbatches(it, tcfg.grad_accum)
    if args.sp_shards and args.trunk_segments:
        raise SystemExit("--sp-shards and --trunk-segments are exclusive: "
                         "the segmented step is a single-device execution "
                         "chain")
    if args.trunk_segments and not args.reversible:
        raise SystemExit("--trunk-segments requires --reversible (segment "
                         "backward IS reversible reconstruction)")
    if resilient and args.trunk_segments:
        raise SystemExit("--max-restarts/--fault-plan and --trunk-segments "
                         "are exclusive: the segmented chain donates state "
                         "internally, which invalidates the supervisor's "
                         "rollback reference")
    # --- live training observability (built BEFORE the step so the pod
    # path can account global-batch assembly into the goodput ledger) ----
    if args.metrics_jsonl and procs > 1:
        # per-process sidecars (metrics.p<i>.jsonl): federation's live
        # pod view gets a durable on-disk twin per host
        args.metrics_jsonl = per_process_metrics_path(
            args.metrics_jsonl, jax.process_index())
    from alphafold2_tpu.utils import MetricsLogger

    logger = MetricsLogger(
        jsonl_path=args.metrics_jsonl, print_every=10,
        process_index=jax.process_index() if procs > 1 else None)
    tracer = tracer_from_args(args)  # NULL_TRACER unless --trace-out / --profile-dir
    registry = MetricRegistry(
        enabled=tracer.enabled or observability_enabled(args))
    from alphafold2_tpu.utils.flops import train_step_flops

    telemetry = build_train_telemetry(
        args, registry=registry, tracer=tracer, logger=logger,
        # pair side is the x3-elongated backbone; MSA columns stay at the
        # CROP length (data.py builds msa as (b, rows, max_len))
        step_flops=train_step_flops(
            ecfg.model, 3 * args.max_len,
            args.msa_rows if args.features == "msa" else 0,
            args.max_len, grad_accum=tcfg.grad_accum),
    )

    if procs > 1:
        # pod path: DP over a process-spanning mesh; per-process pipelines
        # feed local shards, assembled into global arrays every step
        # (parallel/train.py make_multihost_train_step; same contract as
        # train_pre.py)
        from alphafold2_tpu.parallel import make_multihost_train_step
        from alphafold2_tpu.parallel.sharding import host_to_global
        from alphafold2_tpu.training import process_shard

        example_local = process_shard(
            synthetic_microbatch_fn(
                dcfg, tcfg.grad_accum, source=synthetic_structure_batches
            )(int(state["step"])),
            axis=1,
        )
        jitted, st_shardings, assemble, _mh_mesh = make_multihost_train_step(
            ecfg, tcfg, example_local,
            loss_fn=e2e_loss_fn, state_init=e2e_train_state_init,
            tp=False, donate_state=not resilient, telemetry=telemetry,
        )
        state = host_to_global(state, st_shardings)

        def train_step(st, batch, rng=None):
            return jitted(st, assemble(batch), rng)

        def _local(src):
            for b in src:
                yield process_shard(b, axis=1)

        batches = _local(batches)
    elif args.sp_shards:
        from alphafold2_tpu.parallel import make_mesh, make_sp_train_step, sp_e2e_loss_fn

        mesh = make_mesh({"seq": args.sp_shards})
        # the resilient supervisor keeps a rollback reference to the
        # pre-step state, so donation must be off under it
        train_step = make_sp_train_step(
            ecfg, tcfg, mesh, loss_fn=sp_e2e_loss_fn(mesh),
            donate_state=not resilient,
        )
    elif args.trunk_segments:
        # multi-execution step: each piece jits itself; the chain donates
        # state at the optimizer, same live-footprint win as below
        from alphafold2_tpu.training import make_segmented_train_step

        train_step = make_segmented_train_step(ecfg, tcfg,
                                               args.trunk_segments)
    else:
        # donated state: see train_pre.py — halves the live state footprint
        # (the resilient supervisor needs the non-donating step)
        train_step = jax.jit(make_train_step(ecfg, tcfg, loss_fn=e2e_loss_fn),
                             donate_argnums=() if resilient else (0,))

    from alphafold2_tpu.training import predict_structure
    from alphafold2_tpu.utils import structure_eval

    # eval must see the SAME feature inputs training does — evaluating a
    # sequence-only forward of an MSA/ESM-trained model would report
    # metrics for an untrained configuration
    eval_fwd = jax.jit(
        lambda p, seq, mask, rng, msa, msa_mask, embedds: predict_structure(
            p, ecfg, seq, mask=mask, rng=rng,
            msa=msa, msa_mask=msa_mask, embedds=embedds,
        )
    )

    if args.eval_every and procs > 1:
        print("note: --eval-every is ignored on multi-host runs (the "
              "structure eval is a single-process convenience)")
        args.eval_every = 0

    base_rng = jax.random.fold_in(jax.random.PRNGKey(args.seed), 1)
    start = int(state["step"])
    if resumed:
        print(f"resumed from step {start} in {args.ckpt_dir}")

    # bounded profiler window AFTER the compile step, so the trace stays
    # loadable and is not dominated by step-0 compilation; a 1-step run
    # traces its only step (compile included) rather than nothing
    prof_beg = start + 1 if args.steps > 1 else start
    prof_end = prof_beg + max(1, args.profile_steps)
    profiling = False

    if resilient:
        # supervised loop: StepGuard rollback + checkpoint-restore restarts
        # + preemption-safe shutdown (+ the --fault-plan chaos hooks)
        from alphafold2_tpu.reliability import Preempted, PreemptionHandler

        if args.eval_every:
            print("note: --eval-every is ignored under the resilient loop")
        if args.profile_dir:
            print("note: --profile-dir is ignored under the resilient loop")
        if args.data == "synthetic" and args.features != "esm":
            # step-indexed fetch: a retried/resumed step refetches the
            # IDENTICAL batch, making recovery replay-exact (the esm
            # feature wrapper is iterator-shaped, so it keeps `next`
            # semantics). On a pod the fetch yields only THIS process's
            # rows (same purity)
            if procs > 1:
                from alphafold2_tpu.training import per_process_microbatch_fn

                source = per_process_microbatch_fn(
                    dcfg, tcfg.grad_accum,
                    source=synthetic_structure_batches,
                )
            else:
                source = synthetic_microbatch_fn(
                    dcfg, tcfg.grad_accum, source=synthetic_structure_batches
                )
        else:
            source = batches
        fetch = resilient_batches(source, injector=injector)
        step_fn = with_fault_injection(train_step, injector)
        handler = PreemptionHandler().install()
        if injector is not None:
            injector.bind_preemption(handler)
        try:
            state = run_resilient(
                step_fn, state, fetch, steps=args.steps,
                make_rng=lambda i: jax.random.fold_in(base_rng, i),
                mgr=mgr, on_metrics=logger.log,
                max_restarts=max_restarts, logger=logger,
                preemption=handler, tracer=tracer, telemetry=telemetry,
            )
        except Preempted as e:
            # checkpointed + closed by the loop; exit 0 — not a failure
            print(e)
            return
        finally:
            handler.uninstall()
            telemetry.close()
            logger.close()
            finish_trace(tracer, args)  # a preempted run keeps its trace
        if injector is not None and not injector.exhausted():
            print(f"warning: fault plan only partially delivered: "
                  f"{injector.delivered}")
        print("done")
        return

    try:
        for step in range(start, start + args.steps):
            if args.profile_dir and step == prof_beg and not profiling:
                jax.profiler.start_trace(args.profile_dir)
                profiling = True
            # per-step key derived from the step index: identical schedule
            # whether the run is fresh or resumed
            step_rng = jax.random.fold_in(base_rng, step)
            with tracer.span("train.fetch", cat="train", step=step), \
                    telemetry.account("data_fetch"):
                batch = next(batches)
            step_bucket = telemetry.step_bucket()
            with tracer.span("train.step", cat="train", step=step), \
                    telemetry.account(step_bucket):
                state, metrics = train_step(state, batch, step_rng)
            # logger.log is the step's device sync: this span absorbs the
            # async-dispatched execution train.step only launched
            with tracer.span("train.metrics_fetch", cat="train",
                             step=step), telemetry.account(step_bucket):
                logger.log(step, metrics)
            if step == start:
                # the step has traced and compiled: which arm every
                # dispatched call site of it runs, at the shapes it saw, and
                # the bytes that the batch chunks' checkpoints keep of ONE
                # axial pass of the pair stream (ops/attention.py
                # _checkpointed_chunk): the whole-row kernel's two results
                # where that is the pass's core, nothing elsewhere
                from alphafold2_tpu.ops import dispatch
                from alphafold2_tpu.ops.flash import rows_saved_bytes

                m, side = ecfg.model, 3 * args.max_len
                rows = args.batch * side  # the other axis folds into the batch
                kernel_core = (0 < m.attn_batch_chunk < rows and not m.attn_gate
                               and not m.attn_dropout and m.attn_flash is not False)
                logger.event(
                    step, "dispatch", decisions=dispatch.decisions(),
                    chunk_checkpoint_saves=rows_saved_bytes(
                        rows, side, side, m.heads, m.dim_head, m.dtype)
                    if kernel_core else {})
                # how much of the start was compiling, and whether the
                # compile cache served it
                logger.event(step, "compile", **compile_record.totals(top=5))
            telemetry.step_complete(step)
            if args.eval_every and (step + 1) % args.eval_every == 0:
                # structure quality on the last microbatch (the reference's
                # metrics library, finally wired into a loop)
                with tracer.span("train.eval", cat="train", step=step), \
                        telemetry.account("eval"):
                    mb = {k: v[-1] for k, v in batch.items()}
                    out = eval_fwd(
                        state["params"], mb["seq"], mb["mask"], step_rng,
                        mb.get("msa"), mb.get("msa_mask"), mb.get("embedds"),
                    )
                    b = mb["seq"].shape[0]
                    scores = structure_eval(
                        out["refined"].reshape(b, -1, 3),
                        mb["coords"].reshape(b, -1, 3),
                        mask=out["cloud_mask"].reshape(b, -1),
                    )
                logger.log(step, scores)  # into the JSONL stream too
                print("eval  " + "  ".join(f"{k} {v:.4f}" for k, v in scores.items()))
            if mgr is not None:
                with tracer.span("train.checkpoint", cat="train",
                                 step=step), telemetry.account("checkpoint"):
                    mgr.save(state)  # save_interval_steps gates the cadence
            if profiling and step + 1 >= prof_end:
                jax.profiler.stop_trace()
                profiling = False
    finally:
        if profiling:
            jax.profiler.stop_trace()
        # a crashed or interrupted run keeps its trace — the moment it is
        # most wanted (same stance as the resilient branch)
        telemetry.close()
        finish_trace(tracer, args)
    logger.close()
    finish(mgr, state)
    print("done")


if __name__ == "__main__":
    main()
