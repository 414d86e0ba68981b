"""Distogram pretraining entry point (reference train_pre.py, re-designed).

The reference runs a Python loop with 16 eager .backward() calls per
optimizer step on one GPU (reference train_pre.py:72-102). Here the whole
optimizer step — 16 scanned microbatches, grads, Adam update — is ONE jitted
XLA program; data arrives from the static-shape pipeline.

Usage: python train_pre.py [--steps N] [--dim 256] [--depth 1] [--len 128]
"""

from __future__ import annotations

import argparse
import time

import jax

from alphafold2_tpu.models import Alphafold2Config
from alphafold2_tpu.telemetry import (
    MetricRegistry,
    add_observability_args,
    add_telemetry_args,
    build_train_telemetry,
    compile_record,
    device_memory_gauges,
    finish_trace,
    flops_gauges,
    observability_enabled,
    per_process_metrics_path,
    tracer_from_args,
)
from alphafold2_tpu.utils import MetricsLogger
from alphafold2_tpu.training import (
    DataConfig,
    TrainConfig,
    add_resilience_args,
    add_train_args,
    chaos_from_args,
    tcfg_from_args,
    finish,
    make_train_step,
    open_or_init,
    resilient_batches,
    resilient_mode,
    run_resilient,
    sidechainnet_batches,
    stack_microbatches,
    synthetic_batches,
    synthetic_microbatch_fn,
    train_state_init,
    with_fault_injection,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--depth", type=int, default=1)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dim-head", type=int, default=64)
    ap.add_argument("--len", dest="max_len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--accum", type=int, default=16)
    add_train_args(ap)
    ap.add_argument("--bf16", action="store_true", help="bfloat16 compute")
    ap.add_argument(
        "--data", choices=["synthetic", "sidechainnet", "native"], default="synthetic"
    )
    ap.add_argument("--ckpt-dir", default=None, help="checkpoint/resume directory")
    ap.add_argument("--ckpt-every", type=int, default=50)
    add_resilience_args(ap)  # --max-restarts / --ckpt-verify / --fault-plan
    add_telemetry_args(ap)   # --trace-out / --trace-max-spans
    add_observability_args(ap)  # --ops-port / --flight-dir / --federate-every
    ap.add_argument("--metrics-log", default=None, help="JSONL metrics file")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="evaluate held-out distogram loss every N steps "
                         "(0 = off)")
    ap.add_argument("--len-buckets", default=None,
                    help="comma-separated static length buckets (e.g. "
                         "64,128,256): variable-length proteins batch into "
                         "the smallest holding bucket instead of all "
                         "padding to --len (one jit compile per bucket). "
                         "Applies to --data native; batches are assembled "
                         "off-GIL inside the C++ prefetch loader. The "
                         "largest bucket must equal --len.")
    ap.add_argument("--sp-shards", type=int, default=0,
                    help="shard the pair grid over this many devices "
                         "(sequence-parallel trunk; --len must be a "
                         "multiple of it; 0 = replicated)")
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

    distributed_startup("train_pre")
    procs = jax.process_count()
    if procs > 1:
        # validate the pod contract BEFORE any manager/state is built
        if args.sp_shards:
            raise SystemExit(
                "--sp-shards is the single-process grid-sharding path; "
                "multi-host runs shard the batch (DP) — drop the flag"
            )
        if args.data != "synthetic":
            raise SystemExit(
                f"--data {args.data} has no per-process sharding contract "
                "yet; multi-host training runs --data synthetic"
            )
        if args.fault_plan:
            raise SystemExit(
                "--fault-plan is single-process chaos tooling; a per-host "
                "injected fault would desync the SPMD step — run chaos "
                "drills single-process"
            )
        if args.batch % jax.device_count():
            raise SystemExit(
                f"--batch {args.batch} is the GLOBAL batch and must "
                f"divide across jax.device_count()={jax.device_count()} "
                f"devices ({procs} processes x "
                f"{jax.local_device_count()} local) — the DP mesh spans "
                "every chip of the pod"
            )
        if args.ckpt_dir and not args.ckpt_verify:
            raise SystemExit(
                "multi-host checkpointing runs through the verified "
                "manager (process-0 writes + cross-process barrier + "
                "broadcast-consistent restore) — add --ckpt-verify"
            )

    import jax.numpy as jnp

    cfg = Alphafold2Config(
        dim=args.dim,
        depth=args.depth,
        heads=args.heads,
        dim_head=args.dim_head,
        max_seq_len=max(2048, args.max_len),
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
    )
    tcfg = tcfg_from_args(args, grad_accum=args.accum)
    dcfg = DataConfig(batch_size=args.batch, max_len=args.max_len,
                      seed=args.seed)

    resilient = resilient_mode(args)
    injector, ckpt_fault_hook, max_restarts = chaos_from_args(args)
    mgr, state, resumed = open_or_init(
        args.ckpt_dir, train_state_init, jax.random.PRNGKey(args.seed), cfg, tcfg,
        save_every=args.ckpt_every, verify=args.ckpt_verify,
        fault_hook=ckpt_fault_hook,
    )
    start = int(state["step"])

    it = None
    if args.data == "sidechainnet":
        it = sidechainnet_batches(dcfg)
        if it is None:
            print("sidechainnet unavailable; falling back to synthetic data")
    elif args.data == "native":
        # C++ threaded prefetch loader (alphafold2_tpu/runtime): batch
        # assembly runs off the GIL; here it serves a synthetic in-memory
        # structure pool, the same path a real corpus would use
        import numpy as np

        from alphafold2_tpu.runtime import NativePrefetchLoader

        rs = np.random.RandomState(dcfg.seed)
        pool = []
        for _ in range(256):
            L = rs.randint(32, 4 * args.max_len)
            seq = rs.randint(0, 21, L).astype(np.int32)
            cloud = np.cumsum(
                3.8 * rs.randn(L, 14, 3).astype(np.float32), axis=0
            )
            pool.append((seq, cloud))
        buckets = None
        if args.len_buckets:
            # length bucketing: a closed set of static shapes instead of
            # one big pad target. Assembled INSIDE the C++ loader (off the
            # GIL) — csrc/af2_runtime.cc bucketed worker mode.
            buckets = tuple(sorted(set(
                int(x) for x in args.len_buckets.split(","))))
            if buckets[-1] != args.max_len:
                raise SystemExit(
                    f"--len-buckets largest bucket ({buckets[-1]}) must "
                    f"equal --len ({args.max_len}) — the top bucket is the "
                    f"crop length the model is sized for"
                )
            if args.sp_shards:
                bad = [b for b in buckets if b % args.sp_shards]
                if bad:
                    raise SystemExit(
                        f"--len-buckets {bad} not divisible by "
                        f"--sp-shards {args.sp_shards} (sp_trunk needs the "
                        f"pair side to divide the mesh axis)"
                    )
            print(f"length buckets: {buckets}")
        loader = NativePrefetchLoader(
            pool, batch_size=args.batch, max_len=args.max_len,
            seed=dcfg.seed, n_threads=2, buckets=buckets,
        )
        print("native prefetch loader: "
              f"{'C++' if loader.native else 'python fallback'}")

        def native_gen():
            while True:
                b = loader.next()
                out = {
                    "seq": b["seq"],
                    "mask": b["mask"],
                    # CA trace (atom slot 1) drives the distogram labels
                    "coords": b["coords"][:, :, 1],
                }
                if "bucket" in b:
                    out["bucket"] = b["bucket"]
                yield out

        it = native_gen()
    if it is None:
        # synthetic batches are a pure function of their index, so a resumed
        # run jumps the stream to the exact position in O(1) (no replay)
        it = synthetic_batches(dcfg, start_index=start * tcfg.grad_accum)
    elif resumed:
        # stateful sources (sidechainnet shuffle, native loader threads) are
        # not positionally replayable; the resumed run restarts their stream
        # with a fresh shuffle — documented divergence, not silent
        print(f"note: --data {args.data} stream restarts from its top on "
              "resume (only synthetic data is positionally resumable)")
    if args.len_buckets and args.data == "native":
        from alphafold2_tpu.training import bucketed_microbatches

        batches = bucketed_microbatches(it, tcfg.grad_accum)
    else:
        batches = stack_microbatches(it, tcfg.grad_accum)

    # --- live training observability (built BEFORE the step so the pod
    # path can account global-batch assembly into the goodput ledger) ----
    if args.metrics_log and procs > 1:
        # per-process sidecars (metrics.p<i>.jsonl): the pod's metrics
        # stream is no longer a proc-0-only blind spot — federation's
        # live view gets a durable on-disk twin per host
        args.metrics_log = per_process_metrics_path(
            args.metrics_log, jax.process_index())
    logger = MetricsLogger(
        args.metrics_log,
        process_index=jax.process_index() if procs > 1 else None)
    tracer = tracer_from_args(args)  # NULL_TRACER unless --trace-out
    # metric registry: live when tracing (the sidecar dump) OR when the
    # ops plane / flight recorder is mounted; no-op otherwise
    registry = MetricRegistry(
        enabled=tracer.enabled or observability_enabled(args))
    from alphafold2_tpu.utils.flops import train_step_flops

    telemetry = build_train_telemetry(
        args, registry=registry, tracer=tracer, logger=logger,
        step_flops=train_step_flops(cfg, args.max_len, 0, 0,
                                    grad_accum=tcfg.grad_accum),
    )

    assemble = None
    if procs > 1:
        # pod path: the DP(xTP) step over a process-spanning mesh. The
        # global batch is --batch x --accum as ever; every process's
        # pipeline yields ONLY its own rows (training/data.py contract)
        # and the step consumes one global jax.Array assembled from the
        # local shards each step
        from alphafold2_tpu.parallel import make_multihost_train_step
        from alphafold2_tpu.parallel.sharding import host_to_global
        from alphafold2_tpu.training import process_shard

        # per-process view of the SAME global stream: row-slices, so the
        # pod run is bit-identical to the single-process twin
        example_local = process_shard(
            synthetic_microbatch_fn(dcfg, tcfg.grad_accum)(start), axis=1
        )
        jitted, st_shardings, assemble, _mh_mesh = make_multihost_train_step(
            cfg, tcfg, example_local, tp=False,
            donate_state=not resilient, telemetry=telemetry,
        )
        # params replicate identically on every process (same seed /
        # same restored bytes); each process feeds its own shards — no
        # cross-process transfer (parallel/sharding.py host_to_global)
        state = host_to_global(state, st_shardings)

        def train_step(st, batch, rng=None):
            return jitted(st, assemble(batch), rng)

        def _local(it):
            for b in it:
                yield process_shard(b, axis=1)

        batches = _local(batches)
    elif args.sp_shards:
        # sequence-parallel trunk: the pair grid (not the batch) shards —
        # the regime where crops outgrow one chip (parallel/sp_trunk.py)
        from alphafold2_tpu.parallel import make_mesh, make_sp_train_step

        mesh = make_mesh({"seq": args.sp_shards})
        # the resilient supervisor keeps a rollback reference to the
        # pre-step state, so donation must be off under it
        train_step = make_sp_train_step(cfg, tcfg, mesh,
                                        donate_state=not resilient)
    else:
        # donate the input state: without donation both the input and output
        # copies of (params + optimizer state) are live across every step
        # (~2x the state footprint; bench.py does the same). run_resilient
        # needs the non-donating step — it keeps the rollback state alive.
        train_step = jax.jit(
            make_train_step(cfg, tcfg),
            donate_argnums=() if resilient else (0,),
        )
    if resilient:
        # supervised loop: StepGuard rollback + checkpoint-restore restarts
        # + preemption-safe shutdown (+ the --fault-plan chaos hooks)
        from alphafold2_tpu.reliability import Preempted, PreemptionHandler

        if args.eval_every:
            print("note: --eval-every is ignored under the resilient loop")
        if args.data == "synthetic":
            # step-indexed fetch: a retried/resumed step refetches the
            # IDENTICAL batch, making recovery replay-exact. On a pod the
            # fetch yields only THIS process's rows (same purity)
            if procs > 1:
                from alphafold2_tpu.training import per_process_microbatch_fn

                source = per_process_microbatch_fn(dcfg, tcfg.grad_accum)
            else:
                source = synthetic_microbatch_fn(dcfg, tcfg.grad_accum)
        else:
            def stream():
                for b in batches:
                    b.pop("bucket", None)  # shape bookkeeping, not input
                    yield b

            source = stream()
        fetch = resilient_batches(source, injector=injector)
        base_rng = jax.random.fold_in(jax.random.PRNGKey(args.seed), 1)
        step_fn = with_fault_injection(train_step, injector)
        handler = PreemptionHandler().install()
        if injector is not None:
            injector.bind_preemption(handler)
        if resumed:
            print(f"resumed from step {start} in {args.ckpt_dir}")
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

    eval_batch, eval_loss_fn, eval_key = None, None, "eval_loss"
    if args.eval_every and procs > 1:
        print("note: --eval-every is ignored on multi-host runs (the "
              "held-out eval is a single-process convenience)")
        args.eval_every = 0
    if args.eval_every:
        # a FIXED held-out batch from a seed the training stream never
        # draws (stream seeds derive from args.seed; this one is offset).
        # The held-out batch is SYNTHETIC regardless of --data (stateful
        # sources have no clean holdout); when training on another source
        # the metric is named synthetic_eval_loss so the JSONL curve cannot
        # be misread as in-distribution generalization.
        from alphafold2_tpu.training import distogram_loss_fn

        if args.data != "synthetic":
            eval_key = "synthetic_eval_loss"
        eval_dcfg = DataConfig(batch_size=args.batch, max_len=args.max_len,
                               seed=args.seed + 104729)
        eval_batch = next(synthetic_batches(eval_dcfg))
        if args.sp_shards:
            # eval must shard the grid exactly like training: the
            # replicated forward would materialize the full pair grid on
            # one chip — the regime --sp-shards exists to avoid
            from alphafold2_tpu.parallel import sp_distogram_loss_fn

            loss_for_eval = sp_distogram_loss_fn(mesh)
        else:
            loss_for_eval = distogram_loss_fn
        eval_loss_fn = jax.jit(
            lambda p, b: loss_for_eval(p, cfg, b, None)
        )

    base_rng = jax.random.fold_in(jax.random.PRNGKey(args.seed), 1)
    t0 = time.time()
    if resumed:
        print(f"resumed from step {start} in {args.ckpt_dir}")
    try:
        for step in range(start, start + args.steps):
            # per-step key derived from the step index: identical schedule
            # whether the run is fresh or resumed
            step_rng = jax.random.fold_in(base_rng, step)
            with tracer.span("train.fetch", cat="train", step=step), \
                    telemetry.account("data_fetch"):
                batch = next(batches)
            batch.pop("bucket", None)  # shape bookkeeping, not model input
            step_bucket = telemetry.step_bucket()
            with tracer.span("train.step", cat="train", step=step), \
                    telemetry.account(step_bucket):
                state, metrics = train_step(state, batch, step_rng)
            if eval_loss_fn is not None and (step + 1) % args.eval_every == 0:
                metrics = dict(metrics)
                with tracer.span("train.eval", cat="train", step=step), \
                        telemetry.account("eval"):
                    metrics[eval_key] = eval_loss_fn(state["params"],
                                                     eval_batch)
            # logger.log is the step's device sync: the span absorbs the
            # async-dispatched execution train.step only launched
            with tracer.span("train.metrics_fetch", cat="train",
                             step=step), telemetry.account(step_bucket):
                logger.log(step, metrics)
            if step == start:
                # how much of the start was compiling, and whether the
                # compile cache served it
                logger.event(step, "compile", **compile_record.totals(top=5))
            telemetry.step_complete(step)
            if step % 10 == 0 or step == start + args.steps - 1:
                dt = time.time() - t0
                print(f"step {step}  loss {float(metrics['loss']):.4f}  "
                      f"grad_norm {float(metrics['grad_norm']):.3f}  "
                      f"({dt:.1f}s elapsed)")
            if mgr is not None:
                with tracer.span("train.checkpoint", cat="train",
                                 step=step), telemetry.account("checkpoint"):
                    mgr.save(state)  # save_interval_steps gates the cadence
        finish(mgr, state)
    finally:
        # a crashed or interrupted run keeps its trace and profiling
        # sidecar — the moment they are most wanted (same stance as the
        # resilient branch)
        if tracer.enabled:
            # the analytic workload gauges (utils/flops.py; XLA's own
            # count is scan-blind) + whatever memory stats the backend
            # exposes, as a JSON sidecar beside the trace
            import json as _json

            flops_gauges(registry, cfg, n=args.max_len, r=0,
                         c=args.max_len, grad_accum=tcfg.grad_accum)
            device_memory_gauges(registry)
            sidecar = args.trace_out + ".metrics.json"
            with open(sidecar, "w") as fh:
                _json.dump(registry.snapshot(), fh, indent=2)
            print(f"wrote {sidecar}")
        telemetry.close()
        logger.close()
        finish_trace(tracer, args)
    print("done")


if __name__ == "__main__":
    main()
