"""Serving entry point: drive the inference engine over a FASTA stream.

Where `predict.py` is one request per process, this is the traffic-replay
harness for `alphafold2_tpu.serving`: read a many-record FASTA (or
synthesize one with --demo), submit every record to the micro-batching
engine with explicit backpressure handling, and report the serving stats
snapshot (compiles, batch occupancy, latency quantiles, cache hit rate).

With `--replicas N` (N > 1) the replay drives the FLEET tier instead
(`serving/fleet.py`): N engine replicas behind the shared
admission-controlled queue, health-checked failover, and degraded-mode
fallback. `--fault-plan plan.json` wires a chaos schedule into the run —
replica-scoped kill/slow/flap faults in fleet mode, dispatch faults in
single-engine mode — so the failover paths run deterministically from
the CLI. Shed requests are a structured outcome (printed with their
`retry_after_s`), not a crash: the acceptance bar is that every request
ends terminally as served, served-degraded, or shed.

Usage:
  python serve.py --fasta proteins.fasta --out-dir preds/
  python serve.py --demo 24 --buckets 16,32 --max-batch 4 --mds-iters 8
  python serve.py --demo --replicas 3 --buckets 16,32 --fault-plan plan.json
  python serve.py --fasta proteins.fasta --ckpt-dir runs/pre --dim 256 \
      --depth 12 --buckets 128,256,384 --stats-json serving_stats.json

The CPU demo (`--demo 24 --buckets 16,32`) is the subsystem's acceptance
check: >=20 mixed-length sequences complete with at most len(buckets)
compiled executables and mean batch size > 1.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time

import jax
import numpy as np


def read_fasta(path):
    """Plain FASTA records as (name, sequence) pairs (no alignment
    semantics — utils/msa.py's parser enforces equal row widths, which is
    wrong for a request stream of unrelated proteins)."""
    records, name, parts = [], None, []

    def flush():
        if name is not None:
            seq = "".join(parts)
            if seq:
                records.append((name, seq))

    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line or line.startswith((";", "#")):
                continue
            if line.startswith(">"):
                flush()
                name, parts = line[1:].strip() or f"record{len(records)}", []
            else:
                if name is None:
                    name = f"record{len(records)}"
                parts.append(line)
    flush()
    if not records:
        raise SystemExit(f"no sequences found in {path!r}")
    return records


def demo_records(n, buckets, seed):
    """Synthetic mixed-length traffic spanning the whole bucket ladder,
    with a few repeats so the result cache has something to hit."""
    from alphafold2_tpu.constants import AA_ORDER

    rng = random.Random(seed)
    records = []
    for i in range(n):
        bucket = buckets[i % len(buckets)]
        lo = 2 if bucket == min(buckets) else max(b for b in buckets if b < bucket) + 1
        length = rng.randint(lo, bucket)
        seq = "".join(rng.choice(AA_ORDER) for _ in range(length))
        records.append((f"demo{i:03d}_L{length}", seq))
    # ~10% repeated queries — the cache-hit share of real traffic
    for i in range(max(1, n // 10)):
        src = records[rng.randrange(len(records))]
        records.append((src[0] + "_repeat", src[1]))
    rng.shuffle(records)
    return records


def main():
    ap = argparse.ArgumentParser(
        description="batched structure-prediction serving over a FASTA stream"
    )
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--fasta", help="multi-record FASTA of query sequences")
    src.add_argument("--demo", type=int, metavar="N", nargs="?", const=24,
                     help="synthesize N mixed-length demo sequences instead "
                          "(default 24 when given bare)")
    ap.add_argument("--out-dir", default=None,
                    help="write one CA-trace PDB per record here")
    # model (must match the checkpoint when restoring, like predict.py)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--depth", type=int, default=1)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--dim-head", type=int, default=16)
    ap.add_argument("--ckpt-dir", default=None, help="restore trained params")
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--weight-dtype", choices=("f32", "int8"), default="f32",
                    help="serving weight precision: int8 = per-channel "
                         "PTQ trunk weights with fused-dequant matmuls "
                         "(~4x less weight HBM; inference-only arm)")
    ap.add_argument("--max-seq-len", type=int, default=None,
                    help="positional-table size; MUST match the training "
                         "config when restoring (default: largest bucket)")
    # serving
    ap.add_argument("--buckets", default="64,128,256",
                    help="comma-separated length-bucket ladder")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--batch-ladder", action="store_true",
                    help="compile each bucket at power-of-two batch "
                         "shapes {1, 2, ..., max-batch} and serve partial "
                         "batches at the smallest fitting shape instead "
                         "of paying phantom-row chip time at max-batch")
    ap.add_argument("--pipeline-depth", type=int, default=0,
                    help="pipelined dispatch: keep up to this many "
                         "batches enqueued-but-unsettled so device "
                         "compute overlaps host assembly/settle "
                         "(0 = synchronous dispatch)")
    ap.add_argument("--max-wait-ms", type=float, default=50.0,
                    help="batch-assembly deadline for partial batches")
    ap.add_argument("--queue-size", type=int, default=64)
    ap.add_argument("--request-timeout", type=float, default=600.0)
    ap.add_argument("--cache-size", type=int, default=256)
    ap.add_argument("--mds-iters", type=int, default=32)
    ap.add_argument("--mds-init", choices=("random", "classical"),
                    default="classical")
    # SP serving arm (serving/sp_arm.py; docs/SERVING.md "Length-adaptive
    # routing")
    ap.add_argument("--sp-shards", type=int, default=0,
                    help="run each bucket's trunk sequence-parallel over "
                         "this many devices (0 = dense): per-bucket "
                         "schedule (dense / sp_msa / sp_seq) picked by "
                         "the chip-free residency heuristic")
    ap.add_argument("--sp-hbm-gb", type=float, default=16.0,
                    help="per-chip HBM budget the SP schedule heuristic "
                         "prices buckets against")
    ap.add_argument("--precompile", action="store_true",
                    help="AOT-compile every bucket before taking traffic")
    ap.add_argument("--breaker-threshold", type=int, default=0,
                    help="open the circuit after this many consecutive "
                         "dispatch failures (fast-reject until the reset "
                         "window; 0 = breaker off)")
    ap.add_argument("--breaker-reset", type=float, default=30.0,
                    help="seconds the circuit stays open before the "
                         "half-open probe")
    ap.add_argument("--watchdog-timeout", type=float, default=None,
                    help="fail a batch whose model call exceeds this many "
                         "seconds instead of wedging the worker (off by "
                         "default; fleet mode defaults it to 60s — the "
                         "failover path needs hung replicas to FAIL)")
    # fleet tier (serving/fleet.py; docs/OPERATIONS.md "Fleet runbook")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the shared admission "
                         "queue; >1 selects the fleet tier")
    ap.add_argument("--fleet-queue", type=int, default=64,
                    help="shared admission-queue capacity (fleet mode)")
    ap.add_argument("--requeue-limit", type=int, default=3,
                    help="replica failovers per request before it fails "
                         "terminally (fleet mode)")
    ap.add_argument("--degraded-iters", type=int, default=-1,
                    help="MDS iterations for the degraded fallback tier; "
                         "-1 = auto (max(1, mds_iters // 4)), 0 = no "
                         "degraded tier (fleet mode)")
    ap.add_argument("--degraded-weight-dtype", choices=("", "f32", "int8"),
                    default="",
                    help="weight precision for the degraded fallback tier "
                         "(int8 = PTQ trunk weights; fleet mode; composes "
                         "with --degraded-iters)")
    ap.add_argument("--degrade-depth", type=int, default=0,
                    help="admission-queue depth past which NEW work spills "
                         "to the degraded tier (0 = degraded serves only "
                         "when every full replica is down)")
    ap.add_argument("--probe-interval", type=float, default=5.0,
                    help="healthy-replica heartbeat cadence, seconds")
    ap.add_argument("--reprobe-interval", type=float, default=0.5,
                    help="down-replica reinstatement probe cadence, seconds")
    ap.add_argument("--fail-threshold", type=int, default=2,
                    help="consecutive replica failures that drain it")
    # disaggregated serving (serving/featurize.py + serving/autoscale.py;
    # docs/SERVING.md "The featurization tier")
    ap.add_argument("--pools", default=None, metavar="POOLS_JSON",
                    help="heterogeneous capability pools (length-adaptive "
                         "routing): a JSON list of PoolSpec dicts — "
                         '[{"name":"short","replicas":2,"weight_dtype":'
                         '"int8","buckets":[64,128,256]},{"name":"long",'
                         '"replicas":1,"sp_shards":4,"buckets":[256,512,'
                         '1024]}] — inline or a file path. Selects the '
                         "fleet tier; short requests route to the "
                         "cheapest capable pool, sequences past every "
                         "pool's ceiling shed with sequence_too_long")
    ap.add_argument("--cascade", default="off", metavar="POLICY_JSON",
                    help="adaptive-fidelity draft→verify cascade "
                         "(serving/cascade.py; requires --pools): a "
                         "serving.CascadePolicy JSON — "
                         '{"draft_pool":"draft","min_confidence":0.7,'
                         '"max_stress":0.3} — inline or a file path; '
                         "unknown keys reject loudly. Eligible requests "
                         "run on the draft pool first and only "
                         "low-confidence drafts escalate to the "
                         "full-fidelity pools. 'off' (default) keeps "
                         "static pool routing")
    ap.add_argument("--featurize-workers", type=int, default=0,
                    help="CPU featurization worker threads in front of "
                         "the admission queue (0 = featurize inline); "
                         ">0 selects the fleet tier even with one "
                         "replica")
    ap.add_argument("--featurize-queue", type=int, default=128,
                    help="featurize-tier bounded queue capacity")
    ap.add_argument("--min-replicas", type=int, default=None,
                    help="autoscaler floor (requires --max-replicas)")
    ap.add_argument("--max-replicas", type=int, default=None,
                    help="autoscaler ceiling; setting it ARMS the "
                         "elastic replica autoscaler (fleet tier), "
                         "which grows/shrinks the pool live from "
                         "queue-wait p95 / occupancy / SLO burn")
    ap.add_argument("--scale-policy", default=None, metavar="POLICY_JSON",
                    help="autoscaler thresholds/hysteresis "
                         "(serving.ScalePolicy JSON; unknown keys "
                         "reject loudly); default: stock policy with "
                         "--min/--max-replicas bounds")
    ap.add_argument("--scale-grace", type=float, default=0.0,
                    metavar="SECONDS",
                    help="with the autoscaler armed: keep the process "
                         "alive (idle, still ticking) up to this long "
                         "after the replay drains, so idle scale-down "
                         "is observable before shutdown")
    ap.add_argument("--fault-plan", default=None, metavar="PLAN_JSON",
                    help="chaos schedule (reliability.FaultPlan JSON): "
                         "replica-scoped kill/slow/flap faults in fleet "
                         "mode, dispatch faults single-engine; validate "
                         "with `python -m alphafold2_tpu.reliability."
                         "faults --check`")
    ap.add_argument("--passes", type=int, default=1,
                    help="replay the request stream this many times; "
                         "passes after the first exercise the result cache")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stats-json", default=None,
                    help="write the final stats snapshot here (includes "
                         "the telemetry section: registry metrics + "
                         "per-phase span summaries)")
    ap.add_argument("--stats-interval", type=float, default=0.0,
                    metavar="SECONDS",
                    help="with --stats-json: also flush the stats "
                         "snapshot there every N seconds DURING the "
                         "replay (atomic tmp+rename), so a crashed run "
                         "keeps its last periodic snapshot instead of "
                         "losing everything (0 = end-of-run only)")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="stream one record per dispatched batch here")
    # live operations plane (telemetry/ops_plane.py;
    # docs/OBSERVABILITY.md "The operations plane")
    ap.add_argument("--ops-port", type=int, default=None, metavar="PORT",
                    help="serve the observability HTTP endpoints "
                         "(/metrics Prometheus exposition, /healthz, "
                         "/statusz) on 127.0.0.1:PORT while the replay "
                         "runs (0 = ephemeral port, printed at startup); "
                         "also arms the SLO engine (stock objectives "
                         "unless --slo-config)")
    ap.add_argument("--ops-port-file", default=None, metavar="PATH",
                    help="write the bound ops-plane port here once "
                         "listening (how a parent process finds an "
                         "--ops-port 0 ephemeral port)")
    ap.add_argument("--ops-tick", type=float, default=1.0,
                    metavar="SECONDS",
                    help="ops-plane ticker cadence: SLO evaluation, "
                         "flight-recorder metric-delta polling, host "
                         "memory gauges")
    ap.add_argument("--slo-config", default=None, metavar="SLO_JSON",
                    help="declarative SLO objectives (telemetry/slo.py "
                         "schema; docs/OBSERVABILITY.md); default: stock "
                         "availability/shed-rate/queue-wait objectives. "
                         "Requires --ops-port (the ticker evaluates it)")
    ap.add_argument("--flight-dir", default=None, metavar="DIR",
                    help="arm the incident flight recorder: breaker "
                         "opens, replica drains, watchdog fires, and SLO "
                         "pages snapshot a forensic JSON bundle (recent "
                         "spans incl. trace_ids, event ring, registry "
                         "snapshot, stats) into DIR; with --ops-port it "
                         "also arms /profilez (on-demand jax.profiler "
                         "captures land under DIR/profiles)")
    ap.add_argument("--peak-tflops", type=float, default=None,
                    help="declared per-chip peak TFLOP/s for the "
                         "serve_mfu cost-ledger gauge (unset = publish "
                         "achieved FLOP/s only)")
    ap.add_argument("--artifact-store", default="off", metavar="DIR",
                    help="fleet-wide content-addressed result/feature "
                         "cache with front-door coalescing "
                         "(docs/OPERATIONS.md): a directory for the "
                         "disk tier, 'auto' (sibling 'artifacts/' dir "
                         "next to --flight-dir, memory-only without "
                         "one), or 'off' (default). Fleet mode only")
    ap.add_argument("--journal", default="off", metavar="DIR",
                    help="crash-safe durable intake journal "
                         "(docs/OPERATIONS.md): every accepted request "
                         "is written to DIR before dispatch and unlinked "
                         "at its terminal state; on startup unfinished "
                         "records REPLAY through the front door "
                         "(idempotent via coalescing + the artifact "
                         "store). 'auto' = sibling 'journal/' dir next "
                         "to --flight-dir (off without one); 'off' "
                         "(default). Fleet mode only")
    ap.add_argument("--retry-budget", type=int, default=0, metavar="N",
                    help="fleet-wide retry budget: a token bucket of N "
                         "tokens shared by featurize requeues, failover "
                         "retries, and hedged dispatches, refilled as a "
                         "fraction of successful completions — a "
                         "brownout sheds with retry_budget_exhausted "
                         "(HTTP 429 + Retry-After) instead of a retry "
                         "storm (0 = unlimited retries, as before)")
    ap.add_argument("--hedge-factor", type=float, default=0.0,
                    metavar="X",
                    help="hedged dispatch: when a dispatch exceeds X x "
                         "its pool's service-time p95, issue one "
                         "duplicate dispatch to another healthy replica "
                         "— first settle wins, the loser's chip-seconds "
                         "land in hedge_wasted_chip_seconds_total "
                         "(0 = off; 1.5-3 are sane values)")
    ap.add_argument("--hedge-rate-cap", type=float, default=0.1,
                    metavar="FRAC",
                    help="upper bound on hedges as a fraction of total "
                         "dispatches (default 0.1)")
    ap.add_argument("--artifact-mem-entries", type=int, default=256,
                    metavar="N",
                    help="artifact-store hot-ring entry cap "
                         "(default 256)")
    ap.add_argument("--artifact-mem-mb", type=int, default=256,
                    metavar="MB",
                    help="artifact-store hot-ring byte budget "
                         "(default 256 MB)")
    ap.add_argument("--artifact-disk-mb", type=int, default=2048,
                    metavar="MB",
                    help="artifact-store disk-tier byte budget, "
                         "enforced oldest-first by the sweep "
                         "(default 2048 MB)")
    from alphafold2_tpu.telemetry import (
        add_telemetry_args,
        finish_trace,
        tracer_from_args,
    )

    add_telemetry_args(ap)  # --trace-out / --trace-max-spans
    args = ap.parse_args()
    if args.slo_config and args.ops_port is None:
        ap.error("--slo-config requires --ops-port (the ops-plane ticker "
                 "is what evaluates the objectives)")
    if args.stats_interval and not args.stats_json:
        ap.error("--stats-interval requires --stats-json (it needs a "
                 "path to flush to)")
    if args.stats_interval < 0:
        ap.error("--stats-interval must be positive (0 disables the "
                 "periodic flush)")
    if args.ops_port_file and args.ops_port is None:
        ap.error("--ops-port-file requires --ops-port (there is no port "
                 "to publish without the ops server)")
    if args.ops_tick <= 0:
        ap.error("--ops-tick must be positive")
    if args.min_replicas is not None and args.max_replicas is None:
        ap.error("--min-replicas requires --max-replicas (the pair arms "
                 "the autoscaler)")
    if args.scale_policy and args.max_replicas is None:
        ap.error("--scale-policy requires --max-replicas (nothing "
                 "evaluates a policy without the autoscaler armed)")
    if args.scale_grace and args.max_replicas is None:
        ap.error("--scale-grace requires --max-replicas")
    if args.featurize_workers < 0:
        ap.error("--featurize-workers must be >= 0")
    if args.artifact_mem_entries < 1:
        ap.error("--artifact-mem-entries must be >= 1")
    if args.retry_budget < 0:
        ap.error("--retry-budget must be >= 0 (0 disables it)")
    if args.hedge_factor < 0:
        ap.error("--hedge-factor must be >= 0 (0 disables hedging)")
    if not (0.0 < args.hedge_rate_cap <= 1.0):
        ap.error("--hedge-rate-cap must be in (0, 1]")
    if args.artifact_mem_mb < 1 or args.artifact_disk_mb < 1:
        ap.error("--artifact-mem-mb / --artifact-disk-mb must be >= 1")

    # persistent compile cache, placed before the first compile
    # (alphafold2_tpu/compile_cache.py: JAX_COMPILATION_CACHE_DIR if set,
    # else <checkout>/.jax_cache)
    from alphafold2_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()

    # multi-host entry: no-op unless the AF2_COORDINATOR/... contract is
    # configured; must run BEFORE the first backend-initializing JAX call
    # (the shared startup errors loudly otherwise). Serving itself stays
    # per-process — the engine/fleet serve this host's devices — but a
    # pod-launched serve.py must still join the runtime or its
    # jax.devices() view silently degrades to one host.
    from alphafold2_tpu.parallel.distributed import distributed_startup

    distributed_startup("serve")

    import jax.numpy as jnp

    from alphafold2_tpu.models import Alphafold2Config
    from alphafold2_tpu.serving import (
        FleetConfig,
        NoHealthyReplicaError,
        QueueFullError,
        RequestTimeoutError,
        RetryBudgetExhaustedError,
        ServingConfig,
        ServingEngine,
        ServingError,
        ServingFleet,
    )
    from alphafold2_tpu.utils import MetricsLogger

    buckets = tuple(sorted({int(b) for b in args.buckets.split(",")}))

    # heterogeneous capability pools (serving/fleet.py PoolSpec;
    # docs/SERVING.md "Length-adaptive routing") — parsed BEFORE the
    # model config: the positional table must cover the widest pool
    # ladder, and the demo trace should span it
    pools = ()
    if args.pools:
        from alphafold2_tpu.serving import PoolSpec

        raw = args.pools
        if os.path.exists(raw):
            with open(raw) as fh:
                raw = fh.read()
        try:
            pool_dicts = json.loads(raw)
        except ValueError as e:
            ap.error(f"--pools is neither a file nor valid JSON: {e}")
        if not isinstance(pool_dicts, list) or not pool_dicts:
            ap.error("--pools must be a non-empty JSON list of pool dicts")
        try:
            # `is not None`, not truthiness: an (erroneous) empty buckets
            # list must reach PoolSpec's non-empty validation and error,
            # not silently decay into "inherit the base ladder"
            pools = tuple(
                PoolSpec(**{**d, "buckets": tuple(d["buckets"])
                            if d.get("buckets") is not None else None})
                for d in pool_dicts)
        except (TypeError, ValueError) as e:
            ap.error(f"--pools: {e}")
    if pools and args.sp_shards:
        ap.error("--sp-shards and --pools are mutually exclusive: with "
                 "pools configured, declare sp_shards per pool in the "
                 "pools JSON")
    # adaptive-fidelity cascade (serving/cascade.py): parsed next to
    # --pools because the policy's draft_pool must name one of them —
    # FleetConfig validates the pairing loudly
    cascade_policy = None
    if args.cascade != "off":
        from alphafold2_tpu.serving import CascadePolicy

        if not pools:
            ap.error("--cascade requires --pools: the draft tier is a "
                     "capability pool (give it int8 weights / fewer "
                     "mds_iters / reduced msa_rows in the pools JSON)")
        try:
            if os.path.exists(args.cascade):
                cascade_policy = CascadePolicy.from_file(args.cascade)
            else:
                cascade_policy = CascadePolicy.from_dict(
                    json.loads(args.cascade))
        except ValueError as e:
            ap.error(f"--cascade: {e}")
    union_buckets = tuple(sorted(
        set(buckets).union(*[p.buckets or buckets for p in pools])))

    records = (
        demo_records(args.demo, union_buckets, args.seed)
        if args.demo is not None
        else read_fasta(args.fasta)
    )
    print(f"{len(records)} request(s), bucket ladder {buckets}"
          + (f", pools {[p.name for p in pools]} "
             f"(union ladder {union_buckets})" if pools else ""))

    cfg = Alphafold2Config(
        dim=args.dim,
        depth=args.depth,
        heads=args.heads,
        dim_head=args.dim_head,
        max_seq_len=args.max_seq_len or max(64, union_buckets[-1]),
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        # engine build quantizes at this knob (serving/quant_residency.py);
        # checkpoints stay fp32 masters — PTQ happens at serve time
        weight_dtype=args.weight_dtype,
    )

    from alphafold2_tpu.models import alphafold2_init
    from alphafold2_tpu.training import (
        TrainConfig,
        restore_params_for_inference,
        train_state_init,
    )

    # checkpoints hold fp32 MASTER weights whatever the serving precision
    # arm: restore against the f32 twin of the config (train_state_init
    # loudly rejects int8 — it is inference-only), then let the engine
    # quantize at build (serving/quant_residency.py)
    import dataclasses as _dc

    restore_cfg = _dc.replace(cfg, weight_dtype="f32")
    params, step, _ = restore_params_for_inference(
        args.ckpt_dir, train_state_init, jax.random.PRNGKey(0), restore_cfg,
        TrainConfig(),
        cold_params_fn=lambda: alphafold2_init(
            jax.random.PRNGKey(0), restore_cfg),
    )
    # cache fingerprint: two checkpoints must never share result entries
    params_tag = f"{args.ckpt_dir}@step{step}" if args.ckpt_dir else ""

    logger = (
        MetricsLogger(jsonl_path=args.metrics_jsonl, print_every=10)
        if args.metrics_jsonl
        else None
    )
    tracer = tracer_from_args(args)  # NULL_TRACER unless --trace-out
    if (args.ops_port is not None or args.flight_dir) and not tracer.enabled:
        # the ops plane and the flight recorder are span CONSUMERS
        # (/statusz summaries, bundle tails with trace_ids): give them a
        # live tracer even without --trace-out (no Chrome export then)
        from alphafold2_tpu.telemetry import Tracer

        tracer = Tracer(enabled=True, max_spans=args.trace_max_spans)
    recorder = None
    if args.flight_dir:
        from alphafold2_tpu.telemetry import FlightRecorder

        # registry/stats bound AFTER the engine exists (recorder must be
        # built first: it is the engine's incident_hook)
        recorder = FlightRecorder(args.flight_dir, tracer=tracer)
    injector = None
    if args.fault_plan:
        from alphafold2_tpu.reliability import FaultPlan

        injector = FaultPlan.from_file(args.fault_plan).injector()
        print(f"fault plan: {len(injector.plan.faults)} fault(s) from "
              f"{args.fault_plan}")

    autoscale_armed = args.max_replicas is not None
    min_replicas = args.min_replicas if args.min_replicas is not None else 1
    fleet_mode = (args.replicas > 1 or autoscale_armed
                  or args.featurize_workers > 0 or bool(pools))
    initial_replicas = args.replicas
    if autoscale_armed:
        if args.max_replicas < min_replicas:
            ap.error("--max-replicas must be >= --min-replicas")
        initial_replicas = min(max(args.replicas, min_replicas),
                               args.max_replicas)
    serving_cfg = ServingConfig(
        buckets=buckets,
        max_batch=args.max_batch,
        max_queue=args.queue_size,
        max_wait_s=args.max_wait_ms / 1000.0,
        request_timeout_s=args.request_timeout,
        cache_capacity=args.cache_size,
        mds_iters=args.mds_iters,
        mds_init=args.mds_init,
        seed=args.seed,
        precompile=args.precompile,
        params_tag=params_tag,
        sp_shards=args.sp_shards,
        sp_hbm_gb=args.sp_hbm_gb,
        batch_ladder=args.batch_ladder,
        pipeline_depth=args.pipeline_depth,
        breaker_threshold=args.breaker_threshold,
        breaker_reset_s=args.breaker_reset,
        watchdog_timeout_s=(
            args.watchdog_timeout if args.watchdog_timeout is not None
            # the fleet's liveness story needs hung replicas to FAIL (the
            # failover path starts from a failure, never from a hang)
            else (60.0 if fleet_mode else None)
        ),
    )
    if args.artifact_store != "off" and not fleet_mode:
        # the store intercepts at the FLEET front door (before routing);
        # a single engine already has its own LRU + per-replica
        # coalescing, so there is nothing for the fleet tier to collapse
        print("WARNING: --artifact-store applies to fleet mode only "
              "(--replicas > 1, pools, featurize tier, or autoscale); "
              "single-engine mode keeps its per-engine result LRU")
    if args.journal != "off" and not fleet_mode:
        print("WARNING: --journal applies to fleet mode only (the fleet "
              "front door is where requests are accepted and settled); "
              "single-engine mode takes no journal")
    journal_replays = []  # (name, seq, FleetRequest) recovered from a journal
    if fleet_mode:
        if logger is not None:
            # the per-batch JSONL stream is an engine-level concept (one
            # worker, one step counter); N replica workers sharing one
            # unlocked logger would interleave counters and races. Say
            # so instead of silently writing nothing.
            print("WARNING: --metrics-jsonl applies to single-engine mode "
                  "only; fleet observability is --stats-json (registry "
                  "snapshot incl. per-replica engine stats) + --trace-out")
            logger.close()
            logger = None
        degraded_iters = (
            max(1, args.mds_iters // 4) if args.degraded_iters < 0
            else args.degraded_iters
        )
        artifact_store = None
        if args.artifact_store != "off":
            from alphafold2_tpu.serving import (
                ArtifactStore,
                ArtifactStoreConfig,
            )

            if args.artifact_store == "auto":
                # sibling of --flight-dir (the ISSUE 17 layout: forensic
                # bundles and the artifact tier share a volume), memory-
                # only when no flight dir anchors one
                store_root = (os.path.join(
                    os.path.dirname(os.path.abspath(args.flight_dir)),
                    "artifacts") if args.flight_dir else None)
            else:
                store_root = args.artifact_store
            artifact_store = ArtifactStore(ArtifactStoreConfig(
                root=store_root,
                memory_entries=args.artifact_mem_entries,
                memory_bytes=args.artifact_mem_mb << 20,
                disk_bytes=args.artifact_disk_mb << 20,
            ))
            print("artifact store: "
                  + (f"disk tier at {store_root}" if store_root
                     else "memory-only (no --flight-dir to anchor "
                          "'auto' disk tier)")
                  + f", hot ring {args.artifact_mem_entries} entries / "
                    f"{args.artifact_mem_mb} MB")
        journal = None
        if args.journal != "off":
            from alphafold2_tpu.serving import IntakeJournal

            if args.journal == "auto":
                # same volume layout as --artifact-store auto: the
                # journal lives beside the flight dir; without one there
                # is no disk to anchor durability — say so, stay off
                journal_root = (os.path.join(
                    os.path.dirname(os.path.abspath(args.flight_dir)),
                    "journal") if args.flight_dir else None)
            else:
                journal_root = args.journal
            if journal_root is None:
                print("WARNING: --journal auto needs --flight-dir to "
                      "anchor a directory; journal stays OFF")
            else:
                journal = IntakeJournal(journal_root)
                print(f"intake journal: {journal_root}")
        engine = ServingFleet(
            params, cfg, serving_cfg,
            FleetConfig(
                replicas=initial_replicas,
                queue_capacity=args.fleet_queue,
                default_timeout_s=args.request_timeout,
                requeue_limit=args.requeue_limit,
                degraded_mds_iters=degraded_iters,
                degraded_weight_dtype=args.degraded_weight_dtype,
                degrade_depth=args.degrade_depth,
                probe_interval_s=args.probe_interval,
                reprobe_interval_s=args.reprobe_interval,
                fail_threshold=args.fail_threshold,
                featurize_workers=args.featurize_workers,
                featurize_queue=args.featurize_queue,
                pools=pools,
                retry_budget_capacity=args.retry_budget,
                hedge_p95_factor=args.hedge_factor,
                hedge_rate_cap=args.hedge_rate_cap,
                cascade_policy=cascade_policy,
            ),
            injector=injector,
            tracer=tracer,
            incident_hook=recorder.incident if recorder else None,
            artifact_store=artifact_store,
            journal=journal,
        )
        degraded_desc = ", ".join(
            ([f"mds_iters={degraded_iters}"] if degraded_iters else [])
            + ([f"weights={args.degraded_weight_dtype}"]
               if args.degraded_weight_dtype == "int8" else [])
        )
        print(f"fleet: {initial_replicas} replica(s), shared queue "
              f"{args.fleet_queue}, featurize tier "
              + (f"{args.featurize_workers} worker(s)"
                 if args.featurize_workers else "OFF")
              + ", degraded tier " + (degraded_desc or "OFF")
              + (f", retry budget {args.retry_budget}"
                 if args.retry_budget else "")
              + (f", hedging p95 x{args.hedge_factor:g} "
                 f"(cap {args.hedge_rate_cap:g})"
                 if args.hedge_factor else "")
              + (f", cascade draft_pool={cascade_policy.draft_pool!r} "
                 f"min_confidence={cascade_policy.min_confidence:g}"
                 if cascade_policy is not None else ""))
        if journal is not None:
            # replay BEFORE fresh traffic: crash-orphaned requests
            # re-enter the front door (coalescing + artifact store make
            # the replay idempotent — completed work replays as a hit)
            replayed = engine.replay_journal()
            if replayed["replayed"] or replayed["expired"]:
                print(f"journal replay: {replayed['replayed']} "
                      f"re-submitted, {replayed['expired']} expired, "
                      f"{replayed['failed']} rejected")
            journal_replays = [
                (f"journal_{req.trace_id}", req.seq, req)
                for req in replayed["requests"]
            ]
    else:
        from alphafold2_tpu.telemetry import FlightBook

        engine = ServingEngine(
            params, cfg, serving_cfg,
            metrics_logger=logger,
            fault_hook=injector.serving_hook() if injector else None,
            tracer=tracer,
            incident_hook=recorder.incident if recorder else None,
            # single-engine /explainz: the engine records its own
            # submit->terminal exemplars (the fleet keeps its own book)
            flights=FlightBook(),
        )

    # --- live operations plane -----------------------------------------
    registry = engine.registry if fleet_mode else engine.metrics.registry
    if recorder is not None:
        recorder.bind(registry=registry, stats_fn=engine.stats)
    # serving cost plane (telemetry/costs.py): both modes carry a cost
    # ledger (`.costs`); the declared peak arms the serve_mfu gauge
    if args.peak_tflops:
        engine.costs.set_peak(args.peak_tflops * 1e12)

    # --- guaranteed final stats flush (clean shutdown AND SIGTERM) ------
    # the periodic flusher below is timer-driven; without this, a run
    # terminated between ticks (or SIGTERM'd by its supervisor) loses
    # everything since the last tick
    _stats_flushed = {"final": False}

    def _flush_stats_snapshot():
        if not args.stats_json or _stats_flushed["final"]:
            return
        try:
            snap = engine.stats()
            tmp = args.stats_json + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(snap, fh, indent=2)
            os.replace(tmp, args.stats_json)  # atomic: never torn
        except Exception:  # noqa: BLE001 — a flush failure must not mask
            # the run's real exit path
            import traceback

            traceback.print_exc()

    if args.stats_json:
        import atexit
        import signal

        # clean-shutdown guarantee: whatever path the process leaves by
        # (normal return, uncaught exception, sys.exit), the LAST
        # complete snapshot lands — the end-of-run dump below sets the
        # flag, so the common path writes once
        atexit.register(_flush_stats_snapshot)

        def _on_sigterm(signum, frame):  # noqa: ARG001 — signal API
            # one last complete snapshot, then die with the default
            # disposition so the exit status still says "terminated".
            # The flush runs on a WORKER thread with a bounded join:
            # signal handlers run on the main thread, which may have
            # been interrupted while holding a fleet/registry lock that
            # stats() needs — flushing inline could self-deadlock and
            # turn termination into a hang (worst case here: the join
            # times out, the snapshot is lost, the process still dies)
            t = threading.Thread(target=_flush_stats_snapshot,
                                 name="af2-sigterm-flush", daemon=True)
            t.start()
            t.join(10.0)
            signal.signal(signal.SIGTERM, signal.SIG_DFL)
            os.kill(os.getpid(), signal.SIGTERM)

        signal.signal(signal.SIGTERM, _on_sigterm)

    # --- elastic replica autoscaler (serving/autoscale.py) --------------
    scaler = scale_policy = None
    pool_scalers = []
    if autoscale_armed:
        from alphafold2_tpu.serving import ReplicaAutoscaler, ScalePolicy

        scale_policy = (ScalePolicy.from_file(args.scale_policy)
                        if args.scale_policy else ScalePolicy())
        # the CLI bounds armed the scaler; they win over file defaults
        scale_policy = _dc.replace(scale_policy,
                                   min_replicas=min_replicas,
                                   max_replicas=args.max_replicas)
        if pools:
            # heterogeneous fleet: ONE autoscaler per capability pool,
            # each reading its pool-labeled queue-wait/occupancy signals
            # — a saturated SP pool grows while the dense pool idles
            # down, independently (the CLI bounds apply per pool)
            pool_scalers = [
                ReplicaAutoscaler(
                    engine, scale_policy, pool=spec.name,
                    incident_hook=recorder.incident if recorder else None,
                    fault_hook=(injector.autoscale_hook()
                                if injector else None),
                )
                for spec in pools
            ]
        else:
            scaler = ReplicaAutoscaler(
                engine, scale_policy,
                incident_hook=recorder.incident if recorder else None,
                fault_hook=injector.autoscale_hook() if injector else None,
            )
        print(f"autoscaler"
              + (f" (per-pool x{len(pool_scalers)})" if pools else "")
              + f": replicas in "
              f"[{scale_policy.min_replicas}, "
              f"{scale_policy.max_replicas}], "
              f"up @ p95>={scale_policy.up_queue_wait_p95_s}s | "
              f"burn>={scale_policy.up_burn} | "
              f"occ>={scale_policy.up_occupancy}, "
              f"cooldowns {scale_policy.up_cooldown_s}/"
              f"{scale_policy.down_cooldown_s}s")
    ops = slo = None
    if args.ops_port is not None:
        from alphafold2_tpu.telemetry import (
            SloConfig,
            SloEngine,
            default_slo_config,
            host_memory_gauges,
            ops_server_for_engine,
            ops_server_for_fleet,
        )

        slo_cfg = (SloConfig.from_file(args.slo_config) if args.slo_config
                   else default_slo_config("fleet" if fleet_mode
                                           else "serving"))
        slo = SloEngine(
            registry, slo_cfg,
            on_page=recorder.slo_page_hook if recorder else None,
        )
        profiler = None
        if args.flight_dir:
            from alphafold2_tpu.telemetry import ProfileCapturer

            # /profilez: on-demand jax.profiler captures into the
            # flight dir — the next healthy TPU probe can be profiled
            # without redeploying
            profiler = ProfileCapturer(
                os.path.join(args.flight_dir, "profiles"),
                registry=registry)
        make_ops = ops_server_for_fleet if fleet_mode else ops_server_for_engine
        ops = make_ops(engine, tracer=tracer, slo=slo, recorder=recorder,
                       profiler=profiler,
                       port=args.ops_port, tick_interval_s=args.ops_tick)
        ops.add_tick(lambda: host_memory_gauges(registry))
        # live queue/occupancy/cost-plane gauges: scrapes see pressure
        # (and per-request chip cost + headroom) between requests, and
        # the autoscaler's signals stay fresh. Both modes have the hook
        # (the single engine's publishes its private cost ledgers).
        ops.add_tick(engine.sample_gauges)
        ops.start()
        print(f"ops plane listening on {ops.url} "
              f"(/metrics /healthz /statusz)")
        if args.ops_port_file:
            tmp = args.ops_port_file + ".tmp"
            with open(tmp, "w") as fh:
                fh.write(str(ops.port))
            os.replace(tmp, args.ops_port_file)  # readers never see ""
    for sc in ([scaler] if scaler is not None else []) + pool_scalers:
        # the autoscaler always gets its OWN control thread (same
        # cadence as the ops ticker): a scale-up's engine build can
        # compile for seconds, and riding the shared OpsTicker would
        # stall SLO evaluation / flight-recorder polling / gauge
        # sampling during exactly the overload it is reacting to
        sc.start(args.ops_tick)

    stats_stop = threading.Event()
    stats_thread = None
    if args.stats_interval:
        def _flush_stats():
            while not stats_stop.wait(args.stats_interval):
                try:
                    snap = engine.stats()
                    tmp = args.stats_json + ".tmp"
                    with open(tmp, "w") as fh:
                        json.dump(snap, fh, indent=2)
                    os.replace(tmp, args.stats_json)  # atomic: a crash
                    # mid-write never tears the last good snapshot
                except Exception:  # noqa: BLE001 — a flush failure must
                    # not kill the replay
                    import traceback

                    traceback.print_exc()

        stats_thread = threading.Thread(
            target=_flush_stats, name="af2-stats-flusher", daemon=True)
        stats_thread.start()

    # --- replay: submit everything, honoring backpressure explicitly ----
    t0 = time.time()
    # journal-recovered requests drain through the same result loop as
    # fresh traffic (their names carry the journal_ prefix)
    pending, failures, shed = list(journal_replays), 0, 0
    _MAX_SUBMIT_RETRIES = 200  # replay client's patience per record
    for pass_idx in range(max(1, args.passes)):
        for name, seq in records:
            if pass_idx:
                name = f"{name}_p{pass_idx + 1}"
            retries = 0
            while True:
                try:
                    pending.append((name, seq, engine.submit(seq)))
                    break
                except (QueueFullError, RetryBudgetExhaustedError) as e:
                    # honor the server's structured backoff advice (the
                    # bounded queue / retry budget is the throttle), but
                    # stay impatient enough that a demo replay finishes
                    retries += 1
                    if retries > _MAX_SUBMIT_RETRIES:
                        print(f"SHED {name}: [{e.code}] {e}")
                        shed += 1
                        break
                    time.sleep(min(0.1, e.retry_after_s or 0.005))
                except ServingError as e:
                    print(f"REJECTED {name}: [{e.code}] {e}")
                    failures += 1
                    break
        if pass_idx + 1 < max(1, args.passes):
            # drain between passes so later passes replay against a warm
            # cache instead of coalescing onto in-flight duplicates
            for _, _, req in pending:
                if not req.done():
                    try:
                        req.result()
                    except ServingError:
                        pass

    if args.out_dir:
        os.makedirs(args.out_dir, exist_ok=True)
    used_names = set()
    for name, seq, req in pending:
        try:
            res = req.result()
        except ServingError as e:
            retry = (f" (retry_after={e.retry_after_s:.2f}s)"
                     if e.retry_after_s is not None else "")
            if isinstance(e, (QueueFullError, RequestTimeoutError,
                              NoHealthyReplicaError,
                              RetryBudgetExhaustedError)):
                # structured load shed: a terminal outcome, not a bug.
                # An HTTP front end maps this to e.http_status (429 for
                # queue-full / retry-budget brownouts) with a Retry-After
                # header from retry_after_s.
                print(f"SHED {name}: [{e.code}] HTTP {e.http_status} "
                      f"{e}{retry}")
                shed += 1
            else:
                print(f"FAILED {name}: [{e.code}] {e}{retry}")
                failures += 1
            continue
        tag = " (cache)" if res.from_cache else ""
        if res.replica:
            tag += f" [{res.replica}]"
        if res.requeues:
            tag += f" (requeued x{res.requeues})"
        if res.degraded:
            tag += " (DEGRADED)"
        if res.tier:
            tag += f" tier={res.tier}"
            if res.exit_depth:
                tag += f"@exit{res.exit_depth}"
        tid = f" tid={res.trace_id}" if res.trace_id else ""
        print(f"{name}: L={len(seq)} bucket={res.bucket} "
              f"stress={res.stress:.3f} "
              f"conf={100 * float(res.confidence.mean()):.1f}/100 "
              f"lat={res.latency_s * 1000:.0f}ms{tag}{tid}")
        if args.out_dir:
            from alphafold2_tpu.geometry.pdb import coords_to_pdb

            safe = "".join(c if c.isalnum() or c in "-_." else "_"
                           for c in name)[:80]
            # sanitize+truncate can collide (duplicate headers, headers
            # differing only in mapped chars) — suffix instead of
            # silently overwriting an earlier prediction
            base, n = safe, 1
            while safe in used_names:
                safe = f"{base}.{n}"
                n += 1
            used_names.add(safe)
            coords_to_pdb(
                os.path.join(args.out_dir, f"{safe}.pdb"),
                np.asarray(res.coords), sequence=seq, atom_names=("CA",),
                bfactors=100.0 * np.asarray(res.confidence),
            )

    if (scaler is not None or pool_scalers) and args.scale_grace > 0:
        # idle grace: the replay has drained — keep ticking so the
        # autoscaler can observe the idle pool and scale back down
        # before shutdown (the demo's scale-down leg)
        floor = scale_policy.min_replicas * max(1, len(pool_scalers))
        grace_deadline = time.time() + args.scale_grace
        while time.time() < grace_deadline:
            if engine.replica_count() <= floor:
                break
            time.sleep(0.1)
    if slo is not None:
        # one last evaluation BEFORE shutdown: a short replay whose
        # burn crossed the threshold in its final window still records
        # the firing transition
        slo.evaluate()
    if stats_thread is not None:
        stats_stop.set()
        stats_thread.join(timeout=5.0)
    engine.shutdown(drain=True)
    if ops is not None:
        ops.stop()
    if logger is not None:
        logger.close()
    finish_trace(tracer, args)
    wall = time.time() - t0

    stats = engine.stats()
    lat = stats["latency"]
    if fleet_mode:
        reqs = stats["requests"]
        shed_by = ", ".join(f"{k}={v}" for k, v in stats["shed"].items())
        print(
            f"\nfleet served {reqs['completed']} request(s) "
            f"({reqs['degraded']} degraded) from {len(pending)} "
            f"submission(s) in {wall:.1f}s — "
            f"{reqs['requeued']} requeue(s), {reqs['shed']} shed "
            f"({shed_by or 'none'}), {reqs['failed']} failed, "
            f"queue-wait p95 {stats['queue_wait']['p95']:.2f}s, "
            f"latency p50/p95/p99 = {lat['p50']:.2f}/{lat['p95']:.2f}/"
            f"{lat['p99']:.2f}s"
        )
        states = {name: rep["state"]
                  for name, rep in stats["replicas"].items()}
        print(f"replicas: {states}")
        if args.featurize_workers:
            feat = stats.get("featurize", {})
            freqs = feat.get("requests", {})
            print(f"featurize tier: {freqs.get('completed', 0)} job(s) "
                  f"({freqs.get('failed', 0)} failed, "
                  f"{freqs.get('requeued', 0)} requeued), "
                  f"{feat.get('worker_deaths', 0)} worker death(s), "
                  f"busy {feat.get('busy_seconds', 0.0):.2f}s")
        for sc in ([scaler] if scaler is not None else []) + pool_scalers:
            ev = sc.scale_events()
            ups = sum(1 for e in ev if e["action"] == "up")
            downs = sum(1 for e in ev if e["action"] == "down")
            dec = sc.snapshot()["decisions"]
            label = f" [{sc.pool}]" if sc.pool else ""
            print(f"autoscaler{label}: {ups} scale-up(s), {downs} "
                  f"scale-down(s), {dec.get('suppressed', 0)} "
                  f"suppressed, {dec.get('rejected', 0)} rejected; "
                  f"replicas now "
                  f"{engine.replica_count(sc.pool) if sc.pool else engine.replica_count()}")
        if pools and stats.get("shed", {}).get("too_long"):
            print(f"too-long sheds: {stats['shed']['too_long']} "
                  f"(sequence past every pool ceiling)")
        jstats = stats.get("journal")
        if jstats:
            print(f"journal: {jstats['accepted']} accepted, "
                  f"{jstats['settled']} settled, {jstats['pending']} "
                  f"pending, {jstats['corrupt']} corrupt, "
                  f"{jstats['write_errors']} write error(s)")
        bstats = stats.get("retry_budget")
        if bstats:
            print(f"retry budget: {bstats['tokens']:.1f}/"
                  f"{bstats['capacity']:g} token(s) left, "
                  f"{bstats['spent']} spent, "
                  f"{bstats['denied']} denial(s)")
        hstats = stats.get("hedging")
        if hstats and (hstats["issued"] or hstats["denied"]):
            denied = ", ".join(f"{k}={v}"
                               for k, v in sorted(hstats["denied"].items()))
            print(f"hedging: {hstats['issued']} issued "
                  f"(denied: {denied or 'none'}), "
                  f"{hstats['wasted_chip_seconds']:.2f} wasted "
                  f"chip-second(s)")
        if stats["errors"]:
            print(f"errors by code: {stats['errors']}")
        if injector is not None:
            print(f"faults delivered: {injector.delivered}"
                  + ("" if injector.exhausted()
                     else "  WARNING: plan not exhausted"))
    else:
        bat = stats["batches"]
        print(
            f"\nserved {stats['requests']['completed']} request(s) "
            f"({stats['requests']['coalesced']} coalesced) "
            f"from {len(pending)} submission(s) "
            f"in {wall:.1f}s — {stats['compiles']['count']} compiled "
            f"executable(s) over {len(buckets)} bucket(s), "
            f"mean batch {bat['mean_requests_per_batch']:.2f} req "
            f"(occupancy {100 * bat['mean_occupancy']:.0f}%), "
            f"cache hit rate {100 * stats['cache']['hit_rate']:.0f}%, "
            f"latency p50/p95/p99 = {lat['p50']:.2f}/{lat['p95']:.2f}/"
            f"{lat['p99']:.2f}s"
        )
        if stats["errors"]:
            print(f"errors by code: {stats['errors']}")
    if slo is not None:
        events = slo.events()
        fired = sum(1 for e in events if e["transition"] == "firing")
        if events:
            print(f"SLO: {fired} alert(s) fired "
                  f"({len(events)} transition(s)): "
                  + ", ".join(f"{e['objective']}:{e['transition']}"
                              for e in events[-6:]))
        else:
            print("SLO: no alerts")
    if recorder is not None:
        snap = recorder.snapshot()
        if snap["bundles"]:
            print(f"flight recorder: {len(snap['bundles'])} bundle(s) in "
                  f"{snap['dir']}")
    if args.stats_json:
        # same tmp+replace discipline as the periodic flusher: a crash
        # mid-dump must not tear the last good snapshot it kept alive
        tmp = args.stats_json + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(stats, fh, indent=2)
        os.replace(tmp, args.stats_json)
        _stats_flushed["final"] = True  # the atexit flush can stand down
        print(f"wrote {args.stats_json}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
