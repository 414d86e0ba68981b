"""Pass 4 — abstract-interpretation smoke.

`jax.eval_shape` every public op, the full model, and every
`training/presets.py` tier under abstract inputs. eval_shape runs the
whole trace — imports, shape arithmetic, dtype promotion, custom-VJP
wiring, Pallas kernel construction — without compiling or executing a
single FLOP, so an import-time or trace-time regression (exactly the
class that had the seed suite red) surfaces in seconds on a laptop
instead of minutes into a paid TPU reservation.

Each target is a named thunk; a target that raises becomes one SMOKE001
finding carrying the exception head. Registered targets:

  ops.*        flash / blockwise / dense / axial attention, feed-forward,
               the kernel dispatch registry (ops.dispatch)
  model.*      alphafold2 init+apply at smoke shapes
  serving.*    the serving pipeline + the engine's bucketed batch shapes
  reliability.* fault-plan parse/roundtrip, circuit-breaker transitions,
               verified-checkpoint save/restore (host-side construction
               checks — same gate, no shapes involved)
  telemetry.*  span tracer + chrome export, metric registry + Prometheus
               round-trip, regression-gate verdicts, goodput ledger +
               federation + loss-curve gate (host-side, like
               reliability.*)
  presets.*    e2e train-state init for every tier; full e2e loss (fwd +
              structure module) at smoke shapes

Add a target when adding a public op: append to `_targets()`.
"""

from __future__ import annotations

import json
import traceback
from typing import Callable, Dict, List

from alphafold2_tpu.analysis.common import Finding

PASS = "smoke"


def _targets() -> Dict[str, Callable[[], None]]:
    """name -> thunk that eval_shapes one surface (raises on breakage)."""
    import jax
    import jax.numpy as jnp

    import numpy as np

    key = jax.random.PRNGKey(0)
    f32 = jnp.float32

    def abstract(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype)

    targets: Dict[str, Callable[[], None]] = {}

    def register(name):
        def deco(fn):
            targets[name] = fn
            return fn

        return deco

    # --- ops ---------------------------------------------------------------
    @register("ops.flash_attention_tpu")
    def _flash():
        from alphafold2_tpu.ops.flash_kernel import flash_attention_tpu

        jax.eval_shape(
            lambda q, k, v, b: flash_attention_tpu(q, k, v, b, 0.35, qb=128, kb=128),
            abstract((2, 16, 8)), abstract((2, 24, 8)), abstract((2, 24, 8)),
            abstract((2, 24)),
        )

    @register("ops.flash_attention_lse")
    def _flash_lse():
        from alphafold2_tpu.ops.flash_kernel import flash_attention_lse

        jax.eval_shape(
            lambda q, k, v, b: flash_attention_lse(q, k, v, b, 0.35, qb=128, kb=128),
            abstract((2, 16, 8)), abstract((2, 16, 8)), abstract((2, 16, 8)),
            abstract((2, 16)),
        )

    @register("ops.flash_attention_grad")
    def _flash_grad():
        from alphafold2_tpu.ops.flash_kernel import flash_attention_tpu

        jax.eval_shape(
            jax.grad(
                lambda q, k, v, b: flash_attention_tpu(
                    q, k, v, b, 0.35, qb=128, kb=128
                ).sum(),
                argnums=(0, 1, 2),
            ),
            abstract((2, 16, 8)), abstract((2, 16, 8)), abstract((2, 16, 8)),
            abstract((2, 16)),
        )

    @register("ops.flash_attention_fused")
    def _flash_fused():
        from alphafold2_tpu.ops.flash_kernel import flash_attention_fused

        # 2-D pair-bias tiles + in-kernel output gate, fwd and grads
        # (incl. the real d_bias / d_gate cotangents)
        jax.eval_shape(
            jax.grad(
                lambda q, k, v, b, g: flash_attention_fused(
                    q, k, v, b, 0.35, gate=g, qb=128, kb=128
                ).sum(),
                argnums=(0, 1, 2, 3, 4),
            ),
            abstract((2, 16, 8)), abstract((2, 24, 8)), abstract((2, 24, 8)),
            abstract((2, 16, 24)), abstract((2, 16, 8)),
        )

    @register("ops.blockwise_attention")
    def _blockwise():
        from alphafold2_tpu.ops.flash import blockwise_attention

        jax.eval_shape(
            lambda q, k, v: blockwise_attention(q, k, v),
            abstract((2, 32, 4, 8)), abstract((2, 32, 4, 8)),
            abstract((2, 32, 4, 8)),
        )

    @register("ops.attention")
    def _attention():
        from alphafold2_tpu.ops import AttentionConfig, attention_apply, attention_init

        cfg = AttentionConfig(dim=32, heads=4, dim_head=8)
        params = jax.eval_shape(lambda k: attention_init(k, cfg), key)
        jax.eval_shape(
            lambda p, x: attention_apply(p, cfg, x), params, abstract((2, 12, 32))
        )

    @register("ops.axial_attention")
    def _axial():
        from alphafold2_tpu.ops import (
            AttentionConfig,
            axial_attention_apply,
            axial_attention_init,
        )

        cfg = AttentionConfig(dim=32, heads=4, dim_head=8)
        params = jax.eval_shape(lambda k: axial_attention_init(k, cfg), key)
        jax.eval_shape(
            lambda p, x: axial_attention_apply(p, cfg, x),
            params,
            abstract((1, 8, 8, 32)),
        )

    @register("ops.feed_forward")
    def _ff():
        from alphafold2_tpu.ops import feed_forward_apply, feed_forward_init

        params = jax.eval_shape(lambda k: feed_forward_init(k, 32), key)
        jax.eval_shape(
            lambda p, x: feed_forward_apply(p, x), params, abstract((2, 12, 32))
        )

    @register("ops.block_sparse_attention")
    def _sparse():
        from alphafold2_tpu.ops.sparse import SparseConfig, block_sparse_attention

        scfg = SparseConfig(block_size=16)
        jax.eval_shape(
            lambda q, k, v: block_sparse_attention(q, k, v, scfg=scfg),
            abstract((1, 64, 4, 8)), abstract((1, 64, 4, 8)),
            abstract((1, 64, 4, 8)),
        )

    # --- model -------------------------------------------------------------
    @register("model.alphafold2")
    def _model():
        from alphafold2_tpu.models import (
            Alphafold2Config,
            alphafold2_apply,
            alphafold2_init,
        )

        cfg = Alphafold2Config(
            dim=32, depth=1, heads=4, dim_head=8, max_seq_len=64
        )
        params = jax.eval_shape(lambda k: alphafold2_init(k, cfg), key)
        seq = abstract((1, 12), jnp.int32)
        jax.eval_shape(lambda p, s: alphafold2_apply(p, cfg, s), params, seq)

    @register("model.trunk_branch_parallel")
    def _trunk_branch_parallel():
        from alphafold2_tpu.models import Alphafold2Config
        from alphafold2_tpu.models.trunk import (
            sequential_trunk_apply,
            trunk_layer_init,
        )

        # the branch-parallel schedule with a gated attention config —
        # the two tentpole arms of PR 7 trace together
        cfg = Alphafold2Config(
            dim=32, depth=2, heads=4, dim_head=8, max_seq_len=64,
            trunk_schedule="branch_parallel", attn_gate=True,
        )
        layers = jax.eval_shape(
            lambda k: [
                trunk_layer_init(kk, cfg) for kk in jax.random.split(k, 2)
            ],
            key,
        )
        jax.eval_shape(
            lambda ls, x, m: sequential_trunk_apply(ls, cfg, x, m),
            layers, abstract((1, 8, 8, 32)), abstract((1, 4, 8, 32)),
        )

    # --- serving -------------------------------------------------------------
    @register("serving.pipeline")
    def _serving_pipeline():
        from alphafold2_tpu.models import (
            Alphafold2Config,
            alphafold2_init,
        )
        from alphafold2_tpu.serving.pipeline import predict_structure

        cfg = Alphafold2Config(dim=32, depth=1, heads=4, dim_head=8,
                               max_seq_len=64)
        params = jax.eval_shape(lambda k: alphafold2_init(k, cfg), key)
        jax.eval_shape(
            lambda p, t, m: predict_structure(
                p, cfg, t, mask=m, mds_iters=2, mds_init="classical"
            ),
            params, abstract((2, 12), jnp.int32), abstract((2, 12), jnp.bool_),
        )

    @register("serving.engine.bucketed_batch")
    def _serving_bucketed():
        # the exact shape family the engine AOT-compiles: a (max_batch,
        # bucket) padded batch for every ladder rung, msa-free and with a
        # fixed-row MSA stream (ServingConfig.msa_rows)
        from alphafold2_tpu.models import (
            Alphafold2Config,
            alphafold2_init,
        )
        from alphafold2_tpu.serving.bucketing import BucketLadder
        from alphafold2_tpu.serving.pipeline import predict_structure

        cfg = Alphafold2Config(dim=32, depth=1, heads=4, dim_head=8,
                               max_seq_len=32)
        params = jax.eval_shape(lambda k: alphafold2_init(k, cfg), key)
        ladder = BucketLadder((16, 32))
        assert ladder.bucket_for(9) == 16
        for bucket in ladder.buckets:
            jax.eval_shape(
                lambda p, t, m: predict_structure(
                    p, cfg, t, mask=m, mds_iters=2, mds_init="classical"
                ),
                params, abstract((4, bucket), jnp.int32),
                abstract((4, bucket), jnp.bool_),
            )
        jax.eval_shape(
            lambda p, t, m, ms, mm: predict_structure(
                p, cfg, t, mask=m, msa=ms, msa_mask=mm,
                mds_iters=2, mds_init="classical"
            ),
            params, abstract((4, 16), jnp.int32), abstract((4, 16), jnp.bool_),
            abstract((4, 4, 16), jnp.int32), abstract((4, 4, 16), jnp.bool_),
        )

    @register("ops.quant_matmul")
    def _quant_matmul():
        # per-channel PTQ + the fused-dequant Pallas kernel construction
        # (use_kernel=True traces the pallas_call), the XLA dequant
        # reference arm, and a stacked reversible-layout quantize
        from alphafold2_tpu.ops.quant import quant_matmul, quantize_weight

        def run(x, w):
            qw, scale = quantize_weight(w)
            return quant_matmul(x, qw, scale, use_kernel=True)

        jax.eval_shape(run, abstract((6, 4, 32)), abstract((32, 16)))

        def run_xla(x, w):
            qw, scale = quantize_weight(w, per_channel=False)
            return quant_matmul(x, qw, scale, use_kernel=False,
                                dtype=jnp.bfloat16)

        jax.eval_shape(run_xla, abstract((4, 32)), abstract((32, 16)))
        jax.eval_shape(
            lambda w: quantize_weight(w), abstract((3, 32, 16))
        )

    @register("ops.dispatch")
    def _dispatch():
        # registry construction + resolution for every op on every
        # platform (host arithmetic — no tracing), the introspection
        # table/tag, and a dispatch-routed op under eval_shape: the
        # whole resolve path must be trace-safe (ints and env only, no
        # device reads inside jit)
        from alphafold2_tpu.ops import dispatch
        from alphafold2_tpu.ops.flash import flash_attention

        for op in dispatch.ops():
            spec = dispatch.get(op)
            arm_names = set(spec.arm_names())
            assert "xla_ref" in arm_names, op
            for platform in ("tpu", "cpu"):
                arm = dispatch.resolve(op, request="auto",
                                       platform=platform, **spec.probe)
                assert arm in arm_names, (op, platform, arm)
            # forcing the reference arm never depends on shape support
            assert dispatch.resolve(op, request=False, platform="cpu",
                                    **spec.probe) == "xla_ref"
        assert dispatch.resolution_table()
        assert dispatch.resolution_tag().startswith("dispatch[")
        jax.eval_shape(
            lambda q, k, v: flash_attention(q, k, v, use_kernel="auto"),
            abstract((2, 16, 2, 8)), abstract((2, 24, 2, 8)),
            abstract((2, 24, 2, 8)),
        )

    @register("serving.quant_residency")
    def _quant_residency():
        # the engine's build-time precision seam: int8 config -> PTQ tree
        # (fp32 master untouched) + residency info, second build under
        # the same tag served from the process cache (host-side
        # construction check, like reliability.*)
        import dataclasses

        from alphafold2_tpu.models import Alphafold2Config, alphafold2_init
        from alphafold2_tpu.serving.quant_residency import (
            clear_residency_cache,
            resident_params,
        )

        tiny = Alphafold2Config(dim=16, depth=1, heads=2, dim_head=8,
                                max_seq_len=16)
        params = alphafold2_init(key, tiny)
        clear_residency_cache()
        try:
            same, info = resident_params(params, tiny)
            assert same is params and info["weight_dtype"] == "f32"
            int8_cfg = dataclasses.replace(tiny, weight_dtype="int8")
            tree, info = resident_params(params, int8_cfg)
            assert info["weight_bytes"] < info["fp32_weight_bytes"]
            assert not info["cached"]
            tree2, info2 = resident_params(params, int8_cfg)
            assert tree2 is tree and info2["cached"]
        finally:
            clear_residency_cache()

    @register("serving.fleet")
    def _serving_fleet():
        # fleet round trip over stub engines: admission -> dispatch ->
        # completion callback -> client future, plus clean shutdown. An
        # import- or wiring-time break in the fleet/admission layer must
        # surface here, not first in a paid chaos replay
        import numpy as np

        from alphafold2_tpu.models import Alphafold2Config
        from alphafold2_tpu.serving import (
            FleetConfig,
            ServingConfig,
            ServingEngine,
            ServingFleet,
        )

        tiny = Alphafold2Config(dim=16, depth=1, heads=2, dim_head=8,
                                max_seq_len=16)

        class Stub(ServingEngine):
            def _call_executable(self, bucket, tokens, mask, msa=None,
                                 msa_mask=None):
                B, Lb = tokens.shape
                return {
                    "coords": np.zeros((B, Lb, 3), np.float32),
                    "confidence": np.full((B, Lb), 0.5, np.float32),
                    "stress": np.zeros((B,), np.float32),
                }

        fleet = ServingFleet(
            {}, tiny,
            ServingConfig(buckets=(8, 16), max_batch=2, max_wait_s=0.0,
                          cache_capacity=0),
            FleetConfig(replicas=2, probe_interval_s=0),
            engine_factory=lambda n, c, h: Stub({}, tiny, c, fault_hook=h),
        )
        try:
            res = fleet.predict("ACDEF", timeout=30)
            assert res.coords.shape == (5, 3) and res.replica in ("r0", "r1")
            assert fleet.stats()["requests"]["completed"] == 1
        finally:
            fleet.shutdown()

    @register("serving.featurize")
    def _serving_featurize():
        # featurize tier round trip: the pure featurize function agrees
        # with its own re-run (determinism is the tier's bit-exactness
        # contract), and a 1-worker pool carries a job through submit ->
        # worker -> on_done -> clean shutdown
        import threading

        import numpy as np

        from alphafold2_tpu.serving import (
            BucketLadder,
            FeaturizeConfig,
            FeaturizePool,
            featurize_request,
        )

        ladder = BucketLadder((8, 16))
        a = featurize_request("acdef", ladder=ladder)
        b = featurize_request("ACDEF", ladder=ladder)
        assert a.seq == b.seq == "ACDEF" and a.bucket == 8
        np.testing.assert_array_equal(a.tokens, b.tokens)

        done = threading.Event()
        out = {}
        pool = FeaturizePool(FeaturizeConfig(workers=1), ladder)
        try:
            pool.submit("ACDEF", on_done=lambda bun, exc: (
                out.update(bundle=bun, exc=exc), done.set()))
            assert done.wait(30)
            assert out["exc"] is None and out["bundle"].bucket == 8
            assert pool.stats()["requests"]["completed"] == 1
        finally:
            pool.shutdown()

    @register("serving.autoscale")
    def _serving_autoscale():
        # autoscaler state machine over a stub fleet with an injected
        # clock: policy validation, a sustained-signal scale-up, and an
        # idle scale-down after the hysteresis window — no threads
        from alphafold2_tpu.serving import ReplicaAutoscaler, ScalePolicy
        from alphafold2_tpu.telemetry import MetricRegistry

        registry = MetricRegistry()
        depth = registry.gauge("fleet_queue_depth")
        occ = registry.gauge("fleet_occupancy")

        class StubFleet:
            _closed = False

            def __init__(self):
                self.registry = registry
                self.n = 1

            def sample_gauges(self):
                pass

            def replica_count(self):
                return self.n

            def add_replica(self):
                self.n += 1
                return f"r{self.n - 1}"

            def remove_replica(self, name=None):
                self.n -= 1
                return f"r{self.n}"

        fleet = StubFleet()
        t = [0.0]
        scaler = ReplicaAutoscaler(
            fleet,
            ScalePolicy(min_replicas=1, max_replicas=2, up_sustain=2,
                        down_sustain=2, up_cooldown_s=0.0,
                        down_cooldown_s=5.0),
            registry=registry, clock=lambda: t[0])
        depth.set(4), occ.set(2.0)
        for _ in range(2):
            scaler.tick()
            t[0] += 1.0
        assert fleet.n == 2, fleet.n
        depth.set(0), occ.set(0.0)
        t[0] += 10.0  # past the hysteresis window
        for _ in range(2):
            scaler.tick()
            t[0] += 1.0
        assert fleet.n == 1, fleet.n
        assert len(scaler.scale_events()) == 2

    @register("serving.sp_pipeline")
    def _serving_sp_pipeline():
        # the SP serving arm's executable under eval_shape (ISSUE 14):
        # the chip-free schedule plan picks per bucket, and the planned
        # SP apply traces the bucket-shaped serving forward over a
        # model-axis mesh — both dynamic-axial cuts
        from alphafold2_tpu.models import Alphafold2Config, alphafold2_init
        from alphafold2_tpu.parallel import make_mesh
        from alphafold2_tpu.serving import sp_arm
        from alphafold2_tpu.serving.pipeline import predict_structure

        cfg = Alphafold2Config(dim=16, depth=1, heads=2, dim_head=8,
                               max_seq_len=32)
        params = jax.eval_shape(lambda k: alphafold2_init(k, cfg), key)
        # planning is pure shard-count arithmetic + eval_shape pricing
        plan = sp_arm.plan_bucket_schedules(
            cfg, buckets=(16, 32), batch=2, msa_rows=0, shards=2,
            hbm_bytes=float(1 << 40), overrides={32: "sp_seq"})
        assert plan[32].schedule == "sp_seq"
        assert plan[16].schedule == "dense"
        assert plan[32].pair_bytes < sp_arm.schedule_residency(
            cfg, bucket=32, batch=2, msa_rows=0, schedule="dense",
            shards=2).pair_bytes
        # the trace itself runs at whatever mesh this host can provision
        # (the tier-1 suite forces 8 virtual CPU devices; a bare CLI run
        # degrades to a 1-shard mesh — same program, same trace checks)
        mesh = make_mesh({"sp": 2 if len(jax.devices()) >= 2 else 1})
        sp_apply = sp_arm.make_sp_apply_fn(mesh, "sp_seq")
        jax.eval_shape(
            lambda p, t, m: predict_structure(
                p, cfg, t, mask=m, mds_iters=2, mds_init="classical",
                model_apply_fn=sp_apply,
            ),
            params, abstract((2, 32), jnp.int32), abstract((2, 32), jnp.bool_),
        )
        msa_apply = sp_arm.make_sp_apply_fn(mesh, "sp_msa")
        jax.eval_shape(
            lambda p, t, m, ms, mm: predict_structure(
                p, cfg, t, mask=m, msa=ms, msa_mask=mm,
                mds_iters=2, mds_init="classical",
                model_apply_fn=msa_apply,
            ),
            params, abstract((2, 16), jnp.int32), abstract((2, 16), jnp.bool_),
            abstract((2, 2, 16), jnp.int32), abstract((2, 2, 16), jnp.bool_),
        )

    @register("serving.capability_routing")
    def _capability_routing():
        # the length-adaptive router over stub engines (ISSUE 14): short
        # work lands on the cheap pool, long work on the wide pool, and a
        # sequence past every pool's ceiling sheds with the sharp
        # sequence_too_long code instead of dying in dispatch
        import numpy as np

        from alphafold2_tpu.models import Alphafold2Config
        from alphafold2_tpu.serving import (
            FleetConfig,
            PoolSpec,
            SequenceTooLongError,
            ServingConfig,
            ServingEngine,
            ServingFleet,
        )

        tiny = Alphafold2Config(dim=16, depth=1, heads=2, dim_head=8,
                                max_seq_len=32)

        class Stub(ServingEngine):
            def _call_executable(self, bucket, tokens, mask, msa=None,
                                 msa_mask=None):
                B, Lb = tokens.shape
                return {
                    "coords": np.zeros((B, Lb, 3), np.float32),
                    "confidence": np.full((B, Lb), 0.5, np.float32),
                    "stress": np.zeros((B,), np.float32),
                }

        fleet = ServingFleet(
            {}, tiny,
            ServingConfig(buckets=(8, 16), max_batch=2, max_wait_s=0.0,
                          cache_capacity=0),
            FleetConfig(probe_interval_s=0, pools=(
                PoolSpec("short", buckets=(8, 16)),
                PoolSpec("long", buckets=(8, 16, 32)),
            )),
            engine_factory=lambda n, c, h: Stub({}, tiny, c, fault_hook=h),
        )
        try:
            a = fleet.predict("ACDEFGHIKL", timeout=30)          # L=10
            b = fleet.predict("ACDEFGHIKLMNPQRSTVWYACDEF", timeout=30)
            st = fleet.stats()
            assert st["replicas"][a.replica]["pool"] == "short"
            assert st["replicas"][b.replica]["pool"] == "long"
            assert st["replicas"][b.replica]["capability"]["max_len"] == 32
            try:
                fleet.submit("A" * 40)
                raise AssertionError("40-mer must shed: no pool ceiling "
                                     "covers it")
            except SequenceTooLongError as e:
                assert e.code == "sequence_too_long"
            assert fleet.stats()["shed"]["too_long"] == 1
        finally:
            fleet.shutdown()

    # --- reliability --------------------------------------------------------
    # host-side subsystems: no shapes to eval, but the same failure class —
    # an import- or construction-time regression in the chaos layer must
    # surface in the seconds-cheap gate, not first in a paid chaos run
    @register("reliability.fault_plan")
    def _fault_plan():
        from alphafold2_tpu.reliability import (
            FAULT_KINDS,
            REPLICA_FAULT_KINDS,
            FaultPlan,
        )

        plan = FaultPlan.from_json(json.dumps({
            "seed": 7,
            "faults": [
                {"kind": k, "at": i,
                 **({"replica": "r0"} if k in REPLICA_FAULT_KINDS else {})}
                for i, k in enumerate(FAULT_KINDS)
            ],
        }))
        assert FaultPlan.from_json(plan.to_json()) == plan
        inj = plan.injector()
        assert not inj.exhausted()
        # hook factories build (incl. the fleet replica-scoped hook)
        inj.checkpoint_hook(), inj.serving_hook(), inj.replica_hook("r0")

    @register("reliability.breaker")
    def _breaker():
        from alphafold2_tpu.reliability import CircuitBreaker, CircuitState

        t = [0.0]
        b = CircuitBreaker(threshold=2, reset_s=5.0, clock=lambda: t[0])
        assert b.allow()
        b.record_failure(), b.record_failure()
        assert b.state is CircuitState.OPEN and not b.allow()
        t[0] = 6.0
        assert b.allow() and not b.allow()  # one half-open probe
        b.record_success()
        assert b.state is CircuitState.CLOSED

    @register("reliability.health")
    def _health():
        from alphafold2_tpu.reliability import HealthMonitor, ReplicaState

        t = [0.0]
        seen = []
        up = [False]  # replica answers probes only once "repaired"
        mon = HealthMonitor(probe_interval_s=1.0, reprobe_interval_s=1.0,
                            fail_threshold=2, clock=lambda: t[0])
        mon.register("r0", probe=lambda: up[0],
                     on_drain=lambda n, why: seen.append(("drain", n)),
                     on_reinstate=lambda n: seen.append(("up", n)))
        # dispatch evidence drains at threshold, on the next tick
        assert not mon.record_failure("r0")
        assert mon.record_failure("r0")
        assert mon.state("r0") is ReplicaState.DOWN
        mon.tick(now=0.0)
        assert seen == [("drain", "r0")]
        assert mon.state("r0") is ReplicaState.DOWN  # re-probe still failing
        up[0] = True
        t[0] = 2.0
        mon.tick()  # re-probe succeeds -> reinstated
        assert mon.state("r0") is ReplicaState.HEALTHY
        assert seen[-1] == ("up", "r0")

    @register("reliability.verified_checkpoint")
    def _verified_ckpt():
        import tempfile

        import numpy as np

        from alphafold2_tpu.training.checkpoint import VerifiedCheckpointManager

        with tempfile.TemporaryDirectory() as d:
            mgr = VerifiedCheckpointManager(d)
            state = {"params": {"w": np.arange(4.0)},
                     "step": np.asarray(1, np.int32)}
            assert mgr.save(state, force=True)
            assert mgr.latest_step() == 1
            out = mgr.restore()
            np.testing.assert_array_equal(out["params"]["w"], state["params"]["w"])

    # --- telemetry ----------------------------------------------------------
    # host-side like the reliability targets: an import- or construction-
    # time break in the observability layer must surface in the cheap gate
    @register("telemetry.trace")
    def _telemetry_trace():
        from alphafold2_tpu.telemetry import NULL_TRACER, Tracer

        t = Tracer()
        with t.span("outer", cat="smoke", k=1):
            with t.span("inner"):
                pass
        events = t.chrome_trace()["traceEvents"]
        assert any(e["ph"] == "X" and e["name"] == "outer" for e in events)
        assert t.summary()["inner"]["count"] == 1
        # disabled fast path returns the shared no-op singleton
        assert NULL_TRACER.span("a") is NULL_TRACER.span("b")

    @register("telemetry.registry")
    def _telemetry_registry():
        from alphafold2_tpu.telemetry import (
            MetricRegistry,
            parse_prometheus_text,
        )

        r = MetricRegistry()
        r.counter("smoke_total", outcome="ok").inc(2)
        r.gauge("smoke_depth").set(3)
        r.histogram("smoke_seconds").observe(0.5)
        parsed = parse_prometheus_text(r.to_prometheus())
        assert parsed[("smoke_total", (("outcome", "ok"),))] == 2.0

    @register("telemetry.check")
    def _telemetry_check():
        from alphafold2_tpu.telemetry.check import check

        ok, _ = check({"metric": "smoke_steps_per_sec", "value": 1.0},
                      {"metric": "smoke_steps_per_sec", "value": 1.0})
        assert ok
        bad, rows = check({"metric": "smoke_steps_per_sec", "value": 0.5},
                          {"metric": "smoke_steps_per_sec", "value": 1.0})
        assert not bad and rows[0]["status"] == "regressed"

    @register("telemetry.goodput")
    def _telemetry_goodput():
        # host-side like the other telemetry targets: ledger exclusive-
        # time accounting + sums-to-wall invariant, detector firing, and
        # a gather-injected 2-process federation round-trip
        from alphafold2_tpu.telemetry import MetricRegistry
        from alphafold2_tpu.telemetry.goodput import (
            FederatedRegistryView,
            GoodputLedger,
            MetricFederation,
            StragglerDetector,
        )
        from alphafold2_tpu.telemetry.registry import parse_prometheus_text

        clk = [0.0]
        reg = MetricRegistry()
        led = GoodputLedger(reg, clock=lambda: clk[0])
        with led.account("data_fetch"):
            clk[0] += 1.0
        with led.account("compile"):
            clk[0] += 2.0
            with led.account("assembly"):  # nested: exclusive-time split
                clk[0] += 0.5
        led.step_complete(0)
        clk[0] += 0.5  # uncategorized -> idle
        snap = led.snapshot()
        # against the LIVE wall (snapshot's wall_s is the bucket sum, a
        # tautology); the injected clock is frozen so this is exact
        assert abs(sum(snap["buckets"].values()) - led.wall()) < 1e-9
        assert abs(snap["buckets"]["assembly"] - 0.5) < 1e-9
        assert abs(snap["buckets"]["compile"] - 2.0) < 1e-9
        assert led.step_bucket() == "step"  # compiled after the first step

        class _Rec:
            kinds: list = []

            def incident(self, kind, **attrs):
                self.kinds.append(kind)

        det = StragglerDetector(recorder=_Rec(), registry=reg,
                                patience=2, min_seconds=0.001)
        for s in range(2):
            det.observe_pod(s, [
                {"process": 0, "step_s": 0.1, "fetch_s": 0.01},
                {"process": 1, "step_s": 0.5, "fetch_s": 0.01},
            ])
        assert "train_straggler" in _Rec.kinds

        store = {}

        def gather_for(i):
            def gather(b):
                store[i] = b
                return [store.get(0, b), store.get(1, b)]

            return gather

        other = MetricRegistry()
        other.gauge("train_goodput_ratio").set(0.7)
        f0 = MetricFederation(reg, process_index=0, every=1,
                              gather_fn=gather_for(0))
        MetricFederation(other, process_index=1, every=1,
                         gather_fn=gather_for(1)).tick(0)
        f0.tick(0)
        text = FederatedRegistryView(reg, f0).to_prometheus()
        procs = {dict(k[1]).get("process")
                 for k in parse_prometheus_text(text)
                 if k[0] == "train_goodput_ratio"}
        assert procs == {"0", "1"}, procs

    @register("telemetry.cost_ledger")
    def _telemetry_cost_ledger():
        # host-side: the cost-plane algebra — analytic x measured join
        # over an int8 and an SP cell, derived chip-seconds/MFU, pool
        # service-rate model, publish round-trip
        from alphafold2_tpu.telemetry import MetricRegistry
        from alphafold2_tpu.telemetry.costs import ExecutableCostLedger

        reg = MetricRegistry()
        led = ExecutableCostLedger(reg)
        led.set_peak(1e12)
        k8 = led.register_cell(
            pool="short", bucket=256, schedule="dense",
            backend_arm="xla_ref", weight_dtype="int8",
            forward_flops=2e9, residency_bytes=1 << 28, max_batch=4)
        ksp = led.register_cell(
            pool="long", bucket=1024, schedule="sp_seq",
            backend_arm="pallas_tpu", weight_dtype="f32",
            forward_flops=8e10, residency_bytes=1 << 30, chips=8,
            max_batch=2)
        led.observe_batch(k8, device_seconds=0.1, requests=4)
        led.observe_batch(ksp, device_seconds=1.0, requests=2)
        rows = {(c["pool"], c["bucket"]): c for c in led.cells()}
        short = rows[("short", 256)]
        assert abs(short["chip_seconds_per_request"] - 0.1 / 4) < 1e-9
        assert abs(short["mfu"] - (4 * 2e9 / 0.1) / 1e12) < 1e-9
        long_ = rows[("long", 1024)]
        # the SP cell bills all 8 chips: 1.0s x 8 / 2 requests
        assert abs(long_["chip_seconds_per_request"] - 4.0) < 1e-9
        assert led.pool_rate_rps("short") == 40.0
        assert led.pool_rate_rps("unmeasured") is None
        led.publish()
        gauges = reg.snapshot()["gauges"]
        assert any(k.startswith("serve_chip_seconds_per_request")
                   for k in gauges), sorted(gauges)

    @register("serving.goodput")
    def _serving_goodput():
        # host-side: replica-second accounting, sums-to-wall via the
        # explicit idle remainder, probe overlap subtraction, publish
        from alphafold2_tpu.telemetry import MetricRegistry
        from alphafold2_tpu.telemetry.costs import ServeGoodputLedger

        clk = [0.0]
        reg = MetricRegistry()
        led = ServeGoodputLedger(reg, clock=lambda: clk[0])
        led.register("r0", "short")
        led.add("r0", "compile", 2.0)
        led.add("r0", "execute", 3.0)
        with led.probe_span("r0"):
            clk[0] += 1.0
            led.add("r0", "execute", 0.4)  # the probe's own dispatch
        clk[0] += 9.0
        totals = led.totals("r0")
        assert abs(totals["probe"] - 0.6) < 1e-9  # round trip minus inner
        assert abs(sum(totals.values()) - led.wall("r0")) < 1e-9
        snap = led.snapshot()
        assert abs(snap["replicas"]["r0"]["goodput_ratio"] - 3.4 / 10.0) \
            < 1e-9
        assert abs(snap["pools"]["short"]["goodput_ratio"] - 3.4 / 10.0) \
            < 1e-9
        led.publish()
        gauges = reg.snapshot()["gauges"]
        assert gauges['serve_goodput_ratio{pool="short",replica="r0"}'] \
            == snap["replicas"]["r0"]["goodput_ratio"]

    @register("telemetry.loss_curve_gate")
    def _telemetry_loss_curve():
        import os
        import tempfile

        from alphafold2_tpu.telemetry.check import check, load_loss_curve

        def write(vals):
            fd, path = tempfile.mkstemp(suffix=".jsonl")
            with os.fdopen(fd, "w") as fh:
                for i, v in enumerate(vals):
                    fh.write(json.dumps({"step": i, "loss": v}) + "\n")
            return path

        conv = write([3.0 / (1 + 0.2 * i) for i in range(40)])
        div = write([3.0 / (1 + 0.2 * i) + (0.2 * max(0, i - 20)) ** 1.5
                     for i in range(40)])
        try:
            ok, _ = check(load_loss_curve(conv), load_loss_curve(conv))
            assert ok
            bad, rows = check(load_loss_curve(div), load_loss_curve(conv))
            assert not bad
            assert any(r["metric"] == "loss_final"
                       and r["status"] == "regressed" for r in rows)
        finally:
            os.unlink(conv)
            os.unlink(div)

    # --- parallel / overlap -------------------------------------------------
    @register("parallel.partition_rules")
    def _partition_rules():
        # the registry matched over the REAL flagship train state
        # (eval_shape'd — depth-stacked reversible layout included):
        # raises on an unmatched leaf, a rank-incompatible rule, or a
        # registry/model drift — the same contract the sharding-lint
        # coverage pass enforces, kept here so `--files` smoke runs and
        # CI target lists exercise it too
        from jax.sharding import PartitionSpec

        from alphafold2_tpu.models import Alphafold2Config
        from alphafold2_tpu.parallel.rules import (
            match_partition_rules,
            partition_rules,
        )
        from alphafold2_tpu.training.harness import (
            TrainConfig,
            train_state_init,
        )

        cfg = Alphafold2Config(
            dim=16, depth=2, heads=2, dim_head=8, max_seq_len=32,
            reversible=True, msa_tie_row_attn=True,
            cross_attn_compress_ratio=2,
        )
        state = jax.eval_shape(
            lambda k: train_state_init(k, cfg, TrainConfig(grad_accum=1)),
            key,
        )
        specs = match_partition_rules(partition_rules(True), state)
        flat = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, PartitionSpec)
        )
        assert flat and all(isinstance(s, PartitionSpec) for s in flat)
        sharded = [s for s in flat if any(e is not None for e in s)]
        assert sharded, "TP registry produced no sharded specs"

    @register("parallel.overlap_bucketing")
    def _overlap_bucketing():
        import numpy as np  # module-level np is deleted after registration

        from alphafold2_tpu.parallel.overlap import (
            flatten_buckets,
            plan_buckets,
            unflatten_buckets,
        )

        tree = {
            "a": np.arange(6.0, dtype=np.float32).reshape(2, 3),
            "b": {"w": np.ones(17, np.float32),
                  "n": np.arange(4, dtype=np.int32)},
        }
        treedef, buckets = plan_buckets(tree, bucket_elems=8)
        covered = sorted(i for ix in buckets for i in ix)
        assert covered == list(range(3)), buckets
        out = unflatten_buckets(
            flatten_buckets(tree, buckets), tree, treedef, buckets
        )
        np.testing.assert_array_equal(np.asarray(out["a"]), tree["a"])
        np.testing.assert_array_equal(np.asarray(out["b"]["n"]),
                                      tree["b"]["n"])

    @register("parallel.axis_accum_step")
    def _axis_accum_step():
        # the DP-overlap step body traces under eval_shape with a dummy
        # axis env — catches pytree/bucket plumbing breaks without
        # needing the 8-device platform (the overlap pass covers the
        # lowered schedule itself)
        from alphafold2_tpu.models import Alphafold2Config
        from alphafold2_tpu.training.harness import (
            TrainConfig,
            make_axis_accum_train_step,
            train_state_init,
        )

        cfg = Alphafold2Config(dim=32, depth=1, heads=4, dim_head=8,
                               max_seq_len=32)
        tcfg = TrainConfig(grad_accum=2)
        step = make_axis_accum_train_step(cfg, tcfg,
                                          loss_fn=_distogram_loss(),
                                          axis_name="data")
        batch = {
            "seq": abstract((2, 1, 16), jnp.int32),
            "mask": abstract((2, 1, 16), jnp.bool_),
            "coords": abstract((2, 1, 16, 3)),
        }
        state = jax.eval_shape(
            lambda k: train_state_init(k, cfg, tcfg), key
        )

        def under_axis(state, batch):
            return step(state, batch, None)

        import functools

        jax.eval_shape(
            functools.partial(_with_dummy_axis, under_axis, "data"),
            state, batch,
        )

    def _distogram_loss():
        from alphafold2_tpu.training.harness import distogram_loss_fn

        return distogram_loss_fn

    def _with_dummy_axis(fn, axis_name, *args):
        # a single-shard vmapped axis gives lax.psum a bound axis name
        return jax.vmap(lambda _, a, b: fn(a, b), axis_name=axis_name,
                        in_axes=(0, None, None), out_axes=None)(
            jnp.zeros((1,)), *args)

    # --- training presets ---------------------------------------------------
    def _preset_init(tier):
        def thunk():
            from alphafold2_tpu.training.e2e import e2e_train_state_init
            from alphafold2_tpu.training.harness import TrainConfig
            from alphafold2_tpu.training.presets import north_star_e2e_config

            ecfg, _, _ = north_star_e2e_config(depth=2, tier=tier)
            tcfg = TrainConfig()
            jax.eval_shape(lambda k: e2e_train_state_init(k, ecfg, tcfg), key)

        return thunk

    for tier in ("smoke", "proportional", "north_star"):
        targets[f"presets.{tier}.init"] = _preset_init(tier)

    @register("presets.smoke.e2e_loss")
    def _e2e_loss():
        from alphafold2_tpu.training.e2e import (
            e2e_train_state_init,
            make_e2e_loss_fn,
        )
        from alphafold2_tpu.training.harness import TrainConfig
        from alphafold2_tpu.training.presets import north_star_e2e_config

        ecfg, crop, msa_rows = north_star_e2e_config(depth=2, tier="smoke")
        state = jax.eval_shape(
            lambda k: e2e_train_state_init(k, ecfg, TrainConfig()), key
        )
        loss_fn = make_e2e_loss_fn()
        batch = {
            "seq": abstract((1, crop), jnp.int32),
            "mask": abstract((1, crop), jnp.bool_),
            "coords": abstract((1, crop, 14, 3)),
            # the reversible trunk requires an MSA stream
            "msa": abstract((1, msa_rows, crop), jnp.int32),
            "msa_mask": abstract((1, msa_rows, crop), jnp.bool_),
        }
        jax.eval_shape(
            lambda p, b, k: loss_fn(p, ecfg, b, k), state["params"], batch, key
        )

    del np  # imported to fail fast when the env lacks it
    return targets


def run() -> List[Finding]:
    findings: List[Finding] = []
    try:
        targets = _targets()
    except Exception as e:  # registry construction itself failing is a finding
        findings.append(
            Finding(
                PASS,
                "SMOKE000",
                "alphafold2_tpu/analysis/abstract_smoke.py",
                1,
                f"smoke registry failed to build: {type(e).__name__}: {e}",
            )
        )
        return findings
    for name, thunk in targets.items():
        try:
            thunk()
        except Exception as e:
            tb = traceback.format_exc(limit=3).strip().splitlines()
            head = f"{type(e).__name__}: {e}".splitlines()[0][:300]
            findings.append(
                Finding(
                    PASS,
                    "SMOKE001",
                    name,
                    0,
                    f"eval_shape failed — {head} (tail: {tb[-1][:160]})",
                )
            )
    return findings
