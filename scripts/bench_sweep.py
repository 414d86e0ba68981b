"""On-chip tuning sweep for the north-star e2e workload.

Runs a sequence of single-measurement subprocesses, one after another (a
chip belongs to one process at a time; this parent never imports JAX, so
each worker gets the chip and a crashed worker does not take the sweep
down), covering the tuning axes PERF.md lists as unmeasured:

  * dense flash Pallas kernel vs XLA streaming (scripts/bench_kernels.py)
    at the axial shape the crop-384 workload produces;
  * e2e depth-12 step time across {kernel on/off}, {attn_batch_chunk},
    {flash_tile_elems}, {mds_bwd_iters}.

Each attempt gets its own timeout. A leg that fails, times out, or needs a
TPU and finds none is recorded as an error row and the sweep exits
non-zero at the end; a device number is never recorded from another
platform. Results append to PERF_SWEEP.jsonl (one JSON line per
measurement).

Usage: python scripts/bench_sweep.py [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "PERF_SWEEP.jsonl")

# Every worker calls enable_compile_cache() after `import jax` and before its
# first compile (alphafold2_tpu/compile_cache.py): JAX_COMPILATION_CACHE_DIR
# if the machine set it, else <checkout>/.jax_cache — so the legs of one
# sweep share compiled programs.
E2E_WORKER = r"""
import json, sys, time
import jax
import numpy as np

spec = json.loads(sys.argv[1])

if spec.get("require_tpu") and jax.devices()[0].platform != "tpu":
    # the schedule/fusion A/B legs are TPU measurements: without the chip
    # the leg is an error, not a number and not a skip
    sys.exit("leg requires a TPU device, JAX found "
             + jax.devices()[0].platform)

from alphafold2_tpu.training import (
    DataConfig, TrainConfig, e2e_loss_fn, e2e_train_state_init,
    make_train_step, north_star_e2e_config, stack_microbatches,
    synthetic_structure_batches,
)

depth = spec["depth"]
# ONE source for the north-star config (training/presets.py); the sweep's
# tuning axes are override patches so a knob rename breaks loudly here.
# Knobs ABSENT from the spec follow the preset defaults (depth-aware
# attention chunk/tile resolver, promoted 25-iter classical MDS), so the
# base legs always measure exactly the driver-bench configuration.
ecfg, crop, msa_rows = north_star_e2e_config(
    depth,
    model_overrides=dict(
        **({"attn_flash_qb_target": spec["qb_target"]}
           if "qb_target" in spec else {}),
        **({"attn_batch_chunk": spec["batch_chunk"]}
           if "batch_chunk" in spec else {}),
        **({"attn_flash_tile_elems": spec["tile_elems"]}
           if "tile_elems" in spec else {}),
        **({"ff_chunk_size": spec["ff_chunk"]} if "ff_chunk" in spec else {}),
        **({"attn_flash_compute_dtype_logits": spec["logit_bf16"]}
           if "logit_bf16" in spec else {}),
        **({"trunk_schedule": spec["trunk_schedule"]}
           if "trunk_schedule" in spec else {}),
        **({"attn_gate": spec["attn_gate"]} if "attn_gate" in spec else {}),
        **{k: spec[k] for k in ("heads", "dim_head") if k in spec},
    ),
    e2e_overrides=dict(
        **({"mds_bwd_iters": spec["mds_bwd_iters"]}
           if "mds_bwd_iters" in spec else {}),
        **({"mds_unroll": spec["mds_unroll"]}
           if "mds_unroll" in spec else {}),
        **({"mds_init": spec["mds_init"]} if "mds_init" in spec else {}),
        **({"mds_iters": spec["mds_iters"]} if "mds_iters" in spec else {}),
    ),
)
# Kernel policy (spec["kernel"]):
#   "force" -> zero the auto-dispatch j-threshold so every supported shape
#              takes the Pallas kernel (AF2_FLASH_AUTO_MIN_J=0);
#   "auto"  -> exactly what the driver bench runs (shape-aware heuristic);
#   "off"   -> no Pallas anywhere (the AF2_DISABLE_FLASH_KERNEL kill-switch).
#              NOTE: stricter than the retired e2e_nokernel leg (24.43
#              s/step), which monkeypatched only the DENSE kernel off and
#              left the block-sparse kernel live — an "off" number is not
#              directly comparable to that baseline in sparse configs.
# Env is set before any tracing, so the dispatch gate reads it everywhere.
import os
if spec["kernel"] == "force":
    os.environ["AF2_FLASH_AUTO_MIN_J"] = "0"
elif spec["kernel"] == "off":
    os.environ["AF2_DISABLE_FLASH_KERNEL"] = "1"
elif spec["kernel"] != "auto":
    raise ValueError(f"bad kernel policy {spec['kernel']!r}")
if spec.get("unfuse_gate"):
    # fused_gate control arm: Pallas kernel still runs the attention
    # core, the sigmoid gate applies as a separate XLA epilogue
    # (ops/flash.py gate_epilogue_unfused) — the on/off delta is the
    # epilogue fusion alone, not kernel-core-vs-XLA-streaming
    os.environ["AF2_UNFUSE_GATE_EPILOGUE"] = "1"

tcfg = TrainConfig(learning_rate=3e-4, grad_accum=1)
dcfg = DataConfig(batch_size=1, max_len=crop, msa_rows=msa_rows, seed=0)
batch = jax.device_put(next(stack_microbatches(synthetic_structure_batches(dcfg), 1)))
state = e2e_train_state_init(jax.random.PRNGKey(0), ecfg, tcfg)
# resident weight bytes of this leg's param tree (chip-free shape
# arithmetic; computed BEFORE the step donates the state) — the
# denominator the quant legs' residency win is measured against
from alphafold2_tpu.ops.quant import tree_weight_bytes
weight_hbm_bytes = tree_weight_bytes(state["params"])
step = make_train_step(ecfg, tcfg, loss_fn=e2e_loss_fn)

def run_one(state, batch, rng):
    s2, metrics = step(state, batch, rng)
    return s2, metrics["loss"]

compiled = jax.jit(run_one, donate_argnums=(0,)).lower(
    state, batch, jax.random.PRNGKey(1)).compile()
state, loss = compiled(state, batch, jax.random.PRNGKey(1))
np.asarray(loss)  # fetch: dispatch-proof warmup
t0 = time.perf_counter()
state, loss = compiled(state, batch, jax.random.PRNGKey(2))
loss = float(np.asarray(loss))
dt = time.perf_counter() - t0
assert np.isfinite(loss), loss
# the cross-backend matrix contract: every row records WHICH arm ran —
# resolved by the registry at the axial folded shape this leg's
# attention actually hits (crop*3 x crop*3), under the leg's env policy
from alphafold2_tpu.ops import dispatch as _dispatch
backend_arm = _dispatch.resolve("flash_attention", request="auto",
                                i=crop * 3, j=crop * 3,
                                dh=ecfg.model.dim_head)
print(json.dumps({"sec_per_step": round(dt, 2), "loss": round(loss, 4),
                  "weight_hbm_bytes": weight_hbm_bytes,
                  "platform": jax.devices()[0].platform,
                  "backend_arm": backend_arm}))
"""


# int8 weight-quantization A/B (ISSUE 8 tentpole): SERVING-shaped
# inference — the trunk forward -> distogram -> MDS pipeline the engine
# AOT-compiles — at the north-star model configuration, f32 master
# weights vs the per-channel-PTQ int8 tree through the fused-dequant
# Pallas matmul. BOTH arms pin the same forced attention-kernel core
# (AF2_FLASH_AUTO_MIN_J=0), so the on/off delta isolates the weight
# path: int8 HBM weight traffic + in-kernel dequant vs full fp32 weight
# reads. weight_hbm_bytes rides along so the residency win and the
# latency delta come from the same row. TPU legs (require_tpu: an error
# elsewhere — a CPU number would not measure HBM).
QUANT_WORKER = r"""
import json, sys, time, os
spec = json.loads(sys.argv[1])
os.environ["AF2_FLASH_AUTO_MIN_J"] = "0"   # same forced kernel core, both arms
if spec["weight_dtype"] == "int8":
    # force the fused-dequant kernel: a silent XLA-dequant fallback would
    # record fp32-traffic numbers under the int8 leg's name
    os.environ["AF2_QUANT_KERNEL"] = "force"
import jax
from alphafold2_tpu.compile_cache import enable_compile_cache
enable_compile_cache()
import numpy as np

if spec.get("require_tpu") and jax.devices()[0].platform != "tpu":
    sys.exit("leg requires a TPU device, JAX found "
             + jax.devices()[0].platform)

import dataclasses
import jax.numpy as jnp
from alphafold2_tpu.models import alphafold2_init
from alphafold2_tpu.ops.quant import quantize_tree, tree_weight_bytes
from alphafold2_tpu.serving.pipeline import predict_structure
from alphafold2_tpu.training import north_star_e2e_config

ecfg, crop, msa_rows = north_star_e2e_config(spec["depth"])
cfg = dataclasses.replace(ecfg.model, weight_dtype=spec["weight_dtype"])
# fp32 master init, PTQ as the serving tier would at engine build
params = alphafold2_init(jax.random.PRNGKey(0), ecfg.model)
if spec["weight_dtype"] == "int8":
    params = quantize_tree(params)
weight_hbm_bytes = tree_weight_bytes(params)
params = jax.device_put(params)

L = spec.get("len", crop)
rs = np.random.RandomState(0)
tokens = jnp.asarray(rs.randint(0, 21, (1, L)), jnp.int32)
mask = jnp.ones((1, L), bool)
msa = jnp.asarray(rs.randint(0, 21, (1, msa_rows, L)), jnp.int32)
msa_mask = jnp.ones((1, msa_rows, L), bool)

def run(params, tokens, mask, msa, msa_mask, key):
    out = predict_structure(params, cfg, tokens, mask=mask, msa=msa,
                            msa_mask=msa_mask, rng=key,
                            mds_iters=25, mds_init="classical")
    return out["coords"], out["confidence"]

compiled = jax.jit(run).lower(
    params, tokens, mask, msa, msa_mask, jax.random.PRNGKey(1)).compile()
c, _ = compiled(params, tokens, mask, msa, msa_mask, jax.random.PRNGKey(1))
np.asarray(c)  # fetch: dispatch-proof warmup
iters = spec.get("iters", 3)
t0 = time.perf_counter()
for i in range(iters):
    c, _ = compiled(params, tokens, mask, msa, msa_mask,
                    jax.random.PRNGKey(2 + i))
c.block_until_ready()
dt = (time.perf_counter() - t0) / iters
assert np.isfinite(np.asarray(c)).all()
# record which arm actually served the weight path (the int8 arm pins
# AF2_QUANT_KERNEL=force above, so the resolver must answer pallas_tpu
# or raise; the f32 arm has no quant op in the program — record the
# attention arm it rode instead)
from alphafold2_tpu.ops import dispatch as _dispatch
if spec["weight_dtype"] == "int8":
    backend_arm = _dispatch.resolve("quant_matmul", request="auto",
                                    m=L, k=cfg.dim, n=cfg.dim,
                                    x_dtype=jnp.float32)
else:
    backend_arm = _dispatch.resolve("flash_attention", request="auto",
                                    i=L * 3, j=L * 3, dh=cfg.dim_head)
print(json.dumps({"sec_per_iter": round(dt, 3),
                  "weight_hbm_bytes": weight_hbm_bytes,
                  "platform": jax.devices()[0].platform,
                  "backend_arm": backend_arm}))
"""


# Chip-free quant leg: runs on ANY host (no require_tpu) — residency and
# quality are counts and parities, not device timings. Three records in one
# row:
#   * north-star residency via jax.eval_shape (no params materialized):
#     weight_hbm_bytes f32 vs int8, full-tree ratio, and the >=3.5x
#     quantized-tensor ratio the ISSUE 8 acceptance pins (asserted);
#   * interpret-mode fused-dequant kernel vs the XLA dequant reference
#     arm on a real (small) model forward — allclose-pinned;
#   * int8-vs-fp32 quality deltas at the same small shapes: mean
#     distogram KL and top-L contact precision of the int8 arm scored
#     against the fp32 arm's contacts. telemetry.check gates these via
#     the *distogram_kl* (lower) / *contact_precision* (higher) rules.
QUANT_PARITY_WORKER = r"""
import json, sys, os
spec = json.loads(sys.argv[1])
import jax
from alphafold2_tpu.compile_cache import enable_compile_cache
enable_compile_cache()
import jax.numpy as jnp
import numpy as np

from alphafold2_tpu.models import (
    Alphafold2Config, alphafold2_apply, alphafold2_init,
)
from alphafold2_tpu.ops.quant import (
    quantize_tree, quantized_path_bytes, tree_weight_bytes,
)
from alphafold2_tpu.training import north_star_e2e_config

from alphafold2_tpu.ops import dispatch as _dispatch

out = {"platform": jax.devices()[0].platform,
       # which arm the quant matmuls below actually resolve to on this
       # host (cross-backend matrix field — platform-qualifies the row)
       "backend_arm": _dispatch.resolve("quant_matmul", request="auto",
                                        m=32, k=32, n=32,
                                        x_dtype=jnp.float32)}

# 1) residency at the NORTH-STAR preset — pure shape arithmetic
ecfg, crop, msa_rows = north_star_e2e_config(spec.get("depth", 12))
shapes = jax.eval_shape(
    lambda k: alphafold2_init(k, ecfg.model), jax.random.PRNGKey(0))
qshapes = jax.eval_shape(quantize_tree, shapes)
before, after = quantized_path_bytes(shapes)
out["weight_hbm_bytes_f32"] = tree_weight_bytes(shapes)
out["weight_hbm_bytes_int8"] = tree_weight_bytes(qshapes)
out["weight_hbm_ratio"] = round(
    out["weight_hbm_bytes_f32"] / out["weight_hbm_bytes_int8"], 3)
out["quant_weight_ratio"] = round(before / after, 3)
assert out["quant_weight_ratio"] >= 3.5, out  # ISSUE 8 acceptance pin

# 2) kernel-vs-XLA parity + int8-vs-fp32 quality at CPU-runnable shapes
cfg = Alphafold2Config(dim=32, depth=2, heads=2, dim_head=16,
                       max_seq_len=48, msa_tie_row_attn=True)
params = alphafold2_init(jax.random.PRNGKey(1), cfg)
qp = quantize_tree(params)
rs = np.random.RandomState(0)
L = 32
seq = jnp.asarray(rs.randint(0, 21, (1, L)))
msa = jnp.asarray(rs.randint(0, 21, (1, 4, L)))
mask = jnp.ones((1, L), bool)
mmask = jnp.ones((1, 4, L), bool)

def logits_with(p, kernel_env):
    # eager apply: the dispatch gate re-reads AF2_QUANT_KERNEL per call
    os.environ["AF2_QUANT_KERNEL"] = kernel_env
    try:
        return np.asarray(alphafold2_apply(
            p, cfg, seq, msa, mask=mask, msa_mask=mmask), np.float32)
    finally:
        os.environ.pop("AF2_QUANT_KERNEL", None)

l_f32 = logits_with(params, "off")
l_krn = logits_with(qp, "force")  # fused-dequant kernel (interpret off-TPU)
l_xla = logits_with(qp, "off")    # XLA dequant reference arm
np.testing.assert_allclose(l_krn, l_xla, atol=5e-4)
out["kernel_vs_xla_max_abs"] = float(np.abs(l_krn - l_xla).max())

def softmax(z):
    z = z - z.max(-1, keepdims=True)
    e = np.exp(z)
    return e / e.sum(-1, keepdims=True)

p_ref, p_q = softmax(l_f32), softmax(l_krn)
kl = (p_ref * (np.log(p_ref + 1e-9) - np.log(p_q + 1e-9))).sum(-1)
# floored at 1e-9: a recorded 0.0 baseline would turn ANY later nonzero
# KL into an infinite relative change under telemetry.check's
# lower-better rule — the floor keeps the gate's ratio math finite
out["distogram_kl"] = max(float(kl.mean()), 1e-9)

# top-L contact precision, int8 arm scored against the fp32 arm: rank
# pairs (i < j, |i-j| >= 3) by model distance (center_distogram), take
# each arm's L strongest contacts, precision = overlap / L. Rank-based,
# so it needs no absolute contact threshold a random-init distogram
# might never cross.
from alphafold2_tpu.geometry import center_distogram

def top_contacts(logits):
    d, _ = center_distogram(jnp.asarray(softmax(logits)))
    d = np.asarray(d)[0]
    ii, jj = np.triu_indices(L, k=3)
    order = np.argsort(d[ii, jj])[:L]
    return set(zip(ii[order].tolist(), jj[order].tolist()))

ref, got = top_contacts(l_f32), top_contacts(l_krn)
out["contact_precision"] = round(len(ref & got) / max(len(got), 1), 4)
print(json.dumps(out))
"""


# Chip-free featurization-overlap leg (ISSUE 11): drives a REAL tiny
# fleet (1 replica, precompiled) with a 2-worker featurize tier in front
# of the admission queue, with per-job featurize cost made non-trivial by
# a deterministic slow_featurize plan (a stand-in for real MSA assembly —
# the tier's value is structural, not CPU-speed-dependent). Records
#   featurize_overlap_ratio = (featurize busy + execute busy) / wall
# > 1 means CPU feature prep genuinely ran WHILE the engine dispatched
# (the ParaFold split working); a regression that re-serializes the tier
# drags the ratio to <= 1. Gated by telemetry.check's *overlap_ratio*
# higher-is-better rule once recorded.
FEATURIZE_WORKER = r"""
import json, sys, time
spec = json.loads(sys.argv[1])
import jax
from alphafold2_tpu.compile_cache import enable_compile_cache
enable_compile_cache()
import numpy as np

from alphafold2_tpu.constants import AA_ORDER
from alphafold2_tpu.models import Alphafold2Config, alphafold2_init
from alphafold2_tpu.reliability import Fault, FaultPlan
from alphafold2_tpu.serving import FleetConfig, ServingConfig, ServingFleet
from alphafold2_tpu.telemetry import Tracer

n = spec.get("n", 24)
delay = spec.get("featurize_delay_s", 0.08)
cfg = Alphafold2Config(dim=16, depth=1, heads=2, dim_head=8, max_seq_len=32)
params = alphafold2_init(jax.random.PRNGKey(0), cfg)
plan = FaultPlan(faults=(
    Fault("slow_featurize", at=0, count=n, delay_s=delay),
))
tracer = Tracer(enabled=True)
fleet = ServingFleet(
    params, cfg,
    ServingConfig(buckets=(16, 32), max_batch=4, max_queue=64,
                  max_wait_s=0.01, mds_iters=4, cache_capacity=0,
                  precompile=True),
    FleetConfig(replicas=1, queue_capacity=64, featurize_workers=2,
                probe_interval_s=0, default_timeout_s=300.0),
    injector=plan.injector(), tracer=tracer,
)
rng = np.random.RandomState(0)
seqs = ["".join(AA_ORDER[rng.randint(0, 20)] for _ in range(
    int(rng.randint(8, 32)))) for _ in range(n)]
t0 = time.perf_counter()
reqs = [fleet.submit(s) for s in seqs]
for r in reqs:
    r.result(timeout=300)
wall = time.perf_counter() - t0
fams = fleet.registry.collect()
feat_busy = sum(
    m.value
    for m in fams.get("featurize_busy_seconds_total", (None, {}))[1].values()
)
summary = tracer.summary()
exec_busy = summary.get("serving.execute", {}).get("total_s", 0.0)
fleet.shutdown(drain=True)
assert feat_busy > 0 and exec_busy > 0, (feat_busy, exec_busy)
ratio = (feat_busy + exec_busy) / wall
from alphafold2_tpu.ops import dispatch as _dispatch
print(json.dumps({
    "featurize_overlap_ratio": round(ratio, 3),
    "featurize_busy_s": round(feat_busy, 3),
    "execute_busy_s": round(exec_busy, 3),
    "wall_s": round(wall, 3),
    "n_requests": n,
    "platform": jax.devices()[0].platform,
    "backend_arm": _dispatch.resolve("flash_attention", request="auto",
                                     i=32, j=32, dh=8),
}))
"""


# Chip-free training-goodput leg (ISSUE 12): a short REAL run_resilient
# training run (tiny model) under the goodput ledger, with a
# deterministic slow_data fault plan stalling several fetches — so the
# leg proves badput ATTRIBUTION, not just a ratio: the injected stall
# must land in the data_fetch bucket, page a train_data_stall incident,
# and the buckets must sum to wall clock within 1%. Records
#   goodput_ratio            (telemetry.check *goodput* higher-better)
#   data_stall_badput_s      (*badput*/*stall* lower-better)
# so a pipeline regression that re-introduces data stalls gates
# automatically once recorded.
GOODPUT_WORKER = r"""
import json, sys, tempfile
spec = json.loads(sys.argv[1])
import jax
from alphafold2_tpu.compile_cache import enable_compile_cache
enable_compile_cache()
import numpy as np

from alphafold2_tpu.models import Alphafold2Config
from alphafold2_tpu.reliability import Fault, FaultPlan
from alphafold2_tpu.telemetry import MetricRegistry
from alphafold2_tpu.telemetry.goodput import (
    GoodputLedger, StragglerDetector, TrainTelemetry,
)
from alphafold2_tpu.telemetry.ops_plane import FlightRecorder
from alphafold2_tpu.training import (
    DataConfig, TrainConfig, make_train_step, resilient_batches,
    run_resilient, synthetic_microbatch_fn, train_state_init,
    with_fault_injection,
)

steps = spec.get("steps", 8)
delay = spec.get("stall_delay_s", 0.1)
cfg = Alphafold2Config(dim=16, depth=1, heads=2, dim_head=8, max_seq_len=32)
tcfg = TrainConfig(learning_rate=1e-3, grad_accum=1)
dcfg = DataConfig(batch_size=1, max_len=16, seed=0)

plan = FaultPlan(faults=(
    Fault("slow_data", at=2, count=max(2, steps // 2), delay_s=delay),
))
injector = plan.injector()
registry = MetricRegistry()
ledger = GoodputLedger(registry)
flight_dir = tempfile.mkdtemp()
recorder = FlightRecorder(flight_dir, registry=registry,
                          stats_fn=ledger.snapshot, min_interval_s=0)
detector = StragglerDetector(recorder=recorder, registry=registry,
                             patience=2, stall_fraction=0.5,
                             min_seconds=0.001)
telemetry = TrainTelemetry(ledger=ledger, detector=detector,
                           recorder=recorder)

fetch = resilient_batches(synthetic_microbatch_fn(dcfg, tcfg.grad_accum),
                          injector=injector)
state = train_state_init(jax.random.PRNGKey(0), cfg, tcfg)
step_fn = with_fault_injection(
    jax.jit(make_train_step(cfg, tcfg)), injector)
base_rng = jax.random.PRNGKey(1)
state = run_resilient(
    step_fn, state, fetch, steps=steps,
    make_rng=lambda i: jax.random.fold_in(base_rng, i),
    telemetry=telemetry,
)

snap = ledger.snapshot()
assert injector.exhausted(), "slow_data plan never fully delivered"
live_wall = ledger.wall()  # NOT snap["wall_s"] (that IS the bucket sum):
# only a live reading catches double-accounting inflating the sum
assert abs(sum(snap["buckets"].values()) - live_wall) \
    <= 0.01 * live_wall, (snap, live_wall)
stall_s = snap["buckets"]["data_fetch"]
assert stall_s >= delay, ("injected stall not booked as data-stall "
                          "badput", stall_s)
bundles = recorder.snapshot()["bundles"]
assert any("train_data_stall" in b for b in bundles), bundles
from alphafold2_tpu.ops import dispatch as _dispatch
print(json.dumps({
    "goodput_ratio": round(snap["goodput_ratio"], 4),
    "data_stall_badput_s": round(stall_s, 3),
    "wall_s": round(snap["wall_s"], 3),
    "steps_per_sec": round(steps / snap["wall_s"], 3),
    "n_steps": steps,
    "platform": jax.devices()[0].platform,
    "backend_arm": _dispatch.resolve("flash_attention", request="auto",
                                     i=16, j=16, dh=8),
}))
"""


# SP serving arm A/B (ISSUE 14 tentpole): the SAME serving-shaped
# bucket executable (engine AOT path: padded batch -> trunk -> distogram
# -> MDS) with the trunk dense vs sequence-parallel over an sp_shards
# mesh. TPU-only (require_tpu: a CPU ring measures nothing about ICI, so
# another platform is an error); skips when the host exposes fewer
# devices than the mesh needs (run it on the four-chip host). The on-arm FORCES sp_seq at the bucket via the per-bucket
# override so the 16 GB heuristic cannot silently serve the dense twin
# under the SP leg's name.
SERVE_SP_WORKER = r"""
import json, sys, time, os
spec = json.loads(sys.argv[1])
import jax
from alphafold2_tpu.compile_cache import enable_compile_cache
enable_compile_cache()
import numpy as np

platform = jax.devices()[0].platform
if spec.get("require_tpu") and platform != "tpu":
    sys.exit("leg requires a TPU device, JAX found " + platform)
shards = spec["sp_shards"] if spec["sp_on"] else 0
if shards and len(jax.devices()) < shards:
    print(json.dumps({"skipped": f"SP mesh needs {shards} devices",
                      "platform": platform,
                      "devices": len(jax.devices())}))
    sys.exit(0)

import dataclasses
import jax.numpy as jnp
from alphafold2_tpu.models import alphafold2_init
from alphafold2_tpu.serving import ServingConfig, ServingEngine
from alphafold2_tpu.training import north_star_e2e_config
from alphafold2_tpu.constants import AA_ORDER
from alphafold2_tpu.ops import dispatch as _dispatch

bucket = spec["bucket"]
ecfg, crop, msa_rows = north_star_e2e_config(spec["depth"])
cfg = dataclasses.replace(ecfg.model, max_seq_len=bucket)
params = alphafold2_init(jax.random.PRNGKey(0), cfg)
scfg = ServingConfig(
    buckets=(bucket,), max_batch=1, mds_iters=25, cache_capacity=0,
    precompile=True, request_timeout_s=None,
    sp_shards=shards,
    sp_schedules=(((bucket, "sp_seq"),) if shards else ()),
)
t0 = time.perf_counter()
eng = ServingEngine(params, cfg, scfg)
compile_s = time.perf_counter() - t0
rs = np.random.RandomState(0)
seqs = ["".join(AA_ORDER[i] for i in rs.randint(0, 20, bucket))
        for _ in range(spec.get("iters", 3) + 1)]
try:
    eng.predict(seqs[0])  # warmup dispatch
    t0 = time.perf_counter()
    for s in seqs[1:]:
        res = eng.predict(s)
    dt = (time.perf_counter() - t0) / (len(seqs) - 1)
    assert np.isfinite(res.coords).all()
    sp_stats = eng.stats().get("sp")
finally:
    eng.shutdown()
out = {"sec_per_iter": round(dt, 3), "bucket": bucket,
       "sp_shards": shards, "compile_s": round(compile_s, 1),
       "platform": platform,
       "backend_arm": _dispatch.resolve(
           "flash_attention", request="auto", i=bucket, j=bucket,
           dh=cfg.dim_head)}
if sp_stats:
    plan = sp_stats["schedules"][str(bucket)]
    assert plan["schedule"] == "sp_seq", plan
    out["sp_total_bytes"] = plan["total_bytes"]
print(json.dumps(out))
"""


# Chip-free routed-fleet leg (ISSUE 14): the length-adaptive router end
# to end on the virtual CPU mesh — a mixed-length trace over a real
# two-pool fleet (dense short pool + sp_seq long pool), asserting every
# in-ladder request completes on its expected pool with ZERO too_long
# failures, and recording the per-pool queue-wait signals the per-pool
# autoscalers consume. Runs on ANY host (pins JAX_PLATFORMS=cpu + the
# 8-device virtual platform, like the overlap lint): the row is real
# today, not armed.
SERVE_ROUTED_WORKER = r"""
import json, sys, time, os
spec = json.loads(sys.argv[1])
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
import jax
from alphafold2_tpu.compile_cache import enable_compile_cache
enable_compile_cache()
import numpy as np

from alphafold2_tpu.models import Alphafold2Config, alphafold2_init
from alphafold2_tpu.serving import (
    FleetConfig, PoolSpec, SequenceTooLongError, ServingConfig,
    ServingFleet,
)
from alphafold2_tpu.constants import AA_ORDER

cfg = Alphafold2Config(dim=16, depth=1, heads=2, dim_head=8,
                       max_seq_len=32)
params = alphafold2_init(jax.random.PRNGKey(0), cfg)
scfg = ServingConfig(buckets=(8, 16), max_batch=2, max_wait_s=0.01,
                     mds_iters=4, request_timeout_s=None)
fleet = ServingFleet(
    params, cfg, scfg,
    FleetConfig(probe_interval_s=0, reprobe_interval_s=30.0,
                default_timeout_s=None,
                pools=(PoolSpec("short", replicas=1, buckets=(8, 16)),
                       PoolSpec("long", replicas=1, sp_shards=2,
                                buckets=(8, 16, 32)))))
rs = np.random.RandomState(0)
n = spec.get("n", 16)
lens = [int(rs.randint(4, 17)) if i % 2 else int(rs.randint(17, 33))
        for i in range(n)]
t0 = time.perf_counter()
reqs = []
shed = 0
for i, L in enumerate(lens + [40]):  # the 40-mer must shed, not fail
    seq = "".join(AA_ORDER[j] for j in rs.randint(0, 20, L))
    try:
        reqs.append((L, fleet.submit(seq)))
    except SequenceTooLongError:
        shed += 1
by_pool = {"short": 0, "long": 0}
for L, r in reqs:
    res = r.result(timeout=600)
    st = fleet.stats()["replicas"][res.replica]
    expect = "short" if L <= 16 else "long"
    assert st["pool"] == expect, (L, res.replica, st["pool"])
    by_pool[expect] += 1
wall = time.perf_counter() - t0
stats = fleet.stats()
hists = stats["telemetry"]["metrics"]["histograms"]
waits = {name: hists.get(
    f'fleet_pool_queue_wait_seconds{{pool="{name}"}}', {})
    for name in ("short", "long")}
assert stats["requests"]["failed"] == 0, stats["requests"]
assert stats["shed"].get("too_long", 0) == 1 and shed == 1
fleet.shutdown()
out = {"sec_per_iter": round(wall / len(reqs), 3),
       "routed_short": by_pool["short"], "routed_long": by_pool["long"],
       "routed_long_frac": round(by_pool["long"] / len(reqs), 3),
       "too_long_shed": shed,
       "platform": "cpu", "backend_arm": "xla_ref"}
for name, w in waits.items():
    if isinstance(w, dict) and w.get("p95") is not None:
        out[f"pool_queue_wait_p95_{name}"] = round(w["p95"], 4)
print(json.dumps(out))
"""


# Serving cost plane (ISSUE 15): chip-free leg — a REAL tiny fleet on
# CPU serves a short trace, then the row records what the cost ledger
# measured: per-request chip-seconds for the served cells, the serving
# goodput ratio, and the headroom model's capacity column. Gated by
# telemetry.check's *chip_seconds* (lower) / *serve_goodput* /
# *headroom* (higher) rules, platform-qualified like every row.
SERVE_COSTS_WORKER = r"""
import json, sys, time, os
spec = json.loads(sys.argv[1])
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
from alphafold2_tpu.compile_cache import enable_compile_cache
enable_compile_cache()
import numpy as np

from alphafold2_tpu.models import Alphafold2Config, alphafold2_init
from alphafold2_tpu.serving import FleetConfig, ServingConfig, ServingFleet
from alphafold2_tpu.constants import AA_ORDER

cfg = Alphafold2Config(dim=16, depth=1, heads=2, dim_head=8,
                       max_seq_len=16)
params = alphafold2_init(jax.random.PRNGKey(0), cfg)
fleet = ServingFleet(
    params, cfg,
    ServingConfig(buckets=(8, 16), max_batch=2, max_wait_s=0.01,
                  mds_iters=4, request_timeout_s=None),
    FleetConfig(replicas=2, probe_interval_s=0, reprobe_interval_s=30.0,
                default_timeout_s=None))
rs = np.random.RandomState(0)
n = spec.get("n", 16)
t0 = time.perf_counter()
reqs = []
for i in range(n):
    L = int(rs.randint(4, 17))
    seq = "".join(AA_ORDER[j] for j in rs.randint(0, 20, L))
    reqs.append(fleet.submit(seq))
for r in reqs:
    r.result(timeout=600)
wall = time.perf_counter() - t0
fleet.sample_gauges()
time.sleep(0.06)
fleet.sample_gauges()  # second pass: arrival-rate EMA + headroom arm
st = fleet.stats()
cells = [c for c in st["costs"]["cells"] if c["requests"]]
assert cells, "no cost-ledger cell measured"
# traffic-weighted per-request chip cost over the served cells
total_req = sum(c["requests"] for c in cells)
csr = sum(c["chip_seconds_per_request"] * c["requests"]
          for c in cells) / total_req
goodput = st["serve_goodput"]["pools"]["default"]["goodput_ratio"]
# sums-to-wall within 1% against the ledger's LIVE clock wall (the
# snapshot's wall_s is the bucket sum — comparing against it would be
# a tautology); accounted can only exceed wall via cross-thread
# accounting overlap, which this bounds
for name in st["serve_goodput"]["replicas"]:
    tot = sum(fleet.goodput.totals(name).values())
    wall_now = fleet.goodput.wall(name)
    assert tot <= wall_now * 1.01 + 1e-6, (name, tot, wall_now)
head = st["headroom"].get("default", {})
out = {"sec_per_iter": round(wall / n, 4),
       "serve_chip_seconds_per_request": round(csr, 5),
       "serve_goodput_ratio": round(goodput, 4),
       "cells_measured": len(cells),
       "platform": "cpu", "backend_arm": "xla_ref"}
if head.get("capacity_per_sec"):
    out["capacity_per_sec"] = round(head["capacity_per_sec"], 3)
    out["headroom_ratio"] = round(head["headroom_ratio"], 4)
fleet.shutdown()
print(json.dumps(out))
"""


# Cross-backend dispatch matrix (ISSUE 13 tentpole): one leg per
# (hot op, backend arm) over the ops/dispatch.py registry. The arm is
# pinned via AF2_KERNEL_BACKEND_<OP> and VERIFIED against the resolver
# (a leg that silently resolved elsewhere would record one arm's numbers
# under another's name — the worker asserts instead). xla_ref legs run
# on ANY host and produce platform-qualified rows (telemetry.check keys
# them `<leg>.<platform>.<backend_arm>.<metric>`, so a CPU row only ever
# gates against a CPU baseline). pallas_tpu legs carry
# require_platform "tpu" and are an error without the chip; gpu legs
# record a structured skip (no GPU host exists).
DISPATCH_WORKER = r"""
import json, sys, time, os
spec = json.loads(sys.argv[1])
op, arm = spec["op"], spec["arm"]
os.environ["AF2_KERNEL_BACKEND_" + op.upper()] = arm
import jax
from alphafold2_tpu.compile_cache import enable_compile_cache
enable_compile_cache()
import jax.numpy as jnp
import numpy as np

platform = jax.devices()[0].platform
base = {"op": op, "backend_arm": arm, "platform": platform}
need = spec.get("require_platform")
if need == "tpu" and platform != "tpu":
    sys.exit("leg requires a TPU device, JAX found " + platform)
# "gpu" must admit every GPU spelling jax reports (cuda/rocm on newer
# builds) — the registry's own platform set, mirrored in the worker. No
# GPU host exists for this repo, so these legs record a structured skip.
if need == "gpu" and platform not in ("gpu", "cuda", "rocm"):
    print(json.dumps({**base, "skipped": "leg requires a gpu device"}))
    sys.exit(0)

from alphafold2_tpu.ops import dispatch

iters = spec.get("iters", 5)
key = jax.random.PRNGKey(0)


def timeit(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    np.asarray(jax.tree_util.tree_leaves(compiled(*args))[0])  # warmup
    t0 = time.perf_counter()
    for _ in range(iters):
        out = compiled(*args)
    jax.tree_util.tree_leaves(out)[0].block_until_ready()
    return (time.perf_counter() - t0) / iters


if op in ("flash_attention", "fused_attention"):
    from alphafold2_tpu.ops.flash import flash_attention

    B, i, j, h, dh = 8, 512, 512, 8, 64
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, i, h, dh))
    k = jax.random.normal(ks[1], (B, j, h, dh))
    v = jax.random.normal(ks[2], (B, j, h, dh))
    resolved = dispatch.resolve(op, request="auto", i=i, j=j, dh=dh)
    assert resolved == arm, (resolved, arm)
    if op == "flash_attention":
        dt = timeit(lambda q, k, v: flash_attention(q, k, v), q, k, v)
    else:
        pair_bias = jax.random.normal(ks[3], (B, h, i, j))
        gate = jax.random.normal(ks[4], (B, i, h, dh))
        dt = timeit(
            lambda q, k, v, pb, g: flash_attention(
                q, k, v, pair_bias=pb, gate=g), q, k, v, pair_bias, gate)
    shape = f"B{B}_i{i}_j{j}_h{h}_dh{dh}"
elif op == "quant_matmul":
    from alphafold2_tpu.ops.quant import quant_matmul, quantize_weight

    m, kk, n = 2048, 512, 512
    x = jax.random.normal(key, (m, kk))
    qw, scale = quantize_weight(
        jax.random.normal(jax.random.PRNGKey(1), (kk, n)))
    resolved = dispatch.resolve(op, request="auto", m=m, k=kk, n=n,
                                x_dtype=x.dtype)
    assert resolved == arm, (resolved, arm)
    dt = timeit(lambda x, qw, s: quant_matmul(x, qw, s), x, qw, scale)
    shape = f"m{m}_k{kk}_n{n}"
elif op == "sparse_attention":
    from alphafold2_tpu.ops.attention import AttentionConfig, attention_init
    from alphafold2_tpu.ops.sparse import SparseConfig, sparse_attention_apply

    n, dim = 1024, 128
    cfg = AttentionConfig(dim=dim, heads=4, dim_head=32)
    scfg = SparseConfig(block_size=16, max_seq_len=2048)
    params = attention_init(key, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, n, dim))
    resolved = dispatch.resolve(op, request="auto", n=n)
    assert resolved == arm, (resolved, arm)
    dt = timeit(
        lambda p, x: sparse_attention_apply(p, cfg, scfg, x), params, x)
    shape = f"n{n}_dim{dim}_bs{scfg.block_size}"
elif op == "merge_lse":
    # one simulated 2-hop ring on plain arrays: exactly the per-hop
    # compute each arm runs inside parallel/sequence.py's fori_loop,
    # without needing a mesh on this host
    from alphafold2_tpu.ops.flash import (
        hop_attention_lse, merge_lse, stream_block)

    BH, n, dh = 16, 512, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (BH, n, dh))
    k1, k2 = jnp.split(jax.random.normal(ks[1], (BH, 2 * n, dh)), 2, axis=1)
    v1, v2 = jnp.split(jax.random.normal(ks[2], (BH, 2 * n, dh)), 2, axis=1)
    bias = jnp.zeros((BH, n), jnp.float32)
    scale = dh ** -0.5
    resolved = dispatch.resolve(op, request="auto", i=n, j=n, dh=dh)
    assert resolved == arm, (resolved, arm)
    if resolved == "pallas_tpu":
        def hops(q, k1, v1, k2, v2, bias):
            out, lse = hop_attention_lse(q, k1, v1, bias, scale)
            out2, lse2 = hop_attention_lse(q, k2, v2, bias, scale)
            return merge_lse(out, lse, out2, lse2)[0]
    else:
        # the stream_block recurrence both XLA-family arms run
        def hops(q, k1, v1, k2, v2, bias):
            q4 = q.reshape(BH, n, 1, dh)
            m0 = jnp.full((BH, 1, n), float("-inf"), jnp.float32)
            l0 = jnp.zeros((BH, 1, n), jnp.float32)
            a0 = jnp.zeros((BH, 1, n, dh), jnp.float32)
            m, l, a = stream_block(q4, k1.reshape(BH, n, 1, dh),
                                   v1.reshape(BH, n, 1, dh), bias,
                                   m0, l0, a0, scale)
            m, l, a = stream_block(q4, k2.reshape(BH, n, 1, dh),
                                   v2.reshape(BH, n, 1, dh), bias,
                                   m, l, a, scale)
            return a / jnp.where(l > 0, l, 1.0)[..., None]
    dt = timeit(hops, q, k1, v1, k2, v2, bias)
    shape = f"BH{BH}_n{n}_dh{dh}_hops2"
else:
    raise ValueError(f"unknown dispatch op {op!r}")

print(json.dumps({**base, "sec_per_iter": round(dt, 5), "shape": shape,
                  "iters": iters}))
"""


# Communication-compute overlap A/B (the multi-chip distribution story,
# ISSUE 5): times the double-buffered vs synchronous schedules of the two
# overlapped paths — ring attention and the backward-overlapped DP-accum
# step — over ALL devices the host exposes. On a one-chip host this
# records a structured skip (a mesh of 1 has no transfers to hide); run it
# on the four-chip host. The schedule is baked at trace time from
# AF2_COMM_OVERLAP, set per-arm below before any tracing.
OVERLAP_WORKER = r"""
import json, sys, time, os
spec = json.loads(sys.argv[1])
os.environ["AF2_COMM_OVERLAP"] = "1" if spec["overlap"] else "0"
import jax
from alphafold2_tpu.compile_cache import enable_compile_cache
enable_compile_cache()
import jax.numpy as jnp
import numpy as np

n_dev = len(jax.devices())
if n_dev < 2:
    print(json.dumps({"skipped": "single-device host: overlap needs a "
                      "multi-chip mesh", "devices": n_dev}))
    sys.exit(0)

from jax.sharding import PartitionSpec as P
from alphafold2_tpu import compat
from alphafold2_tpu.models import Alphafold2Config
from alphafold2_tpu.parallel import (
    make_dp_overlap_train_step, make_mesh, ring_attention,
)
from alphafold2_tpu.training import (
    DataConfig, TrainConfig, distogram_loss_fn, stack_microbatches,
    synthetic_batches,
)
from alphafold2_tpu.training.harness import train_state_init

from alphafold2_tpu.ops import dispatch as _dispatch

iters = spec.get("iters", 10)
out = {"devices": n_dev, "overlap": spec["overlap"],
       "platform": jax.devices()[0].platform,
       # the per-hop arm the ring legs below resolve to (per-shard key
       # length 512) — the cross-backend matrix field
       "backend_arm": _dispatch.resolve("merge_lse", request="auto",
                                        i=512, j=512, dh=64)}

# ring attention: per-shard 512 keys x 8 heads x 64 dh — big enough that
# the per-hop transfer is bandwidth-bound, P-1 hops around the full ring
mesh = make_mesh({"seq": n_dev})
sp = P(None, "seq", None, None)
key = jax.random.PRNGKey(0)
q, k, v = (jax.random.normal(kk, (1, 512 * n_dev, 8, 64), jnp.bfloat16)
           for kk in jax.random.split(key, 3))
ring = jax.jit(compat.shard_map(
    lambda q, k, v: ring_attention(q, k, v, "seq"),
    mesh=mesh, in_specs=(sp, sp, sp), out_specs=sp))
np.asarray(ring(q, k, v))  # compile + warmup
t0 = time.perf_counter()
for _ in range(iters):
    r = ring(q, k, v)
r.block_until_ready()
out["ring_sec"] = round((time.perf_counter() - t0) / iters, 5)

# DP-accum step: small trunk, grad_accum 4 — the psum/backward overlap
cfg = Alphafold2Config(dim=64, depth=2, heads=4, dim_head=16,
                       max_seq_len=64)
tcfg = TrainConfig(learning_rate=1e-3, grad_accum=4)
dcfg = DataConfig(batch_size=n_dev, max_len=48, seed=0)
batch = jax.device_put(
    next(stack_microbatches(synthetic_batches(dcfg), tcfg.grad_accum)))
dp_mesh = make_mesh({"data": n_dev})
state = train_state_init(jax.random.PRNGKey(1), cfg, tcfg)
step, _ = make_dp_overlap_train_step(
    cfg, tcfg, dp_mesh, batch, loss_fn=distogram_loss_fn,
    donate_state=False)
s2, m = step(state, batch)
float(m["loss"])  # compile + warmup fetch
t0 = time.perf_counter()
for _ in range(iters):
    s2, m = step(state, batch)
loss = float(m["loss"])
out["dp_sec"] = round((time.perf_counter() - t0) / iters, 5)
assert np.isfinite(loss), loss
out["loss"] = round(loss, 4)
print(json.dumps(out))
"""


def err_tail(stderr: str, returncode: int) -> str:
    """Diagnostic-bearing error summary of a failed subprocess.

    The last stderr line alone is useless for XLA/jax failures — an OOM's
    final line is a bar of '=' signs (PERF_SWEEP e2e_chunk0, session 5).
    Prefer the last line that names an error; fall back to the last
    non-blank line; always include the tail for context.
    """
    lines = [ln for ln in (stderr or "").splitlines() if ln.strip()]
    if not lines:
        return f"rc={returncode} (no stderr)"
    import re

    marker = None
    for ln in reversed(lines):
        if re.search(r"Error|Exception|RESOURCE_EXHAUSTED|OOM|Aborted|"
                     r"assert|Traceback", ln):
            marker = ln.strip()
            break
    tail = " | ".join(ln.strip() for ln in lines[-3:])
    msg = marker if marker else tail
    if marker and marker not in tail:
        msg = f"{marker} | {tail}"
    return msg[-400:]


def run_sub(code_or_path, argv, timeout):
    t0 = time.time()
    if os.path.exists(code_or_path):
        cmd = [sys.executable, code_or_path, *argv]
    else:
        cmd = [sys.executable, "-c", code_or_path, *argv]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout, cwd=REPO,
        )
    except subprocess.TimeoutExpired:
        return None, "timeout", time.time() - t0
    if proc.returncode != 0:
        return None, err_tail(proc.stderr, proc.returncode), time.time() - t0
    results = []
    for line in proc.stdout.strip().splitlines():
        try:
            results.append(json.loads(line))
        except ValueError:
            continue
    if not results:
        return None, "no JSON in output", time.time() - t0
    return (results if len(results) > 1 else results[0]), None, time.time() - t0


def record(entry):
    with open(OUT, "a") as f:
        f.write(json.dumps(entry) + "\n")
    print(json.dumps(entry), flush=True)


def run_and_record(name, code_or_path, argv, timeout, extra=None):
    """One measurement subprocess, recorded; returns its result (None on
    error). The worker has exited (or been killed at its timeout) when
    this returns, so the chip is free for the next leg."""
    res, err, dt = run_sub(code_or_path, argv, timeout)
    record({"bench": name, **(extra or {}), "result": res, "error": err,
            "wall": round(dt, 1)})
    return res


# the ops/dispatch.py registry, mirrored here so the orchestrator never
# imports jax (a parent that touched JAX would hold the chip its workers
# need). Drift is loud, not silent: each worker asserts
# dispatch.resolve(op, ...) == the leg's pinned arm, so a renamed or
# removed op fails its leg instead of recording misattributed rows.
DISPATCH_OPS = ("flash_attention", "fused_attention", "quant_matmul",
                "sparse_attention", "merge_lse")


def dispatch_matrix_legs():
    """(name, spec) for the op x arm cross-backend matrix: xla_ref runs
    on ANY host; pallas_tpu legs need the chip (an error without it);
    gpu legs record a structured skip."""
    legs = []
    for op in DISPATCH_OPS:
        legs.append((f"disp_{op}_xla_ref", {"op": op, "arm": "xla_ref"}))
        legs.append((f"disp_{op}_pallas_tpu",
                     {"op": op, "arm": "pallas_tpu",
                      "require_platform": "tpu"}))
        legs.append((f"disp_{op}_gpu",
                     {"op": op, "arm": "gpu", "require_platform": "gpu"}))
    return legs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="kernel microbench + one e2e config only")
    ap.add_argument("--depth", type=int, default=12)
    ap.add_argument("--skip-micro", action="store_true",
                    help="e2e knob sweep only")
    ap.add_argument("--dispatch-only", action="store_true",
                    help="run only the cross-backend dispatch matrix "
                         "(op x arm) legs — chip-free xla_ref rows "
                         "record on any host")
    ap.add_argument("--serving-only", action="store_true",
                    help="run only the ISSUE-14 serving legs: the "
                         "chip-free routed-fleet row (records on any "
                         "host) plus the serve_sp_on/off A/B (TPU-only, "
                         "an error elsewhere)")
    ap.add_argument("--xla-micro", action="store_true",
                    help="also run the XLA-streaming micro leg (its "
                         "compile ran >550 s at the chunk shape on an "
                         "earlier shared chip — see PERF.md)")
    ap.add_argument("--force-all", action="store_true",
                    help="re-run legs already recorded in PERF_SWEEP.jsonl")
    args = ap.parse_args()

    failed = []  # legs recorded with an error: the sweep's exit code

    def run_leg(name, *leg_args, **leg_kwargs):
        res = run_and_record(name, *leg_args, **leg_kwargs)
        if res is None:
            failed.append(name)
        return res

    # Legs that already have a successful measurement recorded are skipped
    # by default: chip time is budgeted.
    # keyed by (name, spec): a --quick/--depth smoke record must not
    # suppress the real-configuration measurement of the same leg
    def done_key(name, spec):
        return (name, json.dumps(spec, sort_keys=True) if spec else "")

    def is_skip(res):
        # structured skips (single-device overlap legs, gpu-arm legs) are
        # NOT measurements: counting them as done would silence the leg
        # for ever
        if isinstance(res, dict):
            return "skipped" in res
        if isinstance(res, list):
            return all(isinstance(i, dict) and "skipped" in i for i in res)
        return False

    done = set()
    prior = {}  # done_key -> latest recorded result (for alias legs)
    if not args.force_all and os.path.exists(OUT):
        with open(OUT) as f:
            for line in f:
                try:
                    e = json.loads(line)
                except ValueError:
                    continue
                if e.get("result") is not None and not is_skip(e["result"]):
                    key = done_key(e.get("bench"), e.get("spec"))
                    done.add(key)
                    prior[key] = e["result"]

    # 1d) cross-backend dispatch matrix (ISSUE 13). In --dispatch-only
    # mode it is the whole run; otherwise it runs AFTER the e2e legs
    # (chip minutes go to the big measurements first).
    def run_dispatch_matrix():
        for name, spec in dispatch_matrix_legs():
            if done_key(name, spec) in done:
                print(f"skip {name}: already recorded in {OUT}", flush=True)
                continue
            run_leg(name, DISPATCH_WORKER, [json.dumps(spec)],
                           timeout=900, extra={"spec": spec})

    # 1e) SP serving arm + routed fleet (ISSUE 14): serve_routed is
    # chip-free (real row on any host); the serve_sp A/B times the
    # serving-shaped SP-vs-dense executable on TPU only (an error
    # elsewhere). The on-arm forces sp_seq at
    # the bucket; the off-arm is the dense twin of the SAME bucket.
    def serving_legs():
        return (
            ("serve_routed", {"n": 16}, SERVE_ROUTED_WORKER, 900),
            # ISSUE 15: the cost-ledger row — chip-free, real on any host
            ("serve_costs", {"n": 16}, SERVE_COSTS_WORKER, 900),
            ("serve_sp_on",
             {"depth": args.depth, "bucket": 1024, "sp_shards": 4,
              "sp_on": True, "require_tpu": True}, SERVE_SP_WORKER, 2100),
            ("serve_sp_off",
             {"depth": args.depth, "bucket": 1024, "sp_shards": 4,
              "sp_on": False, "require_tpu": True}, SERVE_SP_WORKER, 2100),
        )

    def run_serving_legs():
        for name, spec, worker, timeout in serving_legs():
            if done_key(name, spec) in done:
                print(f"skip {name}: already recorded in {OUT}", flush=True)
                continue
            run_leg(name, worker, [json.dumps(spec)],
                           timeout=timeout, extra={"spec": spec})

    def finish():
        if failed:
            sys.exit(f"{len(failed)} leg(s) failed: " + ", ".join(failed))

    if args.serving_only:
        run_serving_legs()
        return finish()

    if args.dispatch_only:
        run_dispatch_matrix()
        return finish()

    # 1) e2e step-time sweep FIRST: it is the sweep's purpose. Order is
    # by information value per chip minute:
    #   auto     — exactly the driver-bench configuration (validates the
    #              shape-aware dispatch heuristic on chip);
    #   qbt1152  — whole-row query blocks: the grid-collapse lever that
    #              could flip the short-j kernel verdict (PERF.md);
    #   mdsbwd25/tile26/chunk0 — streaming-path knob legs.
    # the base spec pins ONLY depth + kernel policy: chunk/tile sizes and
    # the MDS arm follow the preset (depth-aware resolver, promoted
    # 25-iter classical MDS), so e2e_auto is exactly the driver-bench
    # configuration by construction
    base = dict(depth=args.depth, kernel="auto")
    variants = [("e2e_auto", base)]
    if not args.quick:
        variants += [
            # FF chunk size: the session-5 sweep left it fixed at 32768 —
            # 40 sequential lax.map+checkpoint blocks per FF pass, and the
            # pair stream runs TWO GEGLU FFs per reversible layer (~30% of
            # layer FLOPs). Bigger blocks = fewer sequential programs;
            # memory headroom exists at depth<=24 (intermediate is
            # chunk*2048*2B, so 262144 -> ~1 GB live per block)
            ("e2e_ff131072", {**base, "ff_chunk": 131072}),
            ("e2e_ff262144", {**base, "ff_chunk": 262144}),
            # whole-row QUERY blocks on the 1152 axes only (pick_block
            # leaves shorter axes unpadded): collapses the (BH, nqb) grid
            # 3x — the per-grid-step-overhead lever (PERF.md finding 3)
            ("e2e_qbt1152", {**base, "kernel": "force", "qb_target": 1152}),
            # heads 4 x dh 128 keeps inner width 512 but fills the
            # 128-lane tile that bf16 dh=64 pads 2x (session-3 finding 1)
            # on EVERY attention q/k/v/out tile — candidate biggest
            # single-chip lever; BASELINE config 5 pins dim/depth, not
            # the head split
            ("e2e_h4dh128", {**base, "heads": 4, "dim_head": 128}),
            # the RETIRED reference MDS arm (200 iterations, random init)
            # measured against the promoted (25, classical) default the
            # base legs now inherit: quantifies on chip what the cut
            # bought, and catches any regression the classical warm
            # start's eigendecomposition might cost at batch-1 latency
            ("e2e_mds200random",
             {**base, "mds_iters": 200, "mds_init": "random"}),
            # bf16 score/probability tiles in the XLA streaming path:
            # halves the attention passes' dominant HBM traffic (the f32
            # logit materialization — PERF.md round-5 traffic budget) at
            # bf16-rounding probability error (tests/test_flash.py). If
            # the traffic theory is right this is a direct ~2x on the
            # ~60%-of-layer pair attention; if it is noise, the sink is
            # elsewhere — decisive either way. PINNED kernel-off
            # (AF2_DISABLE_FLASH_KERNEL): logit_dtype applies only to the
            # streaming path and ops/flash.py raises loudly if any shape
            # reaches the Pallas dispatch — under kernel='auto' a flat
            # cross mode, qb-target tuning, or an AF2_FLASH_AUTO_MIN_J
            # override would turn this A/B into a trace-time ValueError
            # row instead of a measurement (ADVICE r5). The loud error
            # stays for user configs; only the sweep leg pins.
            ("e2e_logit_bf16", {**base, "logit_bf16": True,
                                "kernel": "off"}),
            ("e2e_mdsbwd25", {**base, "mds_bwd_iters": 25}),
            # MDS scan unroll: amortizes the sequential small-kernel
            # iterations' dispatch overhead (PERF.md "MDS latency")
            ("e2e_mdsunroll8", {**base, "mds_unroll": 8}),
            # the OLD chunk/tile values A/B'd against the depth-aware
            # resolver defaults (96 / 2^26 at depth <= 24) the base legs
            # now inherit — the direct on-chip test of the resolver
            # decision (session-5's chunk96 leg measured the reverse
            # direction against the then-32 base)
            ("e2e_tile25", {**base, "tile_elems": 1 << 25}),
            # e2e_chunk0 is RETIRED: measured OOM at compile (session 5,
            # PERF.md) — re-attempting a known-dead config risks a worker
            # crash for zero information
            ("e2e_chunk32", {**base, "batch_chunk": 32}),
            # branch-parallel trunk schedule A/B (ISSUE 7 tentpole): the
            # SAME step with the intra-layer pair/MSA branches expressed
            # as joined concurrent units vs the serial reference —
            # allclose-pinned, so any delta is schedule, not math. TPU
            # legs (require_tpu: an error elsewhere).
            ("branch_parallel_on",
             {**base, "trunk_schedule": "branch_parallel",
              "require_tpu": True}),
            # the off arm's measured configuration IS e2e_auto's (serial
            # is the preset default): the loop below records it as an
            # ALIAS of e2e_auto's TPU measurement instead of paying a
            # second multi-minute compile; it only runs as its own
            # subprocess when no e2e_auto TPU number exists to copy
            ("branch_parallel_off",
             {**base, "trunk_schedule": "serial", "require_tpu": True}),
            # fused-gate A/B: gated attention with the gate fused into
            # the Pallas kernel's finish step (on) vs the SAME kernel
            # core with the gate applied as a separate XLA epilogue
            # multiply (off: AF2_UNFUSE_GATE_EPILOGUE) — identical math,
            # identical core, so the delta isolates the removed HBM
            # out-read/multiply/write pass. (A kernel:"off" arm would
            # also carry the whole kernel-core-vs-XLA-streaming delta,
            # already measured in the session-4 kernel on/off legs.)
            ("fused_gate_on",
             {**base, "attn_gate": True, "kernel": "force",
              "require_tpu": True}),
            ("fused_gate_off",
             {**base, "attn_gate": True, "kernel": "force",
              "unfuse_gate": True, "require_tpu": True}),
        ]
    e2e_results = dict(prior)  # done_key -> result, grown as legs run
    for name, spec in variants:
        key = done_key(name, spec)
        if key in done:
            print(f"skip {name}: already recorded in {OUT}", flush=True)
            continue
        if name == "branch_parallel_off":
            src = e2e_results.get(done_key("e2e_auto", base))
            # platform guard: older rows predate the worker's platform
            # field, and a CPU e2e_auto number must never masquerade as
            # a TPU leg's measurement — those fall through to a real run
            # (which is an error off-TPU)
            if isinstance(src, dict) and src.get("platform") == "tpu":
                record({"bench": name, "spec": spec, "result": src,
                        "alias_of": "e2e_auto", "error": None, "wall": 0.0})
                print(f"{name}: aliased from e2e_auto (serial is the "
                      f"preset default — identical configuration)",
                      flush=True)
                continue
        res = run_leg(name, E2E_WORKER, [json.dumps(spec)],
                             timeout=2100, extra={"spec": spec})
        if res is not None:
            e2e_results[key] = res

    # 1b) communication-overlap A/B pair (multi-chip only; a one-chip
    # host records a structured skip and costs seconds). Both arms run
    # the SAME programs — only AF2_COMM_OVERLAP differs, baked at trace
    # time inside each worker.
    for name, spec in (
        ("overlap_on", {"overlap": True}),
        ("overlap_off", {"overlap": False}),
    ):
        if done_key(name, spec) in done:
            print(f"skip {name}: already recorded in {OUT}", flush=True)
            continue
        run_leg(name, OVERLAP_WORKER, [json.dumps(spec)],
                       timeout=1200, extra={"spec": spec})

    # 1c) int8 weight-quantization legs (ISSUE 8): quant_parity is
    # chip-free (residency + parity + quality deltas record on any
    # host); the quant_int8 on/off A/B times the serving-shaped forward
    # on TPU only (an error elsewhere).
    # featurize_overlap (ISSUE 11) is chip-free like quant_parity: the
    # disaggregated-serving overlap ratio records on any host.
    # train_goodput (ISSUE 12) likewise: the goodput ledger's attribution
    # proof (injected data stall -> data_fetch badput + incident) is
    # structural, not chip-speed-dependent.
    for name, spec, worker, timeout in (
        ("quant_parity", {"depth": args.depth}, QUANT_PARITY_WORKER, 900),
        ("featurize_overlap", {"n": 24, "featurize_delay_s": 0.08},
         FEATURIZE_WORKER, 900),
        ("train_goodput", {"steps": 8, "stall_delay_s": 0.1},
         GOODPUT_WORKER, 900),
        ("quant_int8_on",
         {"depth": args.depth, "weight_dtype": "int8", "require_tpu": True},
         QUANT_WORKER, 2100),
        ("quant_int8_off",
         {"depth": args.depth, "weight_dtype": "f32", "require_tpu": True},
         QUANT_WORKER, 2100),
    ):
        if done_key(name, spec) in done:
            print(f"skip {name}: already recorded in {OUT}", flush=True)
            continue
        run_leg(name, worker, [json.dumps(spec)],
                       timeout=timeout, extra={"spec": spec})

    # 1d) the cross-backend dispatch matrix (see run_dispatch_matrix)
    run_dispatch_matrix()

    # 1e) SP serving + routed fleet (see serving_legs above)
    run_serving_legs()

    # 2) kernel microbench + block-size tuning at the chunk shape the model
    # actually calls (attn_batch_chunk=32 folded rows x 8 heads): the
    # full-fold backward OOMs from dh=64 lane padding and is not a shape
    # the model ever runs. The XLA-streaming comparison leg is OPT-IN
    # (--xla-micro): at this shape its compile ran >550 s on an earlier
    # shared chip (PERF.md).
    micro = os.path.join(REPO, "scripts", "bench_kernels.py")
    micro_runs = []
    if not args.skip_micro:
        micro_runs.append(("micro_kernel", ["--paths", "kernel"]))
        for qb, kb in ((1152, 384), (1152, 1152), (384, 1152)):
            micro_runs.append((
                f"micro_kernel_qb{qb}_kb{kb}",
                ["--paths", "kernel", "--qb", str(qb), "--kb", str(kb)],
            ))
        if args.xla_micro:
            micro_runs.append(("micro_xla", ["--paths", "xla"]))
    for name, extra in micro_runs:
        if done_key(name, None) in done:
            print(f"skip {name}: already recorded in {OUT}", flush=True)
            continue
        run_leg(
            name, micro, ["--b", "32", "--n", "1152", "--iters", "20", *extra],
            timeout=1500,
        )
    finish()


if __name__ == "__main__":
    main()
