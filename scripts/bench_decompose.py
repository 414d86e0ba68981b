"""Where-the-time-goes decomposition of the north-star e2e step.

The knob sweep (scripts/bench_sweep.py, PERF.md session 5) showed the
depth-12 e2e step pinned at ~24.4 s/step no matter which tuning axis
moves (kernel policy, attention batch-chunk, flash tile budget, MDS
backprop truncation/unroll) — so the time is going somewhere those knobs
do not touch. This bench times each pipeline component in isolation, at
the exact north-star shapes and model config bench.py runs:

  trunk_fwd   full model forward (embeddings + reversible trunk + head)
  trunk_vg    model forward + backward (reversible reconstruction)
  geom_vg     geometry tail fwd+bwd from fixed logits: center_distogram
              -> 200-iter MDS -> sidechain lift -> EGNN refiner ->
              weighted Kabsch -> RMSD + dispersion loss
  ops         one REVERSIBLE trunk layer's pieces (8 blocks), each
              fwd+bwd in isolation: pair axial self-attn, MSA axial
              tied-row self-attn, the two aligned cross-attentions, and
              the TWO GEGLU feed-forwards per stream

Identities: e2e step ~= trunk_vg + geom_vg + optimizer, and
trunk_vg/depth >~ sum(ops) — a LOWER bound, since the reversible backward
re-runs each op's forward once more for activation reconstruction
(expect roughly sum(ops) * (1 + fwd/(fwd+bwd))). Mismatches beyond that
localize hidden costs (reversible-layout overheads, XLA fusion
differences between isolated and composed programs).

Each leg runs in its own subprocess, one after another (this parent never
imports JAX, so each worker gets the chip; a crashed worker does not take
the orchestrator down) and appends one JSON line to PERF_DECOMP.jsonl.
Results are fetched to the host before the clock stops (see bench.py
methodology). Legs need a TPU: without one a leg is an error row and the
script exits non-zero (`--smoke` rehearses the worker on a CPU at tiny
shapes, every row marked `"smoke": true`).

Usage: python scripts/bench_decompose.py [--depth 12] [--legs trunk_fwd,...]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))
from bench_sweep import err_tail  # noqa: E402  (shared failure summarizer)

OUT = os.path.join(REPO, "PERF_DECOMP.jsonl")

WORKER = r"""
import json, sys, time
import jax
import jax.numpy as jnp
import numpy as np

from alphafold2_tpu.compile_cache import enable_compile_cache
enable_compile_cache()

spec = json.loads(sys.argv[1])
leg, depth = spec["leg"], spec["depth"]
if not spec.get("smoke") and jax.devices()[0].platform != "tpu":
    # a decomposition timed on another platform says nothing about the
    # chip: an error, not a row
    sys.exit("leg requires a TPU device, JAX found "
             + jax.devices()[0].platform)

from alphafold2_tpu.models.trunk import (
    cross_apply_grids, prenorm_axial_apply, prenorm_ff_apply,
    trunk_layer_init,
)
from alphafold2_tpu.training import (
    DataConfig, TrainConfig, e2e_train_state_init, north_star_e2e_config,
    stack_microbatches, synthetic_structure_batches,
)
from alphafold2_tpu.training.e2e import elongate, make_e2e_loss_fn
from alphafold2_tpu.models import alphafold2_apply

smoke = spec.get("smoke", False)
# ONE source for the north-star config (training/presets.py): the
# decomposition must time the exact program bench.py's 24.4 s/step runs
ecfg, crop, msa_rows = north_star_e2e_config(depth, smoke=smoke)
cfg = ecfg.model
dim, dt_model = cfg.dim, cfg.dtype
tcfg = TrainConfig(learning_rate=3e-4, grad_accum=1)
dcfg = DataConfig(batch_size=1, max_len=crop, msa_rows=msa_rows, seed=0)
key = jax.random.PRNGKey(0)


def timed(compiled, *args):
    out = compiled(*args)  # warmup (compile happened in .compile())
    jax.tree_util.tree_map(np.asarray, out)
    t0 = time.perf_counter()
    out = compiled(*args)
    jax.tree_util.tree_map(np.asarray, out)  # fetch: dispatch-proof
    return time.perf_counter() - t0


def compiled_tflop(compiled):
    # TFLOPs per XLA cost analysis (0 if opaque). CAUTION: counts
    # scan/map bodies ONCE, so on the reversible/streamed trunk it is
    # ~100x low (utils/flops.py docstring) -- kept for reference only;
    # tf_per_s uses the analytic model count when one is supplied.
    # (comment, not docstring: this code lives inside the WORKER
    # triple-quoted string, which a nested triple-quote would terminate)
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return float(ca.get("flops", 0.0)) / 1e12
    except Exception:
        return 0.0


def perf_fields(compiled, dt, model_tflop=None):
    # model_tflop: analytic matmul count (utils/flops.py), the honest
    # numerator for roofline-relative TF/s on scanned programs
    tf = compiled_tflop(compiled)
    out = {"sec": round(dt, 3)}
    if tf:
        out["tflop_xla"] = round(tf, 3)
    if model_tflop:
        out["tflop_model"] = round(model_tflop, 3)
        out["tf_per_s"] = round(model_tflop / dt, 1)
    elif tf:
        out["tf_per_s"] = round(tf / dt, 1)
    return out


def report(**kv):
    if smoke:
        kv["smoke"] = True  # CPU validation rows must not read as chip data
    # flush per row: the orchestrator salvages completed rows from a leg
    # that later crashes or times out, and a block-buffered pipe would
    # hold them hostage
    print(json.dumps(kv), flush=True)


if leg == "fetch_bw":
    # device->host bandwidth + latency: converts the
    # (fetch-heavy leg) - (scalarized leg) deltas into MB/s, and sizes
    # how much any grad-fetching measurement overstates compute.
    # Runs BEFORE any model-batch setup. jax.Array caches
    # its host copy after the first np.asarray, so each probe times the
    # FIRST fetch of a fresh array; a small throwaway fetch warms the
    # transfer path beforehand.
    jnp.ones((1024,), jnp.bfloat16).block_until_ready()
    np.asarray(jnp.zeros((1024,), jnp.bfloat16))  # warm the D2H path
    for name, elems in (("lat_4B", 2), ("bw_64MB", 32 << 20),
                        ("bw_256MB", 128 << 20)):
        x = jnp.ones((elems,), jnp.bfloat16)
        x.block_until_ready()  # timed section must be transfer-only
        t0 = time.perf_counter()
        np.asarray(x)
        dt = time.perf_counter() - t0
        mb = elems * 2 / 1e6
        report(leg=f"fetch_{name}", depth=depth, sec=round(dt, 6),
               mb=round(mb, 1),
               mb_per_s=round(mb / dt, 1) if dt > 1e-6 else None)
    raise SystemExit(0)


batch = jax.device_put(
    jax.tree_util.tree_map(
        lambda t: t[0],
        next(stack_microbatches(synthetic_structure_batches(dcfg), 1)),
    )
)
n3 = crop * 3
seq3 = elongate(batch["seq"])
mask3 = elongate(batch["mask"])


def sq_total(tree):
    # on-device scalar that depends on every leaf: fetching it waits for
    # the device WITHOUT paying the transfer of the full tree
    # (the fetch-heavy legs measured compute + hundreds of MB of
    # device->host transfer in one number; see the *_s legs' rationale)
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)


# Scalarized twins (trunk_vg_s / geom_vg_s / ops_s) share the fetch-heavy
# legs' bodies below: same traced program plus an on-device grad reduction,
# so the twins can never drift apart. The _s numbers are the component
# compute cost; the fetch-heavy twins are the transfer-inclusive record.
scalarized = leg.endswith("_s")
base_leg = leg[:-2] if scalarized else leg
leg_suffix = "_s" if scalarized else ""


def scalarize(vg):
    def scalar_vg(*a):
        v, g = vg(*a)
        return v, sq_total(g)

    return scalar_vg


def maybe_scalarize(vg):
    return scalarize(vg) if scalarized else vg


if base_leg in ("trunk_fwd", "trunk_vg"):
    state = e2e_train_state_init(key, ecfg, tcfg)
    params = state["params"]["model"]

    def fwd(p):
        logits = alphafold2_apply(
            p, cfg, seq3, batch["msa"], mask=mask3,
            msa_mask=batch["msa_mask"], rng=None,
        )
        # scalar pull so the backward has a cotangent; f32 to match e2e
        return jnp.mean(jnp.square(logits.astype(jnp.float32)))

    fn = (fwd if base_leg == "trunk_fwd"
          else maybe_scalarize(jax.value_and_grad(fwd)))
    compiled = jax.jit(fn).lower(params).compile()
    dt = timed(compiled, params)
    from alphafold2_tpu.utils.flops import model_fwd_flops, train_step_flops
    mt = (model_fwd_flops(cfg, n3, msa_rows, crop) if base_leg == "trunk_fwd"
          else train_step_flops(cfg, n3, msa_rows, crop)) / 1e12
    report(leg=leg, depth=depth, **perf_fields(compiled, dt, model_tflop=mt))

elif base_leg == "geom_vg":
    state = e2e_train_state_init(key, ecfg, tcfg)
    # fixed logits standing in for the trunk output; differentiate the
    # geometry tail wrt logits AND refiner params (what training does)
    logits = jax.random.normal(
        jax.random.PRNGKey(1), (1, n3, n3, cfg.num_buckets), jnp.float32
    )
    mb = dict(batch)

    def tail_loss(lg, refiner_params):
        # the real e2e loss with a stub model-apply returning the fixed
        # logits: everything downstream of the trunk, nothing of it
        lf = make_e2e_loss_fn(model_apply_fn=lambda p, c, s, msa, **kw: lg)
        params = {"model": {}, "refiner": refiner_params}
        return lf(params, ecfg, mb, key)

    fn = maybe_scalarize(jax.value_and_grad(tail_loss, argnums=(0, 1)))
    compiled = jax.jit(fn).lower(logits, state["params"]["refiner"]).compile()
    dt = timed(compiled, logits, state["params"]["refiner"])
    report(leg=leg, depth=depth, **perf_fields(compiled, dt))

elif base_leg == "ops":
    # one REVERSIBLE trunk layer's pieces, each fwd+bwd in isolation at
    # model shapes — 8 blocks: reversible layers carry TWO feed-forwards
    # per stream (models/trunk.py trunk_layer_init; an identity over only
    # 6 blocks would undercount every layer by 2 GEGLU passes)
    layer = trunk_layer_init(key, cfg, reversible=True)
    self_cfg = cfg.self_attn_config()
    x = jax.random.normal(jax.random.PRNGKey(2), (1, n3, n3, dim), dt_model)
    # the MSA stream keeps its own column count (crop, NOT the 3x-elongated
    # pair length): alphafold2_apply embeds msa at msa.shape[2] columns and
    # the aligned cross mode folds 3 pair columns per MSA column
    m = jax.random.normal(jax.random.PRNGKey(3), (1, msa_rows, crop, dim),
                          dt_model)
    x_mask = jnp.broadcast_to(mask3[:, :, None] & mask3[:, None, :],
                              (1, n3, n3))
    msa_mask = batch["msa_mask"]

    # per-op analytic matmul counts, from the SAME source as the layer
    # total (utils/flops.py trunk_layer_op_flops) so the per-op table
    # always sums to trunk_layer_flops: each row's TF/s is
    # roofline-relative, localizing not just WHERE the time goes but
    # which op is furthest off peak. The benched ops split each
    # ff entry (seq_ff/seq_ff2 share one dict key covering both).
    from alphafold2_tpu.utils.flops import trunk_layer_op_flops
    layer_ops = trunk_layer_op_flops(cfg, n3, msa_rows, crop)
    n_ffs = 2 if cfg.reversible else 1  # dict ff entries cover all passes
    op_fwd_tf = {
        "pair_axial": layer_ops["pair_axial"] / 1e12,
        "msa_axial_tied": layer_ops["msa_axial"] / 1e12,
        "cross_pair_from_msa": layer_ops["cross_pair_from_msa"] / 1e12,
        "cross_msa_from_pair": layer_ops["cross_msa_from_pair"] / 1e12,
        "ff_pair": layer_ops["ff_pair"] / n_ffs / 1e12,
        "ff_pair2": layer_ops["ff_pair"] / n_ffs / 1e12,
        "ff_msa": layer_ops["ff_msa"] / n_ffs / 1e12,
        "ff_msa2": layer_ops["ff_msa"] / n_ffs / 1e12,
    }

    def bench_op(name, f, *args):
        def loss(*a):
            return jnp.mean(jnp.square(f(*a).astype(jnp.float32)))
        vg = maybe_scalarize(
            jax.value_and_grad(loss, argnums=tuple(range(len(args)))))
        compiled = jax.jit(vg).lower(*args).compile()
        dt = timed(compiled, *args)
        # vg multiplier: attention ops remat their tiles (fwd +
        # recompute + bwd = 4x fwd); the FFs are chunked, not remat'd (3x)
        vg_mult = 4.0 if "ff" not in name else 3.0
        mt = vg_mult * op_fwd_tf[name] if name in op_fwd_tf else None
        report(leg=f"op{leg_suffix}_{name}", depth=depth,
               **perf_fields(compiled, dt, model_tflop=mt))

    bench_op(
        "pair_axial",
        lambda p, t: prenorm_axial_apply(p, self_cfg, t, mask=x_mask),
        layer["seq_attn"], x,
    )
    bench_op(
        "msa_axial_tied",
        lambda p, t: prenorm_axial_apply(
            p, self_cfg, t, mask=msa_mask, tie_row=cfg.msa_tie_row_attn
        ),
        layer["msa_attn"], m,
    )
    bench_op(
        "cross_pair_from_msa",
        lambda p, a, b_: cross_apply_grids(
            p, cfg, a, b_, x_mask, msa_mask, None, "pair_from_msa"
        ),
        layer["seq_cross"], x, m,
    )
    bench_op(
        "cross_msa_from_pair",
        lambda p, a, b_: cross_apply_grids(
            p, cfg, a, b_, msa_mask, x_mask, None, "msa_from_pair"
        ),
        layer["msa_cross"], m, x,
    )
    bench_op(
        "ff_pair",
        lambda p, t: prenorm_ff_apply(p, cfg, t),
        layer["seq_ff"], x,
    )
    bench_op(
        "ff_pair2",
        lambda p, t: prenorm_ff_apply(p, cfg, t),
        layer["seq_ff2"], x,
    )
    bench_op(
        "ff_msa",
        lambda p, t: prenorm_ff_apply(p, cfg, t),
        layer["msa_ff"], m,
    )
    bench_op(
        "ff_msa2",
        lambda p, t: prenorm_ff_apply(p, cfg, t),
        layer["msa_ff2"], m,
    )

elif leg == "ops_detail":
    # sub-op isolation: answers the follow-up questions the ops leg will
    # raise, in the same chip window. All fwd+bwd, model shapes.
    import dataclasses

    layer = trunk_layer_init(key, cfg, reversible=True)
    self_cfg = cfg.self_attn_config()
    x = jax.random.normal(jax.random.PRNGKey(2), (1, n3, n3, dim), dt_model)
    x_mask = jnp.broadcast_to(mask3[:, :, None] & mask3[:, None, :],
                              (1, n3, n3))

    def bench_fn(name, f, *args):
        def loss(*a):
            return jnp.mean(jnp.square(f(*a).astype(jnp.float32)))
        # grads reduced on device (scalarize): a (1,1152,1152,256) bf16 arg
        # grad is ~680 MB — fetching it would swamp the measurement
        vg = scalarize(
            jax.value_and_grad(loss, argnums=tuple(range(len(args)))))
        compiled = jax.jit(vg).lower(*args).compile()
        dt = timed(compiled, *args)
        report(leg=f"detail_{name}", depth=depth, **perf_fields(compiled, dt))

    # FF chunk-size ladder on the pair stream: isolates the 40-sequential-
    # blocks serialization question without a 4-minute e2e leg per point
    for chunk in (32768, 131072, 262144, 0):
        ccfg = dataclasses.replace(cfg, ff_chunk_size=chunk)
        bench_fn(
            f"ff_pair_chunk{chunk}",
            lambda p, t, c=ccfg: prenorm_ff_apply(p, c, t),
            layer["seq_ff"], x,
        )

    # axial passes separately: column (w folded into batch) vs row — the
    # two halves of op_pair_axial (prenorm_axial_init: {"norm", "attn":
    # {"attn_width", "attn_height"}}), to see whether one dominates
    from alphafold2_tpu.ops.attention import attention_apply

    axial_params = layer["seq_attn"]["attn"]
    bench_fn(
        "pair_attn_colpass",
        lambda p, t: attention_apply(
            p, self_cfg,
            jnp.swapaxes(t, 1, 2).reshape(-1, t.shape[1], t.shape[-1]),
        ),
        axial_params["attn_width"], x,
    )
    bench_fn(
        "pair_attn_rowpass",
        lambda p, t: attention_apply(
            p, self_cfg,
            t.reshape(-1, t.shape[2], t.shape[-1]),
        ),
        axial_params["attn_height"], x,
    )
else:
    raise SystemExit(f"unknown leg {leg!r}")
"""


def run_leg(leg, depth, timeout, smoke=False):
    spec = {"leg": leg, "depth": depth, "smoke": smoke}
    # error rows carry the smoke flag too, so a failed CPU rehearsal is
    # never read as a failed chip attempt
    smoke_kv = {"smoke": True} if smoke else {}
    env = dict(os.environ)
    if smoke:  # the rehearsal is a CPU run by definition
        env["JAX_PLATFORMS"] = "cpu"

    def parse_rows(stdout):
        rows = []
        for line in (stdout or "").strip().splitlines():
            try:
                rows.append(json.loads(line))
            except ValueError:
                continue
        return rows

    t0 = time.time()
    try:
        proc = subprocess.run(
            [sys.executable, "-c", WORKER, json.dumps(spec)],
            capture_output=True, text=True, timeout=timeout, cwd=REPO,
            env=env,
        )
    except subprocess.TimeoutExpired as e:
        # salvage rows the worker already printed (it flushes per row):
        # chip time spent on completed measurements must reach the record
        out = e.stdout.decode() if isinstance(e.stdout, bytes) else e.stdout
        return (parse_rows(out) + [{"leg": leg, "depth": depth,
                                    "error": "timeout", **smoke_kv}],
                time.time() - t0)
    if proc.returncode != 0:
        return (
            parse_rows(proc.stdout)
            + [{"leg": leg, "depth": depth,
                "error": err_tail(proc.stderr, proc.returncode),
                **smoke_kv}],
            time.time() - t0,
        )
    rows = parse_rows(proc.stdout)
    return (rows or [{"leg": leg, "depth": depth, "error": "no JSON",
                      **smoke_kv}]), time.time() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--depth", type=int, default=12)
    ap.add_argument("--legs",
                    # scalarized legs by default: the fetch-heavy trunk_vg
                    # measured compute + ~35 s of gradient-tree transfer in
                    # one number (49.7 s vs the 24.4 s e2e step that
                    # CONTAINS the trunk; an earlier shared chip).
                    # trunk_vg/geom_vg/ops remain available explicitly as
                    # transfer-inclusive twins. Order = information value
                    # per chip minute: fetch_bw (prices device->host
                    # transfer), ops_s (the decisive per-op split of the
                    # 378 ms/layer forward), then the rest.
                    default="trunk_fwd,fetch_bw,ops_s,ops_detail,"
                            "trunk_vg_s,geom_vg_s")
    ap.add_argument("--timeout", type=int, default=1800)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU shapes: validates the worker end-to-end "
                         "without a chip (numbers are meaningless)")
    ap.add_argument("--force-all", action="store_true",
                    help="re-run legs already recorded in PERF_DECOMP.jsonl")
    args = ap.parse_args()

    # Legs with a successful non-smoke record are skipped by default (chip
    # time is budgeted). The ops leg emits op_* rows as it goes (partial
    # rows are salvaged from failed runs), so its done-marker is the LAST
    # row — a partially-measured ops leg re-runs until every op lands.
    marker = {"ops": "op_ff_msa2",
              "ops_s": "op_s_ff_msa2",
              "ops_detail": "detail_pair_attn_rowpass",
              "fetch_bw": "fetch_bw_256MB"}
    done = set()
    if not args.force_all and os.path.exists(OUT):
        with open(OUT) as f:
            for line in f:
                try:
                    e = json.loads(line)
                except ValueError:
                    continue
                if "error" not in e and not e.get("smoke"):
                    done.add((e.get("leg"), e.get("depth")))

    failed = []
    for leg in args.legs.split(","):
        leg = leg.strip()
        if not args.smoke and (marker.get(leg, leg), args.depth) in done:
            print(f"skip {leg}: already recorded in {OUT}", flush=True)
            continue
        rows, wall = run_leg(leg, args.depth, args.timeout, smoke=args.smoke)
        with open(OUT, "a") as f:
            for row in rows:
                row["wall"] = round(wall, 1)
                f.write(json.dumps(row) + "\n")
                print(json.dumps(row), flush=True)
        if any("error" in r for r in rows):
            failed.append(leg)
    if failed:
        sys.exit(f"{len(failed)} leg(s) failed: " + ", ".join(failed))


if __name__ == "__main__":
    main()
