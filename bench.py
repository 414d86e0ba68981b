"""Benchmark: the NORTH-STAR workload — end-to-end structure training
(trunk -> distogram -> MDS -> sidechain lift -> SE(3) refiner -> Kabsch
RMSD loss) at crop=384, MSA=128, bf16, reversible trunk, on one TPU chip —
plus inference sec/protein (BASELINE.md operational target).

One process, one chip, one depth per invocation. A TPU is required: with
any other platform the script exits non-zero and prints no metric line, and
a `device_kind` missing from the peaks table is an error, never a default.
Run it through the chip tool after `python chip_smoke.py` has passed.

Prints one JSON line {"metric", "value", "unit", "vs_baseline", ...extras,
"platform", "device_kind", "device_count"}. That line (and a driver's
BENCH_*.json wrapper of it) is the input format of the perf-regression gate
— `python -m alphafold2_tpu.telemetry.check --current <new> --baseline
<BENCH_rNN>` exits nonzero when a hot-path metric regressed beyond
tolerance (docs/OBSERVABILITY.md).
The reference publishes no numbers (BASELINE.md), so vs_baseline is against
the driver-defined operational target of 1.0 optimizer step/sec/chip.
Extras: achieved TFLOP/s and MFU (analytic model-FLOP count from
utils/flops.py over the chip's peak — XLA cost analysis counts scan
bodies once and underreports the reversible/streamed trunk ~100x), and
inference sec/protein for the predict flow.

Methodology: STEPS optimizer steps run INSIDE one jitted `lax.scan`, and the
per-step losses are fetched to the host before the clock stops. JAX
dispatches asynchronously, so the clock must end on a fetch (or
`block_until_ready`); one execution for all steps pays the host's dispatch
once. The first call compiles and warms up and is not timed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np

# bf16 peak FLOP/s by `device_kind` substring (Google Cloud TPU
# documentation, per-chip figures). A kind not listed here is an error.
_PEAK_FLOPS = (
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6", 918e12),
    ("v4", 275e12),
    ("v3", 123e12),
)

# optimizer steps per timed execution
STEPS = 2


def _peak_flops(device) -> float:
    kind = device.device_kind.lower()
    for key, peak in _PEAK_FLOPS:
        if key in kind:
            return peak
    raise ValueError(
        f"no bf16 peak recorded for device_kind {device.device_kind!r}; "
        f"add it to bench._PEAK_FLOPS with its source"
    )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--single-depth", type=int, default=24,
                    help="trunk depth of this run (config 5 is depth 48; "
                         "24 is the deepest monolithic step with a chip "
                         "record)")
    ap.add_argument("--segments", type=int, default=0,
                    help="run the train step as this many reversible trunk "
                         "segments in SEPARATE device executions "
                         "(training/segmented.py); 0 = one jitted step")
    args = ap.parse_args()

    from alphafold2_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"bench: needs a TPU; JAX found platform {dev.platform!r} "
              f"({dev.device_kind}). No metric is printed for a run "
              f"without the chip.", file=sys.stderr)
        return 2
    _peak_flops(dev)  # an unknown chip fails before the long run, not after

    from alphafold2_tpu.ops.core import pallas_interpret

    if pallas_interpret():
        print("bench: pallas_interpret() is True on a TPU (is "
              "AF2_PALLAS_INTERPRET inherited?); an interpreted kernel is "
              "not a kernel measurement", file=sys.stderr)
        return 2

    print(json.dumps(_run(devices, args.single_depth,
                          segments=args.segments)))
    return 0


def _run(devices, depth: int, segments: int = 0) -> dict:
    from alphafold2_tpu.training import (
        DataConfig,
        TrainConfig,
        e2e_loss_fn,
        e2e_train_state_init,
        make_train_step,
        north_star_e2e_config,
        predict_structure,
        stack_microbatches,
        synthetic_structure_batches,
    )

    dev = devices[0]
    steps = STEPS
    ecfg, crop, msa_rows = north_star_e2e_config(depth)
    tcfg = TrainConfig(learning_rate=3e-4, grad_accum=1)
    dcfg = DataConfig(batch_size=1, max_len=crop, msa_rows=msa_rows, seed=0)

    batch = jax.device_put(
        next(stack_microbatches(synthetic_structure_batches(dcfg), 1))
    )
    state = e2e_train_state_init(jax.random.PRNGKey(0), ecfg, tcfg)

    if segments:
        # multi-execution step (training/segmented.py): same optimizer
        # step as a chain of shorter executions. grad_norm depends on
        # every segment's gradients, so fetching it forces the whole chain.
        from alphafold2_tpu.training import make_segmented_train_step

        seg_step = make_segmented_train_step(ecfg, tcfg, segments)
        state, metrics = seg_step(state, batch, jax.random.PRNGKey(1))
        np.asarray(metrics["grad_norm"])  # warmup: compiles + runs chain
        t0 = time.perf_counter()
        state, metrics = seg_step(state, batch, jax.random.PRNGKey(2))
        loss = float(np.asarray(metrics["loss"]))
        float(np.asarray(metrics["grad_norm"]))
        dt = time.perf_counter() - t0
        if not np.isfinite(loss):
            raise RuntimeError(f"non-finite bench loss: {loss}")
        steps, steps_per_sec = 1, 1.0 / dt
    else:
        step = make_train_step(ecfg, tcfg, loss_fn=e2e_loss_fn)

        def run_steps(state, batch, rng):
            def body(s, k):
                s2, metrics = step(s, batch, k)
                return s2, metrics["loss"]

            return jax.lax.scan(body, state, jax.random.split(rng, steps))

        # donate the state: without donation the input AND output copies of
        # (params + Adam state) are both live — ~8 GB at depth 48 — and the
        # north-star program does not fit; the warmup's output state feeds
        # the timed run
        compiled = (
            jax.jit(run_steps, donate_argnums=(0,))
            .lower(state, batch, jax.random.PRNGKey(1))
            .compile()
        )
        state, losses = compiled(state, batch, jax.random.PRNGKey(1))
        np.asarray(losses)  # warmup, fetched

        t0 = time.perf_counter()
        state, losses = compiled(state, batch, jax.random.PRNGKey(2))
        losses = np.asarray(losses)  # waits for the device
        dt = time.perf_counter() - t0
        if not np.isfinite(losses).all():
            raise RuntimeError(f"non-finite bench losses: {losses}")

        steps_per_sec = steps / dt

    # analytic model-FLOP count, shared by both branches (utils/flops.py):
    # XLA cost analysis counts scan bodies once — on the reversible/
    # streamed trunk it underreports ~100x and every MFU derived from it
    # is garbage — and never could aggregate the segmented chain at all
    from alphafold2_tpu.utils.flops import train_step_flops

    flops_per_step = train_step_flops(
        ecfg.model, 3 * crop, msa_rows, crop, grad_accum=tcfg.grad_accum,
    )
    achieved = flops_per_step * steps_per_sec
    mfu = achieved / _peak_flops(dev)

    # inference sec/protein: the predict flow (forward -> distogram -> MDS ->
    # sidechain -> refiner), BASELINE.md's second target metric
    infer = jax.jit(
        lambda p, s, m, mm, msk: predict_structure(
            p, ecfg, s, mask=msk, msa=m, msa_mask=mm
        )["refined"]
    )
    mb = jax.tree_util.tree_map(lambda t: t[0], batch)  # drop microbatch axis
    args = (state["params"], mb["seq"], mb["msa"], mb["msa_mask"], mb["mask"])
    np.asarray(infer(*args))  # compile + warmup
    t0 = time.perf_counter()
    np.asarray(infer(*args))
    infer_sec = time.perf_counter() - t0

    baseline = 1.0  # driver target: >=1 optimizer step/sec/chip (BASELINE.md)
    return {
        "metric": f"train_end2end_steps_per_sec_crop{crop}_msa{msa_rows}"
                  f"_depth{depth}_{dev.platform}"
                  + (f"_seg{segments}" if segments else ""),
        **({"segments": segments} if segments else {}),
        "value": round(steps_per_sec, 4),
        "unit": "steps/sec",
        "vs_baseline": round(steps_per_sec / baseline, 4),
        "sec_per_step": round(dt / steps, 3),
        "tflops_per_step": round(flops_per_step / 1e12, 2),
        "achieved_tflops_per_sec": round(achieved / 1e12, 2),
        "mfu": round(mfu, 4),
        "inference_sec_per_protein": round(infer_sec, 3),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(devices),
    }


if __name__ == "__main__":
    sys.exit(main())
