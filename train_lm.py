"""Language-model pretraining entry point: the decoder (models/decoder.py)
on the shared harness. It has THREE families, and a configuration file's
`model_type` chooses: `deepseek_v3` (latent attention, a mixture of
experts with shared experts; the default, and the toy), `zaya`
(compressed convolutional attention with grouped keys, a top-1 mixture
picked by an MLP router that carries state from layer to layer, a scaled
residual stream, a tied head) and `mellum` (grouped-query attention in
sliding-window and full causal layers mixed by `layer_types`, YaRN on the
full ones, a softmax top-k mixture of narrow experts with no shared
expert, an untied head).

One jitted, donated optimizer step (`make_train_step` with `lm_loss_fn`
and `lm_aux_update`: the same builder `train_pre.py` and
`train_end2end.py` use), checkpointing, the goodput ledger, the trainer
ops plane and `--profile-dir`, as the other trainers; nothing of its own.
Data: packed sequences of Zipf-distributed token ids from the seed
(`zipf_token_batches`), a pure function of the step index, so a resumed
run continues the stream in O(1).

The defaults are a toy; `--config benchmarks/configs/<name>.json` reads a
published `config.json`'s keys (with `experts_held` and the vocabulary
slice for one chip's share of an expert-parallel deployment).

Usage: python train_lm.py [--steps N] [--config FILE] [--batch 2] [--len 8192]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax

from alphafold2_tpu.models.decoder import FAMILIES, DecoderConfig
from alphafold2_tpu.telemetry import (
    MetricRegistry,
    add_observability_args,
    add_telemetry_args,
    build_train_telemetry,
    compile_record,
    finish_trace,
    observability_enabled,
    tracer_from_args,
)
from alphafold2_tpu.training import (
    add_train_args,
    finish,
    lm_aux_update,
    lm_loss_fn,
    lm_train_state_init,
    make_train_step,
    open_or_init,
    stack_microbatches,
    tcfg_from_args,
    zipf_token_batches,
)
from alphafold2_tpu.utils import MetricsLogger

_TOY = dict(
    vocab_size=512, hidden_size=128, num_hidden_layers=3,
    num_attention_heads=4, qk_nope_head_dim=32, qk_rope_head_dim=16,
    v_head_dim=32, kv_lora_rank=64, intermediate_size=256,
    moe_intermediate_size=64, n_routed_experts=8, num_experts_per_tok=2,
    n_shared_experts=1, routed_scaling_factor=2.5)


def config_from_file(path: str, dtype: str):
    """A DecoderConfig, a ZayaConfig or a MellumConfig, by the file's
    `model_type` (`deepseek_v3` where it has none), from the published
    config.json's keys (and, under `assumed_values`, what config.json does
    not give). Where the file states `experts_held`, its count of experts
    is of those held and `published` has the router's width; where it
    states `layers`, that is the depth to build and `num_hidden_layers` is
    the source's (benchmarks/configs/). `zaya`'s `rope_theta` is its
    `rope_parameters`' for the `hybrid` layers; `mellum` takes
    `rope_parameters` whole and the first `layers` of `layer_types`."""
    with open(path) as f:
        raw = json.load(f)
    model_type = raw.get("model_type", "deepseek_v3")
    if model_type not in FAMILIES:
        raise SystemExit(f"{path}: model_type {model_type!r} is not one of "
                         f"{sorted(FAMILIES)} (models/decoder.py)")
    cls = FAMILIES[model_type]
    fields = {f.name for f in dataclasses.fields(cls)}
    sizes = {k: v for k, v in {**raw, **raw.get("assumed_values", {})}.items()
             if k in fields and k != "dtype"}
    if "experts_held" in raw:
        sizes["experts_held"] = tuple(raw["experts_held"])
        sizes[cls.router_width_key] = raw["published"][cls.router_width_key]
    if "layers" in raw:
        sizes["num_hidden_layers"] = raw["layers"]
    if "rope_parameters" in raw and "rope_theta" in fields:
        sizes["rope_theta"] = float(raw["rope_parameters"]["hybrid"]["rope_theta"])
    if "layer_types" in fields:
        sizes["layer_types"] = raw["layer_types"][:sizes["num_hidden_layers"]]
    return cls(dtype=dtype, **sizes)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--config", default=None,
                    help="JSON file of the published config.json's keys; its "
                    "model_type picks the family (deepseek_v3, zaya or mellum)")
    ap.add_argument("--batch", type=int, default=2, help="sequences a microbatch")
    ap.add_argument("--len", dest="length", type=int, default=256)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--zipf", type=float, default=1.0,
                    help="exponent of the token law p(rank) ~ rank^-s")
    add_train_args(ap)
    ap.add_argument("--bf16", action="store_true", help="bfloat16 compute")
    ap.add_argument("--ckpt-dir", default=None, help="checkpoint/resume directory")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-verify", action="store_true")
    add_telemetry_args(ap)   # --trace-out / --trace-max-spans
    add_observability_args(ap)  # --ops-port / --flight-dir / --federate-every
    ap.add_argument("--metrics-log", default=None, help="JSONL metrics file")
    ap.add_argument("--profile-dir", default=None, help="jax.profiler trace dir")
    ap.add_argument("--profile-steps", type=int, default=3)
    args = ap.parse_args()

    from alphafold2_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()

    dtype = "bfloat16" if args.bf16 else "float32"
    cfg = (config_from_file(args.config, dtype) if args.config
           else DecoderConfig(dtype=dtype, **_TOY))
    tcfg = tcfg_from_args(args, grad_accum=args.accum)
    mgr, state, resumed = open_or_init(
        args.ckpt_dir, lm_train_state_init, jax.random.PRNGKey(args.seed), cfg,
        tcfg, save_every=args.ckpt_every, verify=args.ckpt_verify)
    start = int(state["step"])
    batches = stack_microbatches(
        zipf_token_batches(cfg.vocab_size, args.batch, args.length, args.seed,
                           start_index=start * tcfg.grad_accum,
                           exponent=args.zipf), tcfg.grad_accum)

    logger = MetricsLogger(args.metrics_log)
    tracer = tracer_from_args(args)
    registry = MetricRegistry(enabled=tracer.enabled or observability_enabled(args))
    from alphafold2_tpu.utils.flops import decoder_required_train_flops

    telemetry = build_train_telemetry(
        args, registry=registry, tracer=tracer, logger=logger,
        step_flops=tcfg.grad_accum * decoder_required_train_flops(
            cfg, args.batch, args.length))
    train_step = jax.jit(
        make_train_step(cfg, tcfg, loss_fn=lm_loss_fn,
                        aux_update=lm_aux_update(cfg)),
        donate_argnums=(0,))

    base_rng = jax.random.fold_in(jax.random.PRNGKey(args.seed), 1)
    if resumed:
        print(f"resumed from step {start} in {args.ckpt_dir}")
    prof_beg = start + 1 if args.steps > 1 else start
    prof_end = prof_beg + max(1, args.profile_steps)
    profiling = False
    t0 = time.time()
    try:
        for step in range(start, start + args.steps):
            if args.profile_dir and step == prof_beg and not profiling:
                jax.profiler.start_trace(args.profile_dir)
                profiling = True
            with tracer.span("train.fetch", cat="train", step=step), \
                    telemetry.account("data_fetch"):
                batch = next(batches)
            step_bucket = telemetry.step_bucket()
            with tracer.span("train.step", cat="train", step=step), \
                    telemetry.account(step_bucket):
                state, metrics = train_step(
                    state, batch, jax.random.fold_in(base_rng, step))
            # the router's load is per MoE layer: one scalar a layer for
            # the log and the registry
            by_layer = {k: metrics.pop(k) for k in list(metrics)
                        if k.startswith("moe_")}
            # the fetch is the step's device sync
            with tracer.span("train.metrics_fetch", cat="train", step=step), \
                    telemetry.account(step_bucket):
                for name, values in jax.device_get(by_layer).items():
                    for layer, value in enumerate(values):
                        metrics[f"{name}_l{layer}"] = float(value)
                        registry.gauge(
                            f"train_{name}", help="router load of a MoE layer "
                            "(training/lm.py lm_aux_update)",
                            layer=str(layer)).set(float(value))
                logger.log(step, metrics)
            if step == start:
                from alphafold2_tpu.ops import dispatch
                from alphafold2_tpu.ops.flash import (causal_kernel_plan,
                                                      causal_saved_bytes)

                # the arm each call site took, and what the causal kernel
                # makes of the core's shape where it is the arm: block,
                # sub-tile, grid steps a row (the tiles on or below the
                # diagonal; a family with sliding-window layers has their
                # plan beside it: the band's tiles and the triangle's), and
                # the two results of it that each layer's checkpoint keeps
                # for the backward pass, in bytes a layer
                core = (args.length, cfg.num_attention_heads, cfg.qk_head_dim,
                        cfg.v_head_dim, cfg.compute_dtype)
                window = getattr(cfg, "sliding_window", None)
                plans = {} if window is None else {
                    "causal_kernel_plan_window": causal_kernel_plan(
                        *core, window=window)}
                logger.event(
                    step, "dispatch", decisions=dispatch.decisions(),
                    causal_kernel_plan=causal_kernel_plan(*core), **plans,
                    layer_checkpoint_saves=causal_saved_bytes(args.batch, *core))
                # how much of the start was compiling, and whether the
                # compile cache served it
                logger.event(step, "compile", **compile_record.totals(top=5))
            telemetry.step_complete(step)
            if step % 10 == 0 or step == start + args.steps - 1:
                print(f"step {step}  loss {float(metrics['loss']):.4f}  "
                      f"grad_norm {float(metrics['grad_norm']):.3f}  "
                      f"({time.time() - t0:.1f}s elapsed)")
            if mgr is not None:
                with tracer.span("train.checkpoint", cat="train", step=step), \
                        telemetry.account("checkpoint"):
                    mgr.save(state)
            if profiling and step + 1 >= prof_end:
                jax.profiler.stop_trace()
                profiling = False
        finish(mgr, state)
    finally:
        if profiling:
            jax.profiler.stop_trace()
        telemetry.close()
        logger.close()
        finish_trace(tracer, args)
    print("done")


if __name__ == "__main__":
    main()
