#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a user
calls, at the full width of BASELINE config 5 (depth cut to 2, weights
random from a seed), and checks what comes out by the repo's own means:

  device   the accelerator JAX found, versions, the compile-cache directory,
           Pallas interpret mode (must be off), the kernel dispatch table
  trainer  three donated optimizer steps of `north_star_e2e_config(depth=2)`
           driven as train_end2end.py drives them
  decoder  two donated optimizer steps of the `deepseek_v3` decoder
           (train_lm.py's step) at the widths of the benchmark's
           configuration, depth cut to one dense and one MoE layer, one
           sequence of 8192 tokens: the causal attention must take the
           Pallas kernel and the experts' grouped product is tallied
  server   a `ServingEngine` built as serve.py builds it (buckets 128/256/384,
           batch 2, precompiled) answering six seeded requests
  kernels  every `pallas_call` site compiled by Mosaic (forced: `auto` picks
           none of them at these shapes) against its `xla_ref` arm at the
           tolerances of tests/test_dispatch.py and the kernels' own tests

A phase that fails raises; nothing is caught and carried past. Without a TPU
the script exits non-zero and prints no result. The last stdout line of a
passing run is `{"ok": true, "device": {"platform", "kind", "count"}}`.

    python chip_smoke.py                 # on the chip, through the chip tool
    python chip_smoke.py --sp-shards 4   # four-chip host: the trainer phase
                                         # sequence-parallel, against one chip
    python chip_smoke.py --dry           # rehearsal: toy widths, any platform,
                                         # Pallas interpreted, lines "dry": true

Every phase's observations are also appended, one JSON line per run, to
`chiprun_out/chip_smoke.jsonl`; a second run in the same command reads the
first run's trainer compile seconds from there and prints cold beside warm.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import random
import statistics
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
RUN_LOG = os.path.join(REPO, "chiprun_out", "chip_smoke.jsonl")

# |loss(SP over N chips) - loss(one chip)| / |loss(one chip)| for the bf16
# trunk. tests/test_sequence_parallel.py holds the f32 CPU paths to atol
# 1e-4; on the MXU both arms round every matmul operand to bf16 (eps 2^-8)
# and accumulate ring hops in a different order, so the band is set from the
# dtype instead. The measured difference is printed next to both bands.
SP_LOSS_RTOL_BF16 = 2e-2
SP_LOSS_ATOL_F32_CPU = 1e-4


class SmokeFailure(RuntimeError):
    """A phase produced something wrong; the script exits non-zero."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


class Run:
    """One smoke run's switches and its output channel."""

    def __init__(self, dry: bool):
        self.dry = dry
        self.record: dict = {}

    def emit(self, phase: str, **obs) -> None:
        line = {"phase": phase, **obs}
        if self.dry:
            line["dry"] = True
        self.record[phase] = obs
        print(json.dumps(line), flush=True)


def prior_runs(dry: bool) -> list:
    """Earlier runs' records from this command's output directory."""
    if not os.path.exists(RUN_LOG):
        return []
    with open(RUN_LOG) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    return [r for r in rows if bool(r.get("dry")) == dry]


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# --------------------------------------------------------------------- device


def phase_device(run: Run, cache_dir: str, need_devices: int):
    import jax
    import jaxlib

    from alphafold2_tpu.ops import dispatch
    from alphafold2_tpu.ops.core import pallas_interpret

    devices = jax.devices()
    dev = devices[0]
    if not run.dry and dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r} "
            f"({dev.device_kind}, {len(devices)} device(s), JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}). "
            f"`--dry` rehearses the control flow off the chip.",
            file=sys.stderr,
        )
        return None
    check(len(devices) >= need_devices,
          f"need {need_devices} device(s), JAX found {len(devices)}")

    interpret = pallas_interpret()
    check(interpret == run.dry,
          f"pallas_interpret() is {interpret}: a chip run must compile its "
          f"kernels (is AF2_PALLAS_INTERPRET inherited?) and a dry run must "
          f"interpret them")

    table = {}
    for op, probe, supported, resolved in dispatch.resolution_table():
        check(not resolved.startswith("ERROR"), f"dispatch {op}: {resolved}")
        table[op] = {"probe": {k: str(v) for k, v in probe.items()},
                     "supported": supported, "resolved": resolved}

    libtpu = None
    if dev.platform == "tpu":
        from importlib import metadata

        libtpu = metadata.version("libtpu")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    run.emit(
        "device", **device,
        jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu,
        platform_version=dev.client.platform_version.splitlines()[0],
        compile_cache_dir=cache_dir,
        compile_cache_entries_at_start=cache_entries(cache_dir),
        pallas_interpret=interpret, dispatch=table,
    )
    return device


# -------------------------------------------------------------------- trainer


def _host_leaves(tree):
    import jax
    import numpy as np

    return [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(tree)]


def _three_steps(label, step_fn, state, batches, base_rng):
    """Compile, then three optimizer steps, as train_end2end.py's loop runs
    them (next batch, step-indexed key, loss fetched every step)."""
    import jax
    import numpy as np

    before = {k: _host_leaves(state["params"][k]) for k in state["params"]}
    batch = next(batches)
    t0 = time.perf_counter()
    compiled = step_fn.lower(
        state, batch, jax.random.fold_in(base_rng, 0)).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()

    losses, seconds = [], []
    for step in range(3):
        if step:
            batch = next(batches)
        t0 = time.perf_counter()
        state, metrics = compiled(
            state, batch, jax.random.fold_in(base_rng, step))
        loss = float(np.asarray(metrics["loss"]))  # waits for the device
        seconds.append(time.perf_counter() - t0)
        check(np.isfinite(loss), f"{label}: step {step} loss is {loss}")
        losses.append(loss)

    check(int(state["step"]) == 3,
          f"{label}: state['step'] is {int(state['step'])}, expected 3")
    changed = {}
    for name, old in before.items():
        new = _host_leaves(state["params"][name])
        n = sum(not np.array_equal(a, b) for a, b in zip(old, new))
        check(n > 0, f"{label}: no leaf of params[{name!r}] changed")
        changed[name] = f"{n}/{len(old)}"
    return state, {
        "compile_seconds": round(compile_s, 2),
        "step_seconds": [round(s, 3) for s in seconds],
        "sec_per_step_median": round(statistics.median(seconds), 3),
        "losses": losses,
        "param_leaves_changed": changed,
        # the compiler's own plan for one step, beside what the device
        # reports after running it
        "compiled_bytes": {
            "argument": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "alias": mem.alias_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
        },
    }


def _trainer_setup(run: Run, model_overrides=None):
    from alphafold2_tpu.training import (
        DataConfig,
        TrainConfig,
        north_star_e2e_config,
        stack_microbatches,
        synthetic_structure_batches,
    )

    ecfg, crop, msa_rows = north_star_e2e_config(
        2, smoke=run.dry, model_overrides=model_overrides)
    tcfg = TrainConfig(learning_rate=3e-4, grad_accum=1)
    dcfg = DataConfig(batch_size=1, max_len=crop, msa_rows=msa_rows, seed=0)

    def batches():
        return stack_microbatches(
            synthetic_structure_batches(dcfg), tcfg.grad_accum)

    return ecfg, tcfg, crop, msa_rows, batches


def _config_summary(ecfg, crop, msa_rows) -> dict:
    m = ecfg.model
    return {"dim": m.dim, "heads": m.heads, "dim_head": m.dim_head,
            "depth": m.depth, "crop": crop, "pair_side": 3 * crop,
            "msa_rows": msa_rows, "dtype": m.dtype.__name__,
            "reversible": m.reversible, "remat": m.remat,
            "cross_attn_mode": m.cross_attn_mode,
            "msa_tie_row_attn": m.msa_tie_row_attn,
            "compress": m.cross_attn_compress_ratio,
            "attn_batch_chunk": m.attn_batch_chunk,
            "mds": f"{ecfg.mds_iters} {ecfg.mds_init}"}


def phase_trainer(run: Run, cache_dir: str):
    import jax

    from alphafold2_tpu.ops import dispatch
    from alphafold2_tpu.training import (
        e2e_loss_fn,
        e2e_train_state_init,
        make_train_step,
    )

    ecfg, tcfg, crop, msa_rows, batches = _trainer_setup(run)
    state = e2e_train_state_init(jax.random.PRNGKey(0), ecfg, tcfg)
    step_fn = jax.jit(make_train_step(ecfg, tcfg, loss_fn=e2e_loss_fn),
                      donate_argnums=(0,))
    dispatch.reset_decisions()
    state, obs = _three_steps("trainer", step_fn, state, batches(),
                              jax.random.PRNGKey(1))
    del state
    # which arm every dispatched call site of the compiled step runs
    decisions = dispatch.decisions()
    if not run.dry:
        check(any(k.startswith("flash_attention -> pallas_tpu @ i=1152 j=1152")
                  for k in decisions),
              f"the pair axial attention did not take the kernel: {decisions}")

    stats = jax.devices()[0].memory_stats()
    if stats is None:
        check(run.dry, "the device reports no memory_stats")
        stats = {"peak_bytes_in_use": None}
    earlier = [r["trainer"]["compile_seconds"] for r in prior_runs(run.dry)
               if "trainer" in r]
    run.emit("trainer", config=_config_summary(ecfg, crop, msa_rows), **obs,
             dispatch_decisions=decisions,
             peak_bytes_in_use=stats["peak_bytes_in_use"],
             memory_stats=stats,
             compile_seconds_earlier_runs=earlier,
             compile_cache_entries=cache_entries(cache_dir))


DECODER_CONFIG = os.path.join(REPO, "benchmarks", "configs",
                              "kanana2_30b_a3b_ep8_l5.json")


def phase_decoder(run: Run):
    """Two steps of the language-model trainer as train_lm.py builds them."""
    import jax
    import numpy as np

    import train_lm
    from alphafold2_tpu.ops import dispatch
    from alphafold2_tpu.training import (
        TrainConfig,
        lm_aux_update,
        lm_loss_fn,
        lm_train_state_init,
        make_train_step,
        stack_microbatches,
        zipf_token_batches,
    )

    with open(DECODER_CONFIG) as f:
        learning_rate = json.load(f)["train"]["learning_rate"]
    if run.dry:
        cfg = train_lm.DecoderConfig(dtype="float32", **train_lm._TOY)
        length = 64
    else:
        cfg = dataclasses.replace(
            train_lm.config_from_file(DECODER_CONFIG, "bfloat16"),
            num_hidden_layers=2)
        length = 8192
    tcfg = TrainConfig(learning_rate=learning_rate, grad_accum=1)
    state = lm_train_state_init(jax.random.PRNGKey(0), cfg, tcfg)
    step_fn = jax.jit(make_train_step(cfg, tcfg, loss_fn=lm_loss_fn,
                                      aux_update=lm_aux_update(cfg)),
                      donate_argnums=(0,))
    batches = stack_microbatches(
        zipf_token_batches(cfg.vocab_size, 1, length, seed=0), 1)
    dispatch.reset_decisions()
    losses, seconds, load = [], [], None
    for step in range(2):
        t0 = time.perf_counter()
        state, metrics = step_fn(state, next(batches), jax.random.PRNGKey(step))
        loss = float(np.asarray(metrics["loss"]))  # waits for the device
        seconds.append(round(time.perf_counter() - t0, 3))
        check(np.isfinite(loss), f"decoder: step {step} loss is {loss}")
        losses.append(loss)
        load = {k: np.asarray(v).tolist() for k, v in metrics.items()
                if k.startswith("moe_")}
    del state
    decisions = dispatch.decisions()
    check(any(k.startswith("grouped_matmul -> ") for k in decisions),
          f"the experts' grouped product was not dispatched: {decisions}")
    if not run.dry:
        check(any(k.startswith("flash_attention -> pallas_tpu @ i=8192 j=8192")
                  and "causal=True" in k for k in decisions),
              f"the causal attention did not take the kernel: {decisions}")
    run.emit("decoder",
             config={"hidden_size": cfg.hidden_size, "heads": cfg.num_attention_heads,
                     "qk_head_dim": cfg.qk_head_dim, "v_head_dim": cfg.v_head_dim,
                     "layers": cfg.num_hidden_layers, "experts_held": list(cfg.held),
                     "router_width": cfg.n_routed_experts,
                     "vocab_size": cfg.vocab_size, "tokens": length,
                     "dtype": cfg.dtype},
             losses=losses, step_seconds=seconds, expert_load=load,
             dispatch_decisions=decisions)


def phase_trainer_sp(run: Run, shards: int):
    """The trainer phase through train_end2end.py's `--sp-shards` path, then
    the same steps on one chip from the same seed. The sequence-parallel
    trunk needs the sequential layer list, so both arms drop `reversible`;
    the one-chip arm remats each layer to fit 16 GB (same values)."""
    import jax
    import jax.numpy as jnp

    from alphafold2_tpu.parallel import (
        make_mesh,
        make_sp_train_step,
        sp_e2e_loss_fn,
    )
    from alphafold2_tpu.parallel.sp_trunk import sp_trunk_apply
    from alphafold2_tpu.training import (
        e2e_loss_fn,
        e2e_train_state_init,
        make_train_step,
    )

    ecfg, tcfg, crop, msa_rows, batches = _trainer_setup(
        run, model_overrides={"reversible": False})
    mesh = make_mesh({"seq": shards})
    mesh_ids = sorted(d.id for d in mesh.devices.flat)
    check(len(set(mesh_ids)) == shards, f"mesh devices {mesh_ids}")

    # --- pair activations: the trunk's own output layout ------------------
    state = e2e_train_state_init(jax.random.PRNGKey(0), ecfg, tcfg)
    cfg = ecfg.model
    n = 3 * crop
    x = jnp.zeros((1, n, n, cfg.dim), cfg.dtype)
    m = jnp.zeros((1, msa_rows, crop, cfg.dim), cfg.dtype)
    x_out, _ = jax.jit(
        lambda layers, x, m: sp_trunk_apply(layers, cfg, x, m, mesh)
    )(state["params"]["model"]["trunk"], x, m)
    pair_ids = sorted(d.id for d in x_out.sharding.device_set)
    pair_shard = x_out.addressable_shards[0].data.shape
    check(pair_ids == mesh_ids,
          f"pair activations live on devices {pair_ids}, mesh is {mesh_ids}")
    check(pair_shard == (1, n // shards, n, cfg.dim),
          f"pair shard shape {pair_shard}")
    check(bool(jnp.isfinite(x_out.astype(jnp.float32)).all()),
          "sequence-parallel trunk output is not finite")
    del x, m, x_out

    # --- the sequence-parallel steps --------------------------------------
    step_fn = make_sp_train_step(ecfg, tcfg, mesh,
                                 loss_fn=sp_e2e_loss_fn(mesh))
    state, sp = _three_steps("trainer_sp", step_fn, state, batches(),
                             jax.random.PRNGKey(1))
    leaf = jax.tree_util.tree_leaves(state["params"])[0]
    state_ids = sorted(d.id for d in leaf.sharding.device_set)
    check(state_ids == mesh_ids,
          f"state lives on devices {state_ids}, mesh is {mesh_ids}")
    in_use = {}
    for d in mesh.devices.flat:
        stats = d.memory_stats()
        if stats is None:
            check(run.dry, f"device {d.id} reports no memory_stats")
            continue
        in_use[str(d.id)] = {"bytes_in_use": stats["bytes_in_use"],
                             "peak_bytes_in_use": stats["peak_bytes_in_use"]}
        check(stats["bytes_in_use"] > 0, f"device {d.id} holds no bytes")
    del state, leaf

    # --- the same seed on one chip ----------------------------------------
    ecfg1 = dataclasses.replace(
        ecfg, model=dataclasses.replace(ecfg.model, remat=True))
    state = e2e_train_state_init(jax.random.PRNGKey(0), ecfg1, tcfg)
    one_fn = jax.jit(make_train_step(ecfg1, tcfg, loss_fn=e2e_loss_fn),
                     donate_argnums=(0,))
    state, one = _three_steps("trainer_one_chip", one_fn, state, batches(),
                              jax.random.PRNGKey(1))
    del state

    diffs = [abs(a - b) for a, b in zip(sp["losses"], one["losses"])]
    rel = diffs[0] / abs(one["losses"][0])
    f32 = cfg.dtype == jnp.float32
    in_f32_band = diffs[0] <= SP_LOSS_ATOL_F32_CPU
    run.emit(
        "trainer_sp", config=_config_summary(ecfg, crop, msa_rows),
        sp_shards=shards, mesh_device_ids=mesh_ids,
        state_device_ids=state_ids, pair_device_ids=pair_ids,
        pair_shard_shape=list(pair_shard), memory=in_use,
        sp=sp, one_chip=one, loss_abs_diff=diffs,
        loss_rel_diff_step0=rel,
        within_f32_cpu_band=in_f32_band,
        band={"dtype": cfg.dtype.__name__,
              "rule": "atol 1e-4" if f32 else f"rtol {SP_LOSS_RTOL_BF16}"},
    )
    check(in_f32_band if f32 else rel <= SP_LOSS_RTOL_BF16,
          f"step-0 loss: {shards}-chip {sp['losses'][0]} vs one-chip "
          f"{one['losses'][0]} (rel {rel:.3e}) is outside the band")


# --------------------------------------------------------------------- server


def phase_server(run: Run):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from alphafold2_tpu.constants import AA_ORDER
    from alphafold2_tpu.models import Alphafold2Config, alphafold2_init
    from alphafold2_tpu.serving import ServingConfig, ServingEngine

    if run.dry:
        cfg = Alphafold2Config(dim=16, depth=1, heads=2, dim_head=8,
                               max_seq_len=24)
        buckets, lengths = (8, 16, 24), (5, 24, 12, 14, 7, 20)
    else:
        cfg = Alphafold2Config(dim=256, depth=2, heads=8, dim_head=64,
                               max_seq_len=384, dtype=jnp.bfloat16)
        buckets, lengths = (128, 256, 384), (100, 384, 200, 230, 60, 300)
    params = alphafold2_init(jax.random.PRNGKey(0), cfg)
    scfg = ServingConfig(buckets=buckets, max_batch=2, precompile=True,
                         request_timeout_s=600.0)

    t0 = time.perf_counter()
    engine = ServingEngine(params, cfg, scfg)
    build_s = time.perf_counter() - t0
    try:
        warm = engine.stats()
        built = len(buckets) * len(warm["batch_shapes"])
        check(warm["compiles"]["count"] <= built,
              f"{warm['compiles']['count']} compiles for {built} executables")

        rng = random.Random(0)
        seqs = ["".join(rng.choice(AA_ORDER) for _ in range(n))
                for n in lengths]
        t0 = time.perf_counter()
        pending = [engine.submit(s) for s in seqs]
        results = [p.result(timeout=600.0) for p in pending]
        wall = time.perf_counter() - t0
    finally:
        engine.shutdown(drain=True)

    per_bucket: dict = {}
    for seq, res in zip(seqs, results):
        want = min(b for b in buckets if b >= len(seq))
        check(res.bucket == want,
              f"L={len(seq)} served from bucket {res.bucket}, not {want}")
        check(res.coords.shape == (len(seq), 3)
              and res.confidence.shape == (len(seq),),
              f"L={len(seq)}: coords {res.coords.shape}, confidence "
              f"{res.confidence.shape} are not sliced to the true length")
        check(bool(np.isfinite(res.coords).all()
                   and np.isfinite(res.confidence).all()
                   and np.isfinite(res.stress)),
              f"L={len(seq)}: non-finite structure")
        check(not res.from_cache, f"L={len(seq)} answered from the cache")
        per_bucket.setdefault(str(res.bucket), []).append(
            round(res.latency_s, 3))
    check(sorted(per_bucket) == sorted(str(b) for b in buckets),
          f"buckets hit: {sorted(per_bucket)}")

    stats = engine.stats()
    reqs = stats["requests"]
    check(reqs["completed"] == len(seqs) and reqs["failed"] == 0
          and reqs["timed_out"] == 0 and not stats["errors"],
          f"requests {reqs}, errors {stats['errors']}")
    check(stats["compiles"] == warm["compiles"],
          f"compiled after warm-up: {warm['compiles']} -> {stats['compiles']}")
    check(max(stats["batches"]["recent_sizes"]) == 2,
          f"no pair was batched: sizes {stats['batches']['recent_sizes']}")
    check(stats["closed"], "engine not closed after shutdown")
    alive = [t.name for t in threading.enumerate()
             if t.name.startswith(("af2-serve", "af2-settle", "af2-dispatch"))]
    check(not alive, f"threads alive after shutdown: {alive}")

    run.emit(
        "server",
        config={"dim": cfg.dim, "heads": cfg.heads, "dim_head": cfg.dim_head,
                "depth": cfg.depth, "dtype": cfg.dtype.__name__,
                "buckets": list(buckets), "max_batch": scfg.max_batch,
                "mds": f"{scfg.mds_iters} {scfg.mds_init}"},
        build_seconds=round(build_s, 2),
        compile_seconds_by_bucket={
            k: round(v, 2)
            for k, v in stats["compiles"]["seconds_by_bucket"].items()},
        lengths=list(lengths), request_latency_s_by_bucket=per_bucket,
        six_requests_wall_s=round(wall, 3),
        batch_sizes=stats["batches"]["recent_sizes"],
        requests=reqs, dispatch=stats["dispatch"],
    )


# -------------------------------------------------------------------- kernels


def _max_abs(tree) -> float:
    """Largest |value| over a pytree, NaN if any leaf holds one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return 0.0
    return float(np.max(np.stack([
        np.asarray(jnp.max(jnp.abs(leaf.astype(jnp.float32))))
        for leaf in leaves])))


def _site(name, fn, diff_args, atol_out, atol_grad):
    """Compile `fn(use_kernel, *diff_args)` fwd+bwd under both arms and hold
    the kernel arm to the reference. `atol_grad=None`: forward only
    (inference-only op)."""
    import jax
    import jax.numpy as jnp

    def both(use_kernel):
        def run_arm(*args):
            if atol_grad is None:
                return fn(use_kernel, *args), ()
            out, vjp = jax.vjp(lambda *a: fn(use_kernel, *a), *args)
            # a fixed cotangent that is not constant along any axis
            ct = jax.tree_util.tree_map(
                lambda o: jnp.cos(jnp.arange(o.size, dtype=jnp.float32))
                .reshape(o.shape).astype(o.dtype), out)
            return out, vjp(ct)

        return jax.jit(run_arm)(*diff_args)

    t0 = time.perf_counter()
    k_out, k_grads = both(True)
    r_out, r_grads = both(False)

    def worst(a, b):
        return _max_abs(jax.tree_util.tree_map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))

    out_diff = worst(k_out, r_out)
    grad_diff = worst(k_grads, r_grads)
    ref_out = _max_abs(r_out)
    seconds = time.perf_counter() - t0
    check(out_diff <= atol_out,
          f"{name}: forward differs from xla_ref by {out_diff} > {atol_out}")
    if atol_grad is not None:
        check(grad_diff <= atol_grad,
              f"{name}: gradients differ from xla_ref by {grad_diff} > "
              f"{atol_grad}")
    return {"site": name, "out_max_abs_diff": out_diff, "atol_out": atol_out,
            "grad_max_abs_diff": grad_diff if atol_grad is not None else None,
            "atol_grad": atol_grad, "ref_out_max_abs": ref_out,
            "seconds": round(seconds, 2)}


def kernel_sites(run: Run) -> list:
    """One `_site` argument tuple per `pallas_call` site."""
    import jax
    import jax.numpy as jnp

    from alphafold2_tpu.ops import dispatch
    from alphafold2_tpu.ops.attention import AttentionConfig, attention_init
    from alphafold2_tpu.ops.flash import (
        flash_attention,
        hop_attention_lse,
        stream_block,
    )
    from alphafold2_tpu.ops.quant import quant_matmul, quantize_weight
    from alphafold2_tpu.ops.sparse import SparseConfig, sparse_attention_apply

    dry = run.dry
    dtype = jnp.float32 if dry else jnp.bfloat16
    # tolerances: tests/test_dispatch.py (forward), tests/test_flash_kernel.py,
    # tests/test_fused_kernel.py, tests/test_sparse.py (gradients)
    tol = {"flash": (2e-5, 1e-4), "fused": (5e-5, 1e-4),
           "sparse": (1e-4, 1e-4), "quant": (5e-4, None)} if dry else \
          {"flash": (2e-2, 5e-2), "fused": (3e-2, 5e-2),
           "sparse": (5e-2, 1e-1), "quant": (5e-2, None)}
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 64))
    sites = []

    def normal(shape, dt=dtype):
        return jax.random.normal(next(keys), shape, dt)

    def masked_bias(B, j):
        # the last eighth of the keys masked, as padding would
        return jnp.zeros((B, j), jnp.float32).at[:, j - j // 8:].set(-jnp.inf)

    # --- dense flash, fwd+bwd: the model's chunk shape, and a j >= 4096
    # shape `auto` itself selects
    h, dh = (2, 8) if dry else (8, 64)
    for name, B, i, j, request in (
        (("flash_chunk", 2, 32, 37, True) if dry
         else ("flash_chunk_256x1152x1152x64", 32, 1152, 1152, "auto")),
        (("flash_long_j", 1, 24, 48, True) if dry
         else ("flash_auto_8x1152x4096x64", 1, 1152, 4096, "auto")),
    ):
        if request == "auto":
            arm = dispatch.resolve("flash_attention", request="auto",
                                   i=i, j=j, dh=dh)
            check(arm == dispatch.ARM_PALLAS_TPU,
                  f"auto resolves flash_attention(i={i}, j={j}) to {arm}")
        bias = masked_bias(B, j)
        sites.append((
            name,
            lambda k, q, kk, v, bias=bias, request=request: flash_attention(
                q, kk, v, bias, use_kernel=request if k else False),
            (normal((B, i, h, dh)), normal((B, j, h, dh)),
             normal((B, j, h, dh))), *tol["flash"]))

    # --- flash_attention_lse: one ring hop's (out, lse)
    BH, n = (4, 24) if dry else (64, 576)
    hop_bias = masked_bias(BH, n)
    scale = dh ** -0.5

    def hop(use_kernel, q, k, v, BH=BH, n=n):
        if use_kernel:
            return hop_attention_lse(q, k, v, hop_bias, scale)
        m0 = jnp.full((BH, 1, n), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((BH, 1, n), jnp.float32)
        a0 = jnp.zeros((BH, 1, n, dh), jnp.float32)
        m, l, a = stream_block(q[:, :, None], k[:, :, None], v[:, :, None],
                               hop_bias, m0, l0, a0, scale)
        return (a / l[..., None])[:, 0], (m + jnp.log(l))[:, 0]

    sites.append((f"flash_lse_hop_{BH}x{n}x{n}x{dh}", hop,
                  (normal((BH, n, dh)), normal((BH, n, dh)),
                   normal((BH, n, dh))), *tol["flash"]))

    # --- fused epilogue: gate with the key-side bias, gate with a 2-D bias
    B, n = (2, 19) if dry else (4, 1152)
    kbias = masked_bias(B, n)
    qkvg = tuple(normal((B, n, h, dh)) for _ in range(4))
    sites.append((
        f"fused_gate_keybias_{B * h}x{n}x{n}x{dh}",
        lambda k, q, kk, v, g: flash_attention(
            q, kk, v, kbias, gate=g, use_kernel=k),
        qkvg, *tol["fused"]))
    sites.append((
        f"fused_gate_bias2d_{B * h}x{n}x{n}x{dh}",
        lambda k, q, kk, v, g, pb: flash_attention(
            q, kk, v, kbias, pair_bias=pb, gate=g, use_kernel=k),
        qkvg + (normal((B, h, n, n), jnp.float32),), *tol["fused"]))

    # --- block-sparse, fwd+bwd: config 3's own shape, and the regime `auto`
    # takes the kernel in
    for block, n, dim in (((16, 50, 16), (16, 64, 16)) if dry
                          else ((16, 2048, 256), (128, 4096, 256))):
        acfg = AttentionConfig(dim=dim, heads=h, dim_head=dh, dtype=dtype)
        scfg = SparseConfig(block_size=block, max_seq_len=max(n, 128))
        aparams = attention_init(next(keys), acfg)
        mask = jnp.ones((1, n), bool).at[:, n - n // 8:].set(False)
        sites.append((
            f"sparse_block{block}_n{n}",
            lambda k, x, acfg=acfg, scfg=scfg, aparams=aparams, mask=mask:
            sparse_attention_apply(aparams, acfg, scfg, x, mask=mask,
                                   use_kernel=k),
            (normal((1, n, dim)),), *tol["sparse"]))

    # --- int8 fused-dequant matmul at the feed-forward shapes (outputs kept
    # O(1) so the tests' absolute tolerance means what it means there)
    for m, k, n in (((16, 32, 24), (13, 40, 21)) if dry
                    else ((32768, 256, 2048), (32768, 1024, 256))):
        qw, scale_w = quantize_weight(normal((k, n), jnp.float32) / k ** 0.5)
        sites.append((
            f"quant_matmul_{m}x{k}x{n}",
            lambda kern, x, qw=qw, scale_w=scale_w: quant_matmul(
                x, qw, scale_w, use_kernel=kern),
            (normal((m, k)),), *tol["quant"]))
    return sites


def phase_kernels(run: Run):
    sites = [_site(*spec) for spec in kernel_sites(run)]
    run.emit("kernels", sites=sites,
             seconds=round(sum(s["seconds"] for s in sites), 1))


# ----------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry", action="store_true",
                    help="rehearse the control flow: toy widths, any "
                         "platform, Pallas interpreted")
    ap.add_argument("--sp-shards", type=int, default=0,
                    help="run only the device and trainer phases, the "
                         "trainer sequence-parallel over this many chips "
                         "and again on one chip from the same seed")
    args = ap.parse_args(argv)
    if args.dry:
        os.environ["AF2_PALLAS_INTERPRET"] = "1"

    from alphafold2_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    run = Run(args.dry)
    t0 = time.perf_counter()
    device = phase_device(run, cache_dir, max(1, args.sp_shards))
    if device is None:
        return 2
    if args.sp_shards:
        phase_trainer_sp(run, args.sp_shards)
    else:
        phase_trainer(run, cache_dir)
        phase_decoder(run)
        phase_server(run)
        phase_kernels(run)

    os.makedirs(os.path.dirname(RUN_LOG), exist_ok=True)
    with open(RUN_LOG, "a") as fh:
        fh.write(json.dumps({**run.record, "dry": args.dry,
                             "wall_seconds": round(
                                 time.perf_counter() - t0, 1)}) + "\n")
    final = {"ok": True, "device": device}
    if args.dry:
        final["dry"] = True
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
