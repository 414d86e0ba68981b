"""Training harness: jitted optax train step with scanned grad accumulation.

The reference has no Trainer abstraction at all — its loops are inlined in
entry scripts with a Python-level gradient-accumulation loop
(reference train_pre.py:72-102) and empty DeepSpeed/Lightning launcher files
(reference training_scripts/). Here the harness is a first-class subsystem:

  * one `TrainState` pytree (params, opt state, step);
  * a single jitted `train_step(state, batch, rng)` in which gradient
    accumulation is a `lax.scan` over a leading microbatch axis — the XLA
    analog of the reference's GRADIENT_ACCUMULATE_EVERY=16 Python loop,
    compiled once and free of host round-trips;
  * gradients are averaged over microbatches (the reference sums via
    repeated .backward(); under Adam the two differ only through eps —
    documented divergence, mean is the standard JAX convention).

The distributed variant of this step (mesh-sharded batch, psum-ed grads)
lives in alphafold2_tpu/parallel/.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from alphafold2_tpu.models import Alphafold2Config, alphafold2_apply, alphafold2_init
from alphafold2_tpu.ops.quant import reject_quant_training
from alphafold2_tpu.telemetry.profiling import OPTIMIZER_SCOPE, scope
from alphafold2_tpu.training.losses import bucketed_distance_matrix, distogram_cross_entropy


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Replaces the reference's module-level UPPER_CASE globals
    (reference train_pre.py:12-19)."""

    learning_rate: float = 3e-4
    grad_accum: int = 16
    max_grad_norm: Optional[float] = None  # reference has no clipping
    weight_decay: float = 0.0
    # learning-rate schedule (reference: constant lr only). warmup_steps
    # ramps linearly 0 -> lr; decay_steps (if set) then cosine-decays to
    # lr * decay_floor over that many post-warmup steps.
    warmup_steps: int = 0
    decay_steps: Optional[int] = None
    decay_floor: float = 0.0


def make_schedule(tcfg: TrainConfig):
    """Scalar lr schedule from the config.

    ALWAYS returns a callable (a constant schedule when no knobs are set):
    optax's opt_state carries a schedule count leaf exactly when the lr is
    a callable, so returning a float for the constant case would make the
    checkpoint pytree STRUCTURE depend on the schedule flags — a
    constant-lr restore template (e.g. predict.py's TrainConfig()) could
    then not load checkpoints from scheduled runs.

    MIGRATION NOTE: checkpoints written before schedules existed (optimizer
    built from a float lr) lack the schedule count leaf and cannot be
    restored by this version — re-init or re-train (pre-1.0 break,
    deliberate: a structure that depends on flag values is worse).
    """
    if tcfg.warmup_steps == 0 and tcfg.decay_steps is None:
        return optax.constant_schedule(tcfg.learning_rate)
    if tcfg.decay_steps is None:
        # warmup then hold (linear_schedule clamps at its end value)
        return optax.linear_schedule(
            0.0, tcfg.learning_rate, tcfg.warmup_steps
        )
    if tcfg.warmup_steps == 0:
        # decay only — no phantom zero-lr first step
        return optax.cosine_decay_schedule(
            tcfg.learning_rate, tcfg.decay_steps, alpha=tcfg.decay_floor
        )
    return optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=tcfg.learning_rate,
        warmup_steps=tcfg.warmup_steps,
        decay_steps=tcfg.warmup_steps + tcfg.decay_steps,
        end_value=tcfg.learning_rate * tcfg.decay_floor,
    )


def make_optimizer(tcfg: TrainConfig) -> optax.GradientTransformation:
    """FIXED-ARITY chain — clip (inf = no-op) then adamw (weight_decay=0 is
    numerically plain Adam) — so the opt_state pytree structure never
    depends on flag values. A conditionally-present chain element would
    break checkpoint restore across configs (predict.py restores with a
    default TrainConfig template); see make_schedule's invariant note.
    max_grad_norm <= 0 or None means clipping off (clip(0) would silently
    zero every gradient)."""
    max_norm = (
        tcfg.max_grad_norm
        if tcfg.max_grad_norm is not None and tcfg.max_grad_norm > 0
        else float("inf")
    )
    return optax.chain(
        optax.clip_by_global_norm(max_norm),
        optax.adamw(make_schedule(tcfg), weight_decay=tcfg.weight_decay),
    )


def train_state_init(key, cfg: Alphafold2Config, tcfg: TrainConfig):
    # int8 weights are the inference-only serving arm: refuse at the
    # entry point, not as a custom-vjp error deep inside the scan
    reject_quant_training(cfg, "train_state_init")
    params = alphafold2_init(key, cfg)
    opt = make_optimizer(tcfg)
    return {
        "params": params,
        "opt_state": opt.init(params),
        "step": jnp.zeros((), jnp.int32),
    }


def make_distogram_loss_fn(apply_fn):
    """Build the distogram pretraining loss around any model apply function
    with the alphafold2_apply signature — ONE label/loss construction shared
    by the replicated and sequence-parallel training paths
    (parallel/train.py sp_distogram_loss_fn)."""

    def loss_fn(params, cfg: Alphafold2Config, batch, rng):
        labels = bucketed_distance_matrix(batch["coords"], batch["mask"])
        logits = apply_fn(
            params,
            cfg,
            batch["seq"],
            batch.get("msa"),
            mask=batch["mask"],
            msa_mask=batch.get("msa_mask"),
            rng=rng,
        )
        return distogram_cross_entropy(logits, labels)

    return loss_fn


# Distogram pretraining loss on one microbatch (reference train_pre.py:82-95).
# batch: {"seq": (b, L) int, "mask": (b, L) bool, "coords": (b, L, 3)
# C-alpha coords} and optionally {"msa": (b, r, c), "msa_mask"}.
distogram_loss_fn = make_distogram_loss_fn(alphafold2_apply)


def make_train_step(
    cfg,
    tcfg: TrainConfig,
    loss_fn: Callable[..., Any] = distogram_loss_fn,
    aux_update: Optional[Callable[..., Any]] = None,
):
    """Build the jitted train step.

    The returned step consumes a batch whose leaves carry a leading
    microbatch axis (grad_accum, per_device_batch, ...) and scans over it.

    `cfg` is whatever `loss_fn(params, cfg, batch, rng)` reads: an
    Alphafold2Config, an E2EConfig, a DecoderConfig. With `aux_update`,
    `loss_fn` returns `(loss, aux)`; the aux of the microbatches is summed
    and, after the optimizer, `aux_update(params, aux) -> (params, step
    metrics)` does what lies outside the gradient (training/lm.py: the
    router's selection bias, the expert load).
    """
    reject_quant_training(cfg, "make_train_step")
    opt = make_optimizer(tcfg)
    has_aux = aux_update is not None

    def microbatch_grads(params, batch, rng):
        out, grads = jax.value_and_grad(loss_fn, has_aux=has_aux)(
            params, cfg, batch, rng)
        loss, aux = out if has_aux else (out, None)
        return loss, aux, grads

    def train_step(state, batch, rng=None):
        params = state["params"]

        def accum(carry, inp):
            loss_sum, grad_sum, aux_sum = carry
            mb, i = inp
            mb_rng = jax.random.fold_in(rng, i) if rng is not None else None
            loss, aux, grads = microbatch_grads(params, mb, mb_rng)
            return (
                loss_sum + loss,
                jax.tree_util.tree_map(jnp.add, grad_sum, grads),
                jax.tree_util.tree_map(jnp.add, aux_sum, aux),
            ), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        n = tcfg.grad_accum
        aux_zeros = None  # an empty pytree: nothing rides the scan
        if has_aux:
            first = jax.tree_util.tree_map(lambda t: t[0], batch)
            aux_zeros = jax.tree_util.tree_map(
                lambda t: jnp.zeros(t.shape, t.dtype),
                jax.eval_shape(lambda p, mb: loss_fn(p, cfg, mb, rng)[1],
                               params, first))
        (loss_sum, grad_sum, aux_sum), _ = jax.lax.scan(
            accum, (jnp.zeros((), jnp.float32), zeros, aux_zeros),
            (batch, jnp.arange(n))
        )
        loss = loss_sum / n
        grads = jax.tree_util.tree_map(lambda g: g / n, grad_sum)

        with scope(OPTIMIZER_SCOPE):
            updates, opt_state = opt.update(grads, state["opt_state"], params)
            params = optax.apply_updates(params, updates)
            extra = {}
            if has_aux:
                params, extra = aux_update(params, aux_sum)
            new_state = {
                "params": params,
                "opt_state": opt_state,
                "step": state["step"] + 1,
            }
            return new_state, {"loss": loss,
                               "grad_norm": optax.global_norm(grads), **extra}

    return train_step


def make_axis_accum_train_step(
    cfg: Alphafold2Config,
    tcfg: TrainConfig,
    loss_fn: Callable[..., Any],
    axis_name: str,
    *,
    overlap: bool = True,
    bucket_elems: Optional[int] = None,
    state_init: Callable = train_state_init,
    state_shape=None,
):
    """The microbatch-accumulating train step with an EXPLICIT gradient
    reduction over `axis_name` — the axis-level body of the DP-overlap
    step (parallel/train.py `make_dp_overlap_train_step` wraps it in
    shard_map over the mesh's data axis; this builder is mesh-free so it
    stays testable and composable).

    Where `make_train_step` leaves the data-parallel all-reduce to
    GSPMD — ONE gradient psum after the whole accumulation scan, fencing
    the optimizer — this step places the collectives itself:

      * gradients flatten into a few large dtype-homogeneous buckets
        (parallel/overlap.py) so hundreds of small param leaves ride a
        handful of bandwidth-bound all-reduces instead of hundreds of
        latency-bound ones;
      * with `overlap` (default), the scan body ISSUES the psum of
        microbatch i-1's buckets before computing microbatch i's
        forward/backward — the reduction rides the interconnect under
        the next microbatch's compute, and only the LAST microbatch's
        psum remains on the critical path;
      * with `overlap=False` it accumulates locally and issues one
        bucketed psum after the scan — the synchronous reference arm
        (same arithmetic modulo psum/add reassociation; the A/B pair for
        the dryrun, bench legs, and overlap-lint fixtures).

    Loss semantics: each shard's loss_fn normalizes over ITS microbatch
    (e.g. distogram_cross_entropy's valid-pair count), and shard results
    average with equal weight. This equals the GSPMD global-batch step
    exactly when per-shard normalizers match (uniform masks / padded
    synthetic batches) and differs only in mean-of-means weighting when
    they don't — documented divergence, same convention as the
    microbatch mean `make_train_step` already takes.

    The returned step MUST run inside `shard_map` (it calls
    jax.lax.psum over `axis_name`): signature (state, batch, rng) ->
    (state, metrics) with batch leaves carrying (grad_accum,
    per_shard_batch, ...) leading axes.
    """
    from alphafold2_tpu.parallel.overlap import (
        DEFAULT_BUCKET_ELEMS,
        flatten_buckets,
        plan_buckets,
        unflatten_buckets,
    )

    reject_quant_training(cfg, "make_axis_accum_train_step")
    opt = make_optimizer(tcfg)
    n = tcfg.grad_accum
    if state_shape is None:
        # abstract trace of the init — callers that already have the
        # state shape (make_dp_overlap_train_step computes it for its
        # sharding specs) pass it in so the model is not traced twice
        state_shape = jax.eval_shape(
            lambda k: state_init(k, cfg, tcfg), jax.random.PRNGKey(0)
        )
    params_shape = state_shape["params"]
    treedef, buckets = plan_buckets(
        params_shape, bucket_elems or DEFAULT_BUCKET_ELEMS
    )

    def train_step(state, batch, rng=None):
        params = state["params"]
        num_shards = jax.lax.psum(1, axis_name)

        def bucketed_grads(mb, i):
            mb_rng = jax.random.fold_in(rng, i) if rng is not None else None
            loss, grads = jax.value_and_grad(loss_fn)(params, cfg, mb, mb_rng)
            return loss, flatten_buckets(grads, buckets)

        # microbatch 0 runs before the scan so the overlapped body always
        # has a previous microbatch's buckets in flight — no zero-filled
        # warmup psum
        loss0, bkts0 = bucketed_grads(
            jax.tree_util.tree_map(lambda x: x[0], batch), 0
        )
        zeros = [jnp.zeros_like(b) for b in bkts0]

        if n > 1:

            def accum(carry, inp):
                loss_sum, red, prev = carry
                mb, i = inp
                if overlap:
                    # ISSUE the psum of microbatch i-1 first: its
                    # transfer hides under this microbatch's fwd/bwd
                    # (the dots below do not depend on it —
                    # analysis/overlap_lint.py asserts exactly that)
                    reduced = [jax.lax.psum(b, axis_name) for b in prev]
                    loss, bkts = bucketed_grads(mb, i)
                    red = [a + r for a, r in zip(red, reduced)]
                else:
                    # synchronous arm: accumulate locally, reduce once
                    # after the scan
                    loss, bkts = bucketed_grads(mb, i)
                    bkts = [a + b for a, b in zip(prev, bkts)]
                return (loss_sum + loss, red, bkts), None

            rest = jax.tree_util.tree_map(lambda x: x[1:], batch)
            (loss_sum, red, last), _ = jax.lax.scan(
                accum, (loss0, zeros, bkts0), (rest, jnp.arange(1, n))
            )
        else:
            loss_sum, red, last = loss0, zeros, bkts0

        # flush: the last microbatch's (or, synchronous, the whole
        # accumulated) reduction — the only psum left on the critical path
        red = [a + jax.lax.psum(b, axis_name) for a, b in zip(red, last)]
        denom = n * num_shards
        loss = jax.lax.psum(loss_sum, axis_name) / denom
        grads = unflatten_buckets(
            [b / denom for b in red], params_shape, treedef, buckets
        )

        with scope(OPTIMIZER_SCOPE):
            updates, opt_state = opt.update(grads, state["opt_state"], params)
            new_params = optax.apply_updates(params, updates)
            new_state = {
                "params": new_params,
                "opt_state": opt_state,
                "step": state["step"] + 1,
            }
            return new_state, {"loss": loss,
                               "grad_norm": optax.global_norm(grads)}

    return train_step


# --- fault-injection hook (reliability layer) --------------------------------


def with_fault_injection(step_fn, injector):
    """Wrap a (jitted) step function with the chaos-injection hook point.

    The wrapper runs HOST-side, around the device program: before the
    step, the injector can raise (step-N exception, the path
    `run_resilient` recovers) or trip a preemption flag; after it, a
    `nan_grads` fault poisons the reported metrics (so StepGuard's
    non-finite watchdog must detect and roll back). `injector=None`
    returns `step_fn` unchanged — the production path pays nothing.
    """
    if injector is None:
        return step_fn

    def wrapped(state, batch, rng=None):
        step = int(np.asarray(jax.device_get(state["step"])))
        batch = injector.before_train_step(step, batch)
        new_state, metrics = step_fn(state, batch, rng)
        return injector.after_train_step(step, new_state, metrics)

    return wrapped


# --- shared trainer CLI surface ---------------------------------------------


def add_train_args(ap):
    """The optimizer/schedule/seed argparse block shared by train_pre.py and
    train_end2end.py — one place to add the next knob."""
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0,
                    help="base seed for params, data, and per-step rng")
    ap.add_argument("--warmup-steps", type=int, default=0,
                    help="linear lr warmup steps (0 = constant lr)")
    ap.add_argument("--decay-steps", type=int, default=None,
                    help="cosine-decay the lr over this many post-warmup steps")
    ap.add_argument("--decay-floor", type=float, default=0.0,
                    help="cosine decay ends at lr * this fraction")
    ap.add_argument("--max-grad-norm", type=float, default=None,
                    help="global-norm gradient clipping (<=0 or unset: off)")
    ap.add_argument("--weight-decay", type=float, default=0.0,
                    help="AdamW weight decay (default 0 = plain Adam)")


def tcfg_from_args(args, grad_accum: int) -> TrainConfig:
    return TrainConfig(
        learning_rate=args.lr,
        grad_accum=grad_accum,
        warmup_steps=args.warmup_steps,
        decay_steps=args.decay_steps,
        decay_floor=args.decay_floor,
        max_grad_norm=args.max_grad_norm,
        weight_decay=args.weight_decay,
    )
