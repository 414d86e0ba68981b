"""Language-model training on the shared harness: the next-token loss of
the decoder (models/decoder.py: any of its families, by the configuration's
class), its parameters, its step metrics and a seeded token source.

`make_train_step(cfg, tcfg, loss_fn=lm_loss_fn,
aux_update=lm_aux_update(cfg))` is the whole wiring: `lm_loss_fn` has the
harness's `(params, cfg, batch, rng)` signature and returns
`(loss, aux)`; `lm_aux_update` is the ONE
place that handles what lies outside the gradient: the router's
selection bias `b` (a parameter leaf whose gradient is zero, because the
forward reads it through `stop_gradient`) is moved by each layer's
expert load, and the load becomes step metrics. The looped family
(`ouro`) has no expert layer: its loss is the expected next-token loss
under the exit distribution its gates give each token, less `beta` times
that distribution's entropy, and its step metrics are each pass's loss and
the mean exit step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from alphafold2_tpu.models.decoder import decoder_apply, decoder_init
from alphafold2_tpu.ops.moe import bias_update
from alphafold2_tpu.telemetry.profiling import scope

#: rows of logits computed at a time: (rows, vocab) float32 is the largest
#: tensor of the loss, and never stands for the whole batch
LOSS_BLOCK_ROWS = 2048


def lm_params_init(key, cfg):
    return decoder_init(key, cfg)


def lm_train_state_init(key, cfg, tcfg):
    """The harness's TrainState for the decoder: params, optimizer state,
    step (the twin of `train_state_init` / `e2e_train_state_init`)."""
    from alphafold2_tpu.training.harness import make_optimizer

    params = lm_params_init(key, cfg)
    return {"params": params, "opt_state": make_optimizer(tcfg).init(params),
            "step": jnp.zeros((), jnp.int32)}


def blocked_cross_entropy(hidden, head_w, targets, weights,
                          block_rows: int = LOSS_BLOCK_ROWS, tied: bool = False):
    """sum_r weights[r] * (logsumexp(hidden[r] @ head_w) - logit[targets[r]])
    with the logits in float32, `block_rows` rows at a time (each block
    under `jax.checkpoint`, so the backward builds them again instead of
    keeping them). hidden: (N, d); head_w: (d, V) in the compute dtype, or
    with `tied` the embedding table (V, d) itself, contracted over its d
    (no transposed copy is made); targets: (N,) int; weights: (N,)
    float32, or (N, K): K weightings of the same rows' losses, one sum
    each (K,), for the price of one head."""
    n, d = hidden.shape
    block = min(block_rows, n)
    pad = (-n) % block
    if pad:
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
        weights = jnp.pad(weights, ((0, pad),) + ((0, 0),) * (weights.ndim - 1))
    nb = (n + pad) // block

    @jax.checkpoint
    def one(h, t, w):
        logits = jax.lax.dot_general(
            h, head_w, (((1,), (1 if tied else 0,)), ((), ())),
            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        ce = lse - picked
        return jnp.sum(w * (ce if w.ndim == 1 else ce[:, None]), axis=0)

    def body(total, blk):
        return total + one(*blk), None

    total, _ = jax.lax.scan(
        body, jnp.zeros(weights.shape[1:], jnp.float32),
        (hidden.reshape(nb, block, d), targets.reshape(nb, block),
         weights.reshape((nb, block) + weights.shape[1:])))
    return total


def exit_log_distribution(gate_logits):
    """log p(t) of the exit distribution, in float32, from the T passes'
    gate logits z (T, ...): lambda_t = sigmoid(z_t), p(t) = lambda_t
    prod_{j<t} (1 - lambda_j) for t < T, p(T) = prod_{j<T} (1 - lambda_j),
    so that the T of them sum to 1; the last pass's gate is not read. Sums
    of log sigmoid(z) and log sigmoid(-z), so that no product underflows."""
    z = gate_logits.astype(jnp.float32)
    zero = jnp.zeros_like(z[:1])
    stayed = jnp.concatenate(
        [zero, jnp.cumsum(jax.nn.log_sigmoid(-z[:-1]), axis=0)])
    return stayed + jnp.concatenate([jax.nn.log_sigmoid(z[:-1]), zero])


def exit_entropy(log_p):
    """H(p) = -sum_t p(t) log p(t) over the leading axis of log p."""
    return -jnp.sum(jnp.exp(log_p) * log_p, axis=0)


def _expected_loss(states, gate_logits, head_w, targets, weights, beta):
    """The looped family's objective, summed over the rows: sum_t p(t)
    CE_t - beta H(p) of each target, with ONE `blocked_cross_entropy` a
    pass whose weights are p(t) * mask (the gate's gradient comes back
    through them) and, beside them, the mask (that pass's own loss, for
    the step metrics). Returns (the sum, aux of sums over the rows)."""
    rows = targets.size
    mask = weights.reshape(-1)
    with scope("exit_gate"):
        log_p = exit_log_distribution(gate_logits).reshape(len(states), rows)
        p = jnp.exp(log_p)
        entropy = jnp.sum(mask * exit_entropy(log_p))
        steps = jnp.arange(1, len(states) + 1, dtype=jnp.float32)
        exit_step = jnp.sum(mask * jnp.sum(steps[:, None] * p, axis=0))
    sums = jnp.stack([
        blocked_cross_entropy(h.reshape(rows, -1), head_w, targets.reshape(-1),
                              jnp.stack([p[t] * mask, mask], axis=-1))
        for t, h in enumerate(states)])
    aux = {"loop_ce_sum": sums[:, 1], "loop_exit_step_sum": exit_step,
           "loop_targets": jnp.sum(mask)}
    return jnp.sum(sums[:, 0]) - beta * entropy, aux


def lm_loss_fn(params, cfg, batch, rng=None):
    """Next-token cross-entropy over the held vocabulary slice: the mean
    over the L - 1 targets of each sequence, then over sequences. A tree
    without a `head` is tied: the logits are against the embedding table,
    whose gradient is then the sum of the lookup's and the head's.
    batch: {"tokens": (B, L) int32}. Returns (loss, {"load": each MoE
    layer's expert load}, empty without MoE layers): what the step sums
    over its microbatches. The looped family's loss is `_expected_loss`'s
    mean over the targets, its aux the sums its step metrics are made of.
    `rng` is unused (no dropout)."""
    tokens = batch["tokens"]
    B, L = tokens.shape
    hidden, aux = decoder_apply(params, cfg, tokens)
    with scope("lm_head_loss"):
        # every position gives a row; the last of each sequence has no
        # target and weighs nothing
        targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        weights = jnp.broadcast_to(
            (jnp.arange(L) < L - 1).astype(jnp.float32), (B, L))
        tied = "head" not in params
        head_w = params["embed"]["table"] if tied else params["head"]["w"]
        if "gate_logits" in aux:  # a looped stack: a list of passes' states
            total, aux = _expected_loss(hidden, aux["gate_logits"],
                                        head_w.astype(cfg.compute_dtype),
                                        targets, weights, cfg.beta)
            return total / (B * (L - 1)), aux
        total = blocked_cross_entropy(
            hidden.reshape(B * L, -1), head_w.astype(cfg.compute_dtype),
            targets.reshape(-1), weights.reshape(-1), tied=tied)
        # the picks are per token: nothing a step sums over microbatches
        return total / (B * (L - 1)), {k: v for k, v in aux.items() if k != "picks"}


def lm_aux_update(cfg):
    """`aux_update` for `make_train_step`: (params after the optimizer,
    aux summed over the step's microbatches) -> (params with each MoE
    layer's selection bias moved, step metrics). A family that has the
    bias keeps it at `moe/mlp/bias` (`zaya`'s one stack is `moe`); one
    whose router has none (`mellum`) has nothing moved. Per MoE layer, a
    row a layer in the published order: assignments held here, the sorted
    rows the expert loop walked for them (`ops.moe.rows_walked`: its live
    blocks, whole) and most-loaded over mean load of the held experts
    (none is dropped: the expert layer has no capacity). A family without
    an expert layer moves nothing; the looped one (`ouro`) reports
    `loop_pass_loss` (T,), each pass's mean next-token loss over the
    targets, and `loop_exit_step_mean`, the mean over the targets of
    sum_t t p(t)."""

    def update(params, aux):
        if "load" not in aux:
            if "loop_ce_sum" not in aux:
                return params, {}
            n = aux["loop_targets"]
            return params, {"loop_pass_loss": aux["loop_ce_sum"] / n,
                            "loop_exit_step_mean": aux["loop_exit_step_sum"] / n}
        lo, hi = cfg.held
        load = aux["load"]  # (n_moe, E)
        mlp = params["moe"]["mlp"]
        if "bias" in mlp:
            moved = bias_update(mlp["bias"], load, cfg.bias_update_rate)
            params = {**params, "moe": {**params["moe"],
                                        "mlp": {**mlp, "bias": moved}}}
        held = load[:, lo:hi]
        metrics = {
            "moe_assignments_held": jnp.sum(held, axis=-1),
            "moe_rows_walked": aux["rows_walked"],
            "moe_load_max_over_mean": jnp.max(held, axis=-1)
            / jnp.maximum(jnp.mean(held, axis=-1), 1.0),
        }
        return params, metrics

    return update


def zipf_token_batches(vocab_size: int, batch: int, length: int, seed: int,
                       start_index: int = 0, exponent: float = 1.0):
    """An endless stream of {"tokens": (batch, length) int32}, batch i a
    pure function of (seed, i): ids drawn from a Zipf law
    p(rank) ~ rank^-exponent over the vocabulary, the rank -> id map a
    permutation drawn from the seed."""
    p = np.arange(1, vocab_size + 1, dtype=np.float64) ** -exponent
    cdf = np.cumsum(p / p.sum())
    ids = np.random.default_rng([seed, 11]).permutation(vocab_size)
    index = start_index
    while True:
        u = np.random.default_rng([seed, 12, index]).random((batch, length))
        ranks = np.minimum(np.searchsorted(cdf, u), vocab_size - 1)
        yield {"tokens": ids[ranks].astype(np.int32)}
        index += 1
