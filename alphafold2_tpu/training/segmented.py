"""Multi-execution (segmented) end-to-end train step.

Built for a runtime that bounded the device time of one execution, which
the north-star depth-48 step (~96 s in one program) exceeded. A plain
TPU host has no such limit, so on it this is a duplicate of the
monolithic step and a CANDIDATE FOR REMOVAL: it goes (with
`bench.py --segments` and `train_end2end.py --trunk-segments`) once a
benchmark cell shows the monolithic depth-48 step runs on the chip
(ROADMAP.md D2, R1). Until then it is the only depth-48 path with a
chip record. This module runs the SAME optimizer step as
`make_train_step(e2e_loss_fn)` but as a chain of short device
executions, exploiting the reversible trunk's defining property: the
backward reconstructs each segment's input state from its output state,
so NO inter-segment activations are ever stored — the host passes one
(x1, x2, m1, m2) boundary between executions and nothing else.

Execution chain per optimizer step (each < ~depth/segments layer-costs):

  front      embeddings + template tower -> (x, m) and masks
  seg_fwd*K  reversible segments forward (state4 -> state4)
  tail       (z-streams mean) -> head -> distogram -> MDS -> sidechain ->
             refiner -> Kabsch loss, with value_and_grad wrt head params,
             refiner params, AND the trunk output state
  seg_bwd*K  reverse: reconstruct segment input state + propagate
             cotangents + per-segment trunk param grads
  front_bwd  vjp of front wrt model params (embeddings, template tower)
  opt        assemble grads, optax update (the same FIXED-ARITY chain as
             harness.make_optimizer), step += 1

Numerics are IDENTICAL to the monolithic step by construction: the same
`_layer_forward`/`_layer_backward` bodies run with the same global layer
indices (dropout keys are `fold_in(rng_trunk, layer)` — offset is passed
as a traced operand so equal-length segments share one compiled
executable), and the rng split chain mirrors
harness.make_train_step -> e2e_loss_fn -> predict_structure exactly.
Parity is pinned by tests/test_segmented.py.

Limitations: requires `cfg.reversible` and an MSA stream (the reversible
trunk's own requirements). The step is a HOST-LEVEL callable — it cannot
be jitted as a whole (that would defeat its purpose); each piece is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from alphafold2_tpu.models import alphafold2_front, alphafold2_head
from alphafold2_tpu.models.reversible import (
    _layer_backward,
    _layer_forward,
    _num_layers,
    _op_rngs,
    uniform_flag_runs,
)
from alphafold2_tpu.training.e2e import E2EConfig, elongate, make_e2e_loss_fn
from alphafold2_tpu.training.harness import TrainConfig, make_optimizer


def plan_segments(layer_sparse, n_segments: int):
    """Split the depth into <= n_segments-per-uniform-run chunks.

    Segment boundaries must respect uniform sparse-flag runs (the scan
    body is specialized on the flag; the run computation is shared with
    the reversible trunk). Returns [(start, end, flag), ...].
    """
    depth = len(layer_sparse)
    target = max(1, -(-depth // max(1, n_segments)))  # ceil
    runs = uniform_flag_runs(layer_sparse)
    segments = []
    for run_start, run_end in runs:
        pos = run_start
        while pos < run_end:
            end = min(pos + target, run_end)
            segments.append((pos, end, layer_sparse[pos]))
            pos = end
    return segments


def _seg_fwd(cfg, sparse, seg_params, state4, x_mask, m_mask, rng, offset):
    def body(carry, inp):
        lp, li = inp
        return (
            _layer_forward(cfg, lp, carry, x_mask, m_mask,
                           _op_rngs(rng, li), sparse),
            None,
        )

    L = _num_layers(seg_params)
    carry, _ = jax.lax.scan(
        body, state4, (seg_params, offset + jnp.arange(L))
    )
    return carry


def _seg_bwd(cfg, sparse, seg_params, state4_end, cts4, x_mask, m_mask, rng,
             offset):
    def body(carry, inp):
        state, dstate = carry
        lp, li = inp
        state, dstate, dlp = _layer_backward(
            cfg, lp, state, dstate, x_mask, m_mask, _op_rngs(rng, li), sparse
        )
        return (state, dstate), dlp

    L = _num_layers(seg_params)
    (state4_start, cts4_start), dseg = jax.lax.scan(
        body, (state4_end, cts4), (seg_params, offset + jnp.arange(L)),
        reverse=True,
    )
    return state4_start, cts4_start, dseg


def _jit_static_sparse(fn):
    """jit with the leading `sparse` flag static (it selects the scan
    body); everything else traced — offsets included, so equal-length
    segments reuse one executable."""
    return jax.jit(fn, static_argnums=(0,))


def make_segmented_train_step(
    ecfg: E2EConfig, tcfg: TrainConfig, trunk_segments: int
):
    """Host-level train step running as a chain of short device executions.

    Same contract as `make_train_step(ecfg, tcfg, loss_fn=e2e_loss_fn)`:
    `step(state, batch, rng) -> (new_state, {"loss", "grad_norm"})`, with
    `batch` carrying the leading (grad_accum) microbatch axis. The
    returned state pytree is structurally identical (checkpoint compat).
    """
    cfg = ecfg.model
    if not cfg.reversible:
        raise ValueError("the segmented step requires cfg.reversible=True "
                         "(segment backward IS reversible reconstruction)")
    segments = plan_segments(cfg.layer_sparse, trunk_segments)
    opt = make_optimizer(tcfg)

    # --- jitted pieces (compiled once per shape/static combination) -------

    @jax.jit
    def front_fwd(model_params, seq3, msa, mask3, msa_mask, embedds,
                  rng_model):
        return alphafold2_front(
            model_params, cfg, seq3, msa, mask=mask3, msa_mask=msa_mask,
            embedds=embedds, rng=rng_model,
        )

    # sparse flag is static (different scan body); offset is traced so all
    # equal-length segments of a run share ONE executable
    @_jit_static_sparse
    def seg_fwd(sparse, seg_params, state4, x_mask, m_mask, rng, offset):
        return _seg_fwd(cfg, sparse, seg_params, state4, x_mask, m_mask,
                        rng, offset)

    @_jit_static_sparse
    def seg_bwd(sparse, seg_params, state4_end, cts4, x_mask, m_mask, rng,
                offset):
        return _seg_bwd(cfg, sparse, seg_params, state4_end, cts4, x_mask,
                        m_mask, rng, offset)

    @jax.jit
    def tail_vg(head_params, refiner_params, state4, mb, rng_loss):
        def tail_loss(hp, rp, s4):
            z1, z2, o1, o2 = s4
            xm = (z1 + z2) * 0.5

            def apply_stub(p, c, s, msa, **kw):
                return alphafold2_head(hp, c, xm)

            lf = make_e2e_loss_fn(model_apply_fn=apply_stub)
            return lf({"model": {}, "refiner": rp}, ecfg, mb, rng_loss)

        return jax.value_and_grad(tail_loss, argnums=(0, 1, 2))(
            head_params, refiner_params, state4
        )

    # keys the front never reads — differentiating over them would
    # materialize a trunk-grad-sized ZERO cotangent buffer alongside the
    # real trunk grads (at depth 48 that is a whole extra trunk in HBM)
    _NON_FRONT_KEYS = ("trunk", "head_norm", "head_out")

    @jax.jit
    def front_bwd(model_params, seq3, msa, mask3, msa_mask, embedds,
                  rng_model, dx, dm):
        rest = {k: model_params[k] for k in _NON_FRONT_KEYS
                if k in model_params}
        front_sub = {k: v for k, v in model_params.items()
                     if k not in rest}

        def front_xm(p_sub):
            x, m, *_ = alphafold2_front(
                {**p_sub, **rest}, cfg, seq3, msa, mask=mask3,
                msa_mask=msa_mask, embedds=embedds, rng=rng_model,
            )
            return x, m

        _, vjp = jax.vjp(front_xm, front_sub)
        (d_params,) = vjp((dx, dm))
        return d_params

    @jax.jit
    def accum_grads(a, b):
        return jax.tree_util.tree_map(jnp.add, a, b)

    def _opt_apply(state, grads, loss):
        n = tcfg.grad_accum
        loss = loss / n
        grads = jax.tree_util.tree_map(lambda g: g / n, grads)
        updates, opt_state = opt.update(
            grads, state["opt_state"], state["params"]
        )
        params = optax.apply_updates(state["params"], updates)
        new_state = {
            "params": params,
            "opt_state": opt_state,
            "step": state["step"] + 1,
        }
        return new_state, {"loss": loss,
                           "grad_norm": optax.global_norm(grads)}

    # donate state AND grads: without donation the optimizer execution
    # holds input params+Adam state, the gradients, and the output
    # params+Adam state live at once — at depth 48 that is the two-copy
    # condition bench.py documents as not fitting the chip. Callers must
    # reassign `state = step(state, ...)` (standard donation contract).
    opt_apply = jax.jit(_opt_apply, donate_argnums=(0, 1))

    # --- one microbatch: the execution chain ------------------------------

    def microbatch_grads(params, mb, rng_loss):
        # rng chain mirrors e2e_loss_fn -> predict_structure exactly:
        # rng_loss splits into (model, mds); the tail re-splits the same
        # rng_loss internally, using mds and ignoring model
        rng_model = (
            jax.random.split(rng_loss)[0] if rng_loss is not None else None
        )
        mp = params["model"]
        seq3 = elongate(mb["seq"])
        mask3 = elongate(mb["mask"]) if mb.get("mask") is not None else None
        msa, msa_mask = mb.get("msa"), mb.get("msa_mask")
        embedds = mb.get("embedds")

        x, m, x_mask, m_mask, rng_trunk = front_fwd(
            mp, seq3, msa, mask3, msa_mask, embedds, rng_model
        )
        if m is None:
            raise ValueError("segmented step requires an MSA (or embedds) "
                             "stream — the reversible trunk does")

        def seg_slice(start, end):
            # one SLICE per use, not a held list: keeping every segment's
            # copy alive would duplicate the whole trunk on device
            return jax.tree_util.tree_map(
                lambda t: t[start:end], mp["trunk"]
            )

        state4 = (x, x, m, m)  # channel-double (models/reversible.py)
        for start, end, flag in segments:
            state4 = seg_fwd(flag, seg_slice(start, end), state4, x_mask,
                             m_mask, rng_trunk, jnp.int32(start))

        head_params = {"head_norm": mp["head_norm"],
                       "head_out": mp["head_out"]}
        loss, (d_head, d_refiner, cts4) = tail_vg(
            head_params, params["refiner"], state4, mb, rng_loss
        )

        dsegs = [None] * len(segments)
        for idx in range(len(segments) - 1, -1, -1):
            start, end, flag = segments[idx]
            state4, cts4, dsegs[idx] = seg_bwd(
                flag, seg_slice(start, end), state4, cts4, x_mask, m_mask,
                rng_trunk, jnp.int32(start)
            )

        dx1, dx2, dm1, dm2 = cts4
        d_model = front_bwd(
            mp, seq3, msa, mask3, msa_mask, embedds, rng_model,
            accum_grads(dx1, dx2), accum_grads(dm1, dm2)
        )
        # front_bwd returns only the front-read subtree; fill in the
        # trunk/head grads computed by the segment chain and the tail
        d_model = dict(d_model)
        d_model["trunk"] = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *dsegs
        )
        d_model["head_norm"] = d_head["head_norm"]
        d_model["head_out"] = d_head["head_out"]
        return loss, {"model": d_model, "refiner": d_refiner}

    def step(state, batch, rng=None):
        loss_sum, grad_sum = None, None
        for i in range(tcfg.grad_accum):
            mb = jax.tree_util.tree_map(lambda t: t[i], batch)
            mb_rng = (
                jax.random.fold_in(rng, i) if rng is not None else None
            )
            loss, grads = microbatch_grads(state["params"], mb, mb_rng)
            if grad_sum is None:
                loss_sum, grad_sum = loss, grads
            else:
                loss_sum = loss_sum + loss
                grad_sum = accum_grads(grad_sum, grads)
        return opt_apply(state, grad_sum, loss_sum)

    return step
