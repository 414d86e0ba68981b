"""A language-model training cell whose decoder family is the BUILDER's:
the run of `kinds/lm_train_steps.py` (one donated jitted optimizer step
per call on a fresh token batch from the seed, the first `check_steps`
through the window's own call, `common.timed_window`, then the plain
reference over the same first steps), with everything that belongs to one
family read from what `builders/<builder>.py build()` returns, so that
the next decoder brings a builder and a reference and no third kind:

  reference      the module under `reference/` that follows the steps (it
                 offers `unstack`, `value_and_grad_layers`, `stacked_norms`,
                 `adam_init`, `train_step_layers`, as `decoder_lm.py` does)
  reference_hp   (cfg, tcfg) -> that reference's `hp`, less the blocks
                 (the configuration file's `reference`, 0 in a dry run)
  picks          (params, cfg, tokens) -> (layers, tokens, top_k): the
                 program's forward that returns the router's picks
  leaf_rule      (path, assumed_values) -> ("normal", std) or
                 ("constant", value): how the harness draws each leaf of
                 the seed's weights (only the layout comes from the program)
  dry_scopes     the scope keys `dry_facts()` makes up times for

What `correct` holds is `lm_train_steps`' own: `loss1_gap` / `loss2_gap`,
`grad_gap`, `change_gap`, `route_mismatch_share` (`compared_numbers`),
non-finite losses, from the timed call's first two steps at the timed
sizes. The feed (`cell_batch`: the id map from `common.weights_seed`, the
batches from `--seed`), the runner with its faults
(`build_runner` -> `Runner`), the first steps, the comparison (with
`route_mismatch_share`) and the window's expert loop (`expert_window`) are
imported from there; what is here is what named MLA's reference.
"""
from __future__ import annotations

import gc
import importlib
import time

import numpy as np

import common
import compare
from common import log
from kinds.lm_train_steps import (build_runner, cell_batch, compared_numbers,
                                  expert_window, first_steps, made_up_expert_window,
                                  memory_line, shape_of)


def reference_of(ctx):
    return importlib.import_module("reference." + ctx["built"]["reference"])


def reference_hp(ctx) -> dict:
    built = ctx["built"]
    blocks = ({"attn_block": 0, "ff_block": 0, "loss_block": 0} if ctx["dry"]
              else ctx["config"]["reference"])
    return {**built["reference_hp"](built["cfg"], built["tcfg"]), **blocks}


def param_maker(shapes, assumed: dict, leaf_rule):
    """The jitted key -> weights on the device, each leaf by the builder's
    `leaf_rule`. `shapes` is a tree of ShapeDtypeStructs: the layout the
    program reads, and nothing else of it."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rules = [leaf_rule([common.key_name(k) for k in path], assumed)
             for path, _ in leaves]

    def build(key):
        out = []
        for i, ((_, leaf), (how, value)) in enumerate(zip(leaves, rules)):
            if how == "normal":
                v = value * jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                              jnp.float32)
            else:
                v = jnp.full(leaf.shape, value, jnp.float32)
            out.append(v.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)


class Weights:
    """The cell's weights by `param_maker` from `common.weights_seed`, made
    again whenever asked: no second copy lives through the window."""

    def __init__(self, ctx):
        import jax

        from alphafold2_tpu.training.lm import lm_params_init

        cfg = ctx["built"]["cfg"]
        self.key = common.seed_key(common.weights_seed(ctx))
        self.shapes = jax.eval_shape(lambda k: lm_params_init(k, cfg), self.key)
        self.make = param_maker(self.shapes, ctx["config"]["assumed_values"],
                                ctx["built"]["leaf_rule"])

    def __call__(self):
        return self.make(self.key)


def program_picks(ctx, weights, tokens):
    """The experts the program's router picks for `tokens` on the cell's
    weights: the builder's forward at the timed sizes and precision,
    outside the timed step."""
    import jax

    built = ctx["built"]
    picks = jax.jit(lambda p, t: built["picks"](p, built["cfg"], t))(
        weights(), jax.device_put(tokens))
    return np.asarray(picks)


def follow_reference(ctx, weights, batches, q=None):
    """The builder's plain reference over the same first steps from the
    same weights: losses, the first step's picks, first gradient's norms,
    change norms. As `lm_train_steps.follow_reference`: the UNSTACKED
    parameters, Adam's moments on the host while a gradient is computed."""
    import jax

    ref, hp = reference_of(ctx), reference_hp(ctx)

    def leaves(tree):
        return jax.tree_util.tree_leaves(tree)

    def fresh():
        return ref.unstack(weights())

    outer, layers, kinds = fresh()
    memory_line(ctx["devices"], "reference's weights")
    opt = None
    losses, picks, grad = [], None, None
    for i, tokens in enumerate(batches):
        t_step = time.perf_counter()
        value, grads, idx, load = ref.value_and_grad_layers(
            outer, layers, kinds, jax.device_put(tokens), hp, q)
        losses.append(float(value))
        log(f"reference step {i + 1}: {time.perf_counter() - t_step:.1f} s")
        if i == 0:
            picks = np.asarray(idx)
            grad = leaves(ref.stacked_norms(grads[0], grads[1], kinds))
        opt = ref.adam_init((outer, layers)) if opt is None else jax.device_put(opt)
        (outer, layers), opt = ref.train_step_layers(
            outer, layers, kinds, opt, grads, load, hp)
        del grads
        if i + 1 < len(batches):
            opt = jax.device_get(opt)  # off the device for the next gradient
    del opt
    outer0, layers0, _ = fresh()
    sub = jax.jit(lambda a, b: jax.tree_util.tree_map(lambda x, y: x - y, a, b))
    change = ref.stacked_norms(
        sub(outer, outer0), [sub(a, b) for a, b in zip(layers, layers0)], kinds)
    return {"losses": losses, "picks": picks, "grad": grad, "change": leaves(change)}


def control(ctx, q):
    """The control's numbers: the reference with `q` on every operand put
    in the program's place, against the reference itself."""
    weights = Weights(ctx)
    batches = [cell_batch(ctx, i) for i in range(ctx["traffic"]["check_steps"])]
    ref = follow_reference(ctx, weights, batches)
    ctl = follow_reference(ctx, weights, batches, q)
    log("losses control", ctl["losses"], "reference", ref["losses"])
    return compared_numbers(ctl, ref, compare.leaf_paths(weights.shapes))


def dry_facts(config, traffic):
    """Facts of the shape `run()` hands on, at the toy sizes of `--dry`
    with made-up times and a made-up scope table of the builder's scopes:
    what the tests of the result line give the readers."""
    built = common.module("builders", config["builder"]).build(config, True)
    batch, length = traffic["dry"]["batch"], traffic["dry"]["length"]
    return common.made_up_facts(
        built["dry_scopes"],
        {"forward": 0.01, "reconstruct": 0.0, "remat": 0.01, "backward": 0.02,
         "other": 0.0},
        model_cfg=built["cfg"], lm_shape=(batch, length),
        trace_steps=traffic["trace_steps"],
        assignments_held=0.5 * batch * length, moe_load_max_over_mean=1.3,
        **made_up_expert_window(built["cfg"], (batch, length)))


def run(ctx):
    setup, traffic = ctx["setup"], ctx["traffic"]
    weights = Weights(ctx)
    runner = build_runner(ctx, setup, weights)
    n_check = traffic["check_steps"]
    prog_first = first_steps(runner, weights, n_check)
    # the two small reductions above compile once; run the first again so
    # that nothing is left to compile in the window
    compare.norms(compare.find_mu(runner.state["opt_state"]))
    setup.mark("first_steps_through_the_timed_call")
    log("setup phases (s):", setup.table())
    setup_facts = setup.facts()

    held_at_check = np.asarray(runner.metrics["moe_assignments_held"]).tolist()

    traced_metrics, window_metrics = [], []
    window = common.timed_window(
        ctx, runner, after_traced_step=lambda: traced_metrics.append(runner.metrics),
        after_timed_step=lambda: window_metrics.append(runner.metrics))
    steps, losses = window["steps"], window.pop("losses")
    experts = expert_window(window_metrics)

    # the router's own counts: the traced steps' where there are any, else
    # the window's last step
    counted = traced_metrics or [runner.metrics]
    held = float(np.mean([np.asarray(m["moe_assignments_held"]) for m in counted]))
    skew = float(np.mean([np.asarray(m["moe_load_max_over_mean"]) for m in counted]))
    log(f"expert load: {held:.1f} assignments held a MoE layer, most-loaded over "
        f"mean {skew:.4f}; by layer at check step {n_check} {held_at_check}, at the "
        f"window's last step {np.asarray(runner.metrics['moe_assignments_held']).tolist()}")

    planned = common.planned_peak(runner.compiled)
    device = common.device_block(ctx["devices"], planned)
    names = compare.leaf_paths(runner.state["params"])
    from alphafold2_tpu.ops import dispatch

    log("dispatch decisions:", dispatch.decisions())
    runner.state = runner.compiled = runner.metrics = None
    del counted, traced_metrics, window_metrics
    gc.collect()
    memory_line(ctx["devices"], "program's state was dropped")

    t_ref = time.perf_counter()
    prog_first["picks"] = program_picks(ctx, weights, prog_first["batches"][0])
    log(f"program's picks of step 1: {time.perf_counter() - t_ref:.1f} s")
    ref_first = follow_reference(ctx, weights, prog_first["batches"])
    log(f"reference: {n_check} steps in {time.perf_counter() - t_ref:.1f} s")
    log("losses program", prog_first["losses"], "reference", ref_first["losses"])
    values = compared_numbers(prog_first, ref_first, names)
    finite = all(np.isfinite(losses))
    values["nonfinite_losses"] = 0.0 if finite else 1.0
    correct, rows = common.judge_values(values, ctx["limits"])

    facts = {
        **setup_facts, **window, "model_cfg": ctx["built"]["cfg"],
        "lm_shape": shape_of(ctx), "planned_hbm_bytes": planned,
        "assignments_held": held, "moe_load_max_over_mean": skew, **experts,
    }
    return {"correct": correct, "attempted": steps + n_check, "failed": 0 if finite else 1,
            "facts": facts, "device": device, "compared": rows}
