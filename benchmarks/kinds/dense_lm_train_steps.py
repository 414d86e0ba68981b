"""A language-model training cell whose decoder has NO expert layer (the
builder's family: `ouro`, the looped one): the run of
`kinds/lm_train_steps_by_builder.py` (one donated jitted optimizer step
per call on a fresh token batch from the seed, the first `check_steps`
through the window's own call, `common.timed_window`, then the builder's
plain reference over the same first steps) with nothing of a router in
it: no picks, no `expert_window`, no `route_mismatch_share`, none of the
load's facts. `lm_train_steps_by_builder` reads them unconditionally and
ends in a `KeyError` on such a model, so this is a kind of its own; a
later PR may fold the two into one kind that reads the expert loop only
where there is one.

What it takes from the other two kinds: the feed, the runner with its
faults (`--fault half_batch`, `state_unchanged`) and the first steps from
`kinds/lm_train_steps.py`; the weights by the builder's `leaf_rule`
(`Weights`), the reference module and its `hp` from
`kinds/lm_train_steps_by_builder.py`. The builder hands `cfg`, `tcfg`,
`reference`, `reference_hp`, `leaf_rule` and `dry_scopes`; its reference
offers `unstack`, `value_and_grad_layers` (returning (loss, grads, aux)
with aux {"pass_loss": (T,), "exit_step_mean"}), `stacked_norms`,
`adam_init`, `train_step_layers`.

What `correct` holds (`compared_numbers`), from the timed call's first two
steps at the timed sizes: `loss1_gap`, `loss2_gap`, `grad_gap` (the worst
leaf of the larger half), `change_gap`, `pass_loss_gap` (the worst over
the passes of the relative gap of step 1's mean CE of a pass; a pass the
program did not run reads 1) and `exit_gap` (the relative gap of step 1's
mean exit step), and non-finite losses.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import common
import compare
from common import log
from kinds import lm_train_steps
from kinds.lm_train_steps import build_runner, cell_batch, memory_line, shape_of
from kinds.lm_train_steps_by_builder import Weights, reference_hp, reference_of

#: the step metrics of the looped family that `correct` reads at step 1
LOOP_METRICS = ("loop_pass_loss", "loop_exit_step_mean")


def first_steps(runner, weights, n):
    """`lm_train_steps.first_steps`, keeping the first step's loop metrics:
    the program's `pass_loss` and `exit_step` of step 1."""
    import jax

    kept, step = [], runner.step

    def step_and_keep():
        out = step()
        kept.append(runner.metrics)
        return out

    runner.step = step_and_keep
    try:
        first = lm_train_steps.first_steps(runner, weights, n)
    finally:
        runner.step = step
    loop = jax.device_get({k: kept[0][k] for k in LOOP_METRICS})
    return {**first, "pass_loss": np.asarray(loop["loop_pass_loss"]).tolist(),
            "exit_step": float(loop["loop_exit_step_mean"])}


def follow_reference(ctx, weights, batches, q=None):
    """The builder's plain reference over the same first steps from the
    same weights: losses, step 1's pass losses and mean exit step, the first
    gradient's norms, change norms. As `lm_train_steps_by_builder
    .follow_reference`, without picks or load."""
    import jax

    ref, hp = reference_of(ctx), reference_hp(ctx)

    def leaves(tree):
        return jax.tree_util.tree_leaves(tree)

    def fresh():
        return ref.unstack(weights())

    outer, layers, kinds = fresh()
    memory_line(ctx["devices"], "reference's weights")
    opt = None
    out = {"losses": []}
    for i, tokens in enumerate(batches):
        t_step = time.perf_counter()
        value, grads, aux = ref.value_and_grad_layers(
            outer, layers, kinds, jax.device_put(tokens), hp, q)
        out["losses"].append(float(value))
        log(f"reference step {i + 1}: {time.perf_counter() - t_step:.1f} s")
        if i == 0:
            out["grad"] = leaves(ref.stacked_norms(grads[0], grads[1], kinds))
            out["pass_loss"] = np.asarray(aux["pass_loss"]).tolist()
            out["exit_step"] = float(aux["exit_step_mean"])
        opt = ref.adam_init((outer, layers)) if opt is None else jax.device_put(opt)
        (outer, layers), opt = ref.train_step_layers(outer, layers, kinds, opt, grads, hp)
        del grads
        if i + 1 < len(batches):
            opt = jax.device_get(opt)  # off the device for the next gradient
    del opt
    outer0, layers0, _ = fresh()
    sub = jax.jit(lambda a, b: jax.tree_util.tree_map(lambda x, y: x - y, a, b))
    change = ref.stacked_norms(
        sub(outer, outer0), [sub(a, b) for a, b in zip(layers, layers0)], kinds)
    out["change"] = leaves(change)
    return out


def pass_loss_gap(prog, ref) -> float:
    """The worst relative gap over the reference's passes of step 1's mean
    CE; a pass the program did not run (or ran beyond them) reads 1."""
    if len(prog) != len(ref):
        return 1.0
    return max(compare.rel(a, b) for a, b in zip(prog, ref))


def compared_numbers(prog, ref, names):
    """{name: value} of every number `correct` holds, and which leaf."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        out[f"loss{i + 1}_gap"] = compare.rel(a, b)
    gaps = compare.leaf_gaps(prog["grad"], ref["grad"])
    log(f"grad_gap over all leaves (not held): {max(gaps):.6g}")
    gap, where = compare.worst_leaf_gap(prog["grad"], ref["grad"],
                                        compare.larger_half(ref["grad"]))
    out["grad_gap"] = gap
    log(f"worst gradient leaf of the larger half: {names[where]} prog "
        f"{prog['grad'][where]:.6g} ref {ref['grad'][where]:.6g}")
    keep = compare.moved_leaves(ref["grad"])
    gap, where = compare.worst_leaf_gap(prog["change"], ref["change"], keep)
    out["change_gap"] = gap
    log(f"worst change leaf: {names[where]} prog {prog['change'][where]:.6g} "
        f"ref {ref['change'][where]:.6g}; {sum(keep)} of {len(keep)} leaves held")
    log(f"step 1 pass losses: prog {prog['pass_loss']} ref {ref['pass_loss']}; "
        f"mean exit step: prog {prog['exit_step']:.6f} ref {ref['exit_step']:.6f}")
    out["pass_loss_gap"] = pass_loss_gap(prog["pass_loss"], ref["pass_loss"])
    out["exit_gap"] = compare.rel(prog["exit_step"], ref["exit_step"])
    return out


def control(ctx, q):
    """The control's numbers: the reference with `q` on every operand put
    in the program's place, against the reference itself."""
    weights = Weights(ctx)
    batches = [cell_batch(ctx, i) for i in range(ctx["traffic"]["check_steps"])]
    ref = follow_reference(ctx, weights, batches)
    ctl = follow_reference(ctx, weights, batches, q)
    log("losses control", ctl["losses"], "reference", ref["losses"])
    return compared_numbers(ctl, ref, compare.leaf_paths(weights.shapes))


def loop_facts(cfg, metrics) -> dict:
    """The loop's counters as facts of the run, from step metrics (the
    traced steps', else the window's last step's): passes, layer
    applications a step, each pass's mean loss, the mean exit step."""
    import jax

    got = jax.device_get([{k: m[k] for k in LOOP_METRICS} for m in metrics])
    passes = len(got[0]["loop_pass_loss"])
    return {"loop_passes": passes, "loop_layer_applications": passes * cfg.layers,
            "loop_pass_loss": np.mean([g["loop_pass_loss"] for g in got], axis=0).tolist(),
            "loop_exit_step_mean": float(np.mean([g["loop_exit_step_mean"] for g in got]))}


def dry_facts(config, traffic):
    """Facts of the shape `run()` hands on, at the toy sizes of `--dry`
    with made-up times and a made-up scope table of the builder's scopes:
    what the tests of the result line give the readers."""
    built = common.module("builders", config["builder"]).build(config, True)
    cfg = built["cfg"]
    batch, length = traffic["dry"]["batch"], traffic["dry"]["length"]
    return common.made_up_facts(
        built["dry_scopes"],
        {"forward": 0.01, "reconstruct": 0.0, "remat": 0.01, "backward": 0.02,
         "other": 0.0},
        model_cfg=cfg, lm_shape=(batch, length), trace_steps=traffic["trace_steps"],
        loop_passes=cfg.total_ut_steps,
        loop_layer_applications=cfg.total_ut_steps * cfg.layers,
        loop_pass_loss=[5.6] * cfg.total_ut_steps, loop_exit_step_mean=1.9)


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

    traced_metrics = []
    window = common.timed_window(
        ctx, runner, after_traced_step=lambda: traced_metrics.append(runner.metrics))
    steps, losses = window["steps"], window.pop("losses")
    loop = loop_facts(ctx["built"]["cfg"], traced_metrics or [runner.metrics])
    log(f"loop: {loop['loop_passes']} passes, {loop['loop_layer_applications']} layer "
        f"applications a step; pass losses {loop['loop_pass_loss']}, mean exit step "
        f"{loop['loop_exit_step_mean']:.4f}")

    planned = common.planned_peak(runner.compiled)
    device = common.device_block(ctx["devices"], planned)
    names = compare.leaf_paths(runner.state["params"])
    from alphafold2_tpu.ops import dispatch

    log("dispatch decisions:", dispatch.decisions())
    runner.state = runner.compiled = runner.metrics = None
    del traced_metrics
    gc.collect()
    memory_line(ctx["devices"], "program's state was dropped")

    t_ref = time.perf_counter()
    ref_first = follow_reference(ctx, weights, prog_first["batches"])
    log(f"reference: {n_check} steps in {time.perf_counter() - t_ref:.1f} s")
    log("losses program", prog_first["losses"], "reference", ref_first["losses"])
    values = compared_numbers(prog_first, ref_first, names)
    finite = all(np.isfinite(losses))
    values["nonfinite_losses"] = 0.0 if finite else 1.0
    correct, rows = common.judge_values(values, ctx["limits"])

    facts = {
        **setup_facts, **window, "model_cfg": ctx["built"]["cfg"],
        "lm_shape": shape_of(ctx), "planned_hbm_bytes": planned, **loop,
    }
    return {"correct": correct, "attempted": steps + n_check, "failed": 0 if finite else 1,
            "facts": facts, "device": device, "compared": rows}
