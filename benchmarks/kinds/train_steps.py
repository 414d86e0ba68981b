"""A training cell: one donated jitted optimizer step per call on fresh
examples from the seed, the loss fetched every step, until `--seconds`
have passed (`common.timed_window`). `train_step_s` is the whole window
over all its steps.

Set-up builds ONE object, the compiled step with its state, drives it
through its first `check_steps` steps by the window's own call and feed,
and hands the same object to the window. After the window the plain
reference follows those first steps from the same weights and examples.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import common
import compare
import traffic_gen
from common import log


class Runner:
    """The timed path: the compiled step, its state, its feed."""

    def __init__(self, ctx, loss, prog, state, compiled, shape):
        self.ctx, self.loss, self.prog = ctx, loss, prog
        self.state, self.compiled, self.shape = state, compiled, shape
        self.index = 0
        self.dispatched_at = 0.0

    def feed(self):
        import jax

        batch = traffic_gen.train_batch(self.shape, self.ctx["seed"], self.index)
        self.index += 1
        return batch, jax.device_put(self.loss.program_batch(batch))

    def step(self):
        """One optimizer step through the compiled, donated call; returns
        the host example and the fetched loss."""
        import jax

        batch, dev = self.feed()
        rng = jax.random.fold_in(jax.random.PRNGKey(1), self.index)
        if self.ctx.get("fault") == "state_unchanged":  # tests only
            kept = jax.tree_util.tree_map(lambda t: t.copy(), self.state)
            _, metrics = self.compiled(self.state, dev, rng)
            self.state = kept
        else:
            self.state, metrics = self.compiled(self.state, dev, rng)
        self.dispatched_at = time.perf_counter()
        return batch, float(np.asarray(metrics["loss"]))


def example_shape(built, traffic):
    """What `traffic_gen.train_batch` needs to know of the cell."""
    return {"crop": built["crop"], "msa_rows": built["msa_rows"],
            "atoms_per_residue": traffic["atoms_per_residue"]}


def build_runner(ctx, setup):
    import jax
    import jax.numpy as jnp

    traffic, built = ctx["traffic"], ctx["built"]
    loss = common.module("losses", traffic["loss"])
    prog = loss.program(built)
    shape = example_shape(built, traffic)
    params, params0 = common.make_params(
        prog["param_shapes"], common.seed_key(ctx["seed"]), stacked=prog["stacked"],
        copies=2)
    state = {"params": params,
             "opt_state": jax.jit(prog["optimizer"].init)(params),
             "step": jnp.zeros((), jnp.int32)}
    jax.block_until_ready(state)
    setup.mark("weights_and_state_on_device")
    example = loss.program_batch(traffic_gen.train_batch(shape, ctx["seed"], 0))
    compiled = (jax.jit(prog["step"], donate_argnums=(0,))
                .lower(state, example, jax.random.PRNGKey(1)).compile())
    setup.mark("trace_and_compile_or_cache_load")
    return Runner(ctx, loss, prog, state, compiled, shape), params0


def first_steps(runner, params0, n):
    """The first n steps through the window's own call: each loss, the
    first gradient's norms (from Adam's first moment after one step: mu =
    (1 - b1) g), and the norms of the parameters' change after n."""
    import jax

    batches, losses, grad = [], [], None
    for i in range(n):
        t_step = time.perf_counter()
        batch, value = runner.step()
        log(f"first step {i + 1} through the timed call: {time.perf_counter() - t_step:.4f} s")
        batches.append(batch)
        losses.append(value)
        if i == 0:
            mu = compare.find_mu(runner.state["opt_state"])
            grad = [g / 0.1 for g in compare.norms(mu)]
    change = compare.delta_norms(runner.state["params"], params0)
    jax.block_until_ready(runner.state)
    return {"batches": batches, "losses": losses, "grad": grad, "change": change}


def follow_reference(ctx, loss, params0, batches, q=None):
    """The plain reference over the same first steps from the same
    weights: losses, first gradient's norms, change norms."""
    import jax

    from reference import af2

    blocks = {"attn_block": 0, "ff_block": 0, "cross_block": 0, "atom_block": 0}
    if not ctx["dry"]:
        blocks = dict(ctx["config"]["reference"])
        af2.set_precision(blocks.pop("precision", "highest"))
    hp = loss.reference_hp(ctx["built"], blocks)
    lr = ctx["built"]["tcfg"].learning_rate
    params, opt = params0, af2.adam_init(params0)
    losses, grad = [], None
    for i, batch in enumerate(batches):
        dev = {k: jax.device_put(v) for k, v in batch.items()}
        t_step = time.perf_counter()
        value, grads = loss.reference_value_and_grad(params, dev, hp, q)
        losses.append(float(value))
        log(f"reference step {i + 1}: {time.perf_counter() - t_step:.1f} s")
        if i == 0:
            grad = compare.norms(grads)
        params, opt = af2.adam_step(params, grads, opt, lr)
    return {"losses": losses, "grad": grad,
            "change": compare.delta_norms(params, params0)}


def gradient_numbers(prog_grad, ref_grad, names):
    """The first gradient's two numbers from every leaf's norm on both
    sides. `grad_gap`: the worst of the leaves whose reference norm is the
    median leaf's or more: a widest gap, which swings from seed to seed
    with the few leaves whose gradient is a sum of cancelling terms (the
    two position tables, the refiner's scalar biases: PERF.md section 2).
    `grad_gap_median`: the median used leaf's gap, steady from seed to seed
    and two hundred times under what the control reads. The worst of ALL
    leaves is printed beside them."""
    gaps = compare.leaf_gaps(prog_grad, ref_grad)
    for i in sorted(range(len(gaps)), key=lambda i: -gaps[i])[:4]:
        log(f"gradient leaf {names[i]}: gap {gaps[i]:.4g} prog {prog_grad[i]:.6g} "
            f"ref {ref_grad[i]:.6g}")
    live = sorted(g for g, r in zip(gaps, ref_grad) if r > 0)
    log(f"grad_gap over all leaves (not held): {max(gaps):.6g}; median leaf's gap "
        f"{live[len(live) // 2]:.6g}")
    gap, where = compare.worst_leaf_gap(prog_grad, ref_grad,
                                        compare.larger_half(ref_grad))
    log(f"worst gradient leaf of the larger half: {names[where]} prog "
        f"{prog_grad[where]:.6g} ref {ref_grad[where]:.6g}")
    return {"grad_gap": gap, "grad_gap_median": live[len(live) // 2]}


def compared_numbers(prog, ref, names):
    """{name: value} of every number `correct` holds, and which leaf."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        out[f"loss{i + 1}_gap"] = compare.rel(a, b)
    out.update(gradient_numbers(prog["grad"], ref["grad"], names))
    keep = compare.moved_leaves(ref["grad"])
    gap, where = compare.worst_leaf_gap(prog["change"], ref["change"], keep)
    out["change_gap"] = gap
    log(f"worst change leaf: {names[where]} prog {prog['change'][where]:.6g} "
        f"ref {ref['change'][where]:.6g}; {sum(keep)} of {len(keep)} leaves held")
    return out


def control(ctx, q):
    """The control's numbers: the reference with `q` on every operand put
    in the program's place, against the reference itself."""
    traffic, built = ctx["traffic"], ctx["built"]
    loss = common.module("losses", traffic["loss"])
    prog = loss.program(built)
    params0 = common.make_params(prog["param_shapes"], common.seed_key(ctx["seed"]),
                                 stacked=prog["stacked"])
    shape = example_shape(built, traffic)
    batches = [traffic_gen.train_batch(shape, ctx["seed"], i)
               for i in range(traffic["check_steps"])]
    ref = follow_reference(ctx, loss, params0, batches)
    ctl = follow_reference(ctx, loss, params0, batches, q)
    log("losses control", ctl["losses"], "reference", ref["losses"])
    return compared_numbers(ctl, ref, compare.leaf_paths(params0))


def dry_facts(config, traffic):
    """Facts of the shape `run()` hands on, at the toy sizes of `--dry`
    with made-up times and a made-up scope table: what the tests of the
    result line give the readers."""
    built = common.module("builders", config["builder"]).build(config, True)
    n = built["crop"] * traffic["atoms_per_residue"]
    return common.made_up_facts(
        ("seq_attn/attn_core", "seq_attn/qkv_proj", "seq_ff/geglu", "seq_ff2/geglu",
         "seq_cross/attn_core", "msa_cross/attn_core", "msa_attn/attn_core",
         "msa_ff/geglu", "msa_ff2/geglu", "trunk", "refiner", "mds"),
        {"forward": 0.01, "reconstruct": 0.01, "remat": 0.005, "backward": 0.02,
         "other": 0.0},
        model_cfg=built["ecfg"].model, grid=(n, built["msa_rows"], built["crop"]),
        trace_steps=traffic["trace_steps"])


def run(ctx):
    setup, traffic = ctx["setup"], ctx["traffic"]
    runner, params0 = build_runner(ctx, setup)
    n_check = traffic["check_steps"]
    prog_first = first_steps(runner, params0, n_check)
    # the two small reductions above compile once; run the first again so
    # that nothing is left to compile in the window
    compare.norms(compare.find_mu(runner.state["opt_state"]))
    setup.mark("first_steps_through_the_timed_call")
    log("setup phases (s):", setup.table())
    setup_facts = setup.facts()

    window = common.timed_window(ctx, runner)
    steps, losses = window["steps"], window.pop("losses")

    planned = common.planned_peak(runner.compiled)
    device = common.device_block(ctx["devices"], planned)
    names = compare.leaf_paths(runner.state["params"])
    model_cfg, shape = runner.prog["model_cfg"], runner.shape
    runner.state = None
    runner.compiled = None
    gc.collect()

    t_ref = time.perf_counter()
    ref_first = follow_reference(ctx, runner.loss, params0, prog_first["batches"])
    log(f"reference: {n_check} steps in {time.perf_counter() - t_ref:.1f} s")
    log("losses program", prog_first["losses"], "reference", ref_first["losses"])
    values = compared_numbers(prog_first, ref_first, names)
    finite = all(np.isfinite(losses))
    values["nonfinite_losses"] = 0.0 if finite else 1.0
    correct, rows = common.judge_values(values, ctx["limits"])

    n = shape["crop"] * shape["atoms_per_residue"]
    facts = {
        **setup_facts, **window, "model_cfg": model_cfg,
        "grid": (n, shape["msa_rows"], shape["crop"]),
        "planned_hbm_bytes": planned,
    }
    return {"correct": correct, "attempted": steps + n_check, "failed": 0 if finite else 1,
            "facts": facts, "device": device, "compared": rows}
