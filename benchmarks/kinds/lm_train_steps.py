"""A language-model training cell: one donated jitted optimizer step per
call of the decoder's next-token loss on a fresh token batch from the
seed, the loss fetched every step, until `--seconds` have passed
(`common.timed_window`). `train_step_s` is the whole window over all its
steps.

As `train_steps` does: set-up builds ONE object (the compiled step with
its state), drives it through its first `check_steps` steps by the
window's own call and feed (the warm-up too) and hands the same object to
the window; after the window the plain reference
(`reference/decoder_lm.py`) follows those steps from the same weights and
batches. The weights are drawn HERE (`param_maker`: by each leaf's role,
at the scales the configuration file assumes; only the layout comes from
the program), so that a fault of the program's own init cannot hide on
both sides; `tests/test_lm_cell.py` holds the program's init to the same
scales. They and the rank -> id map are drawn from `common.weights_seed`
(the traffic file's `weights_seed` where it states one: every run of the
cell trains one model), the batches from `--seed`. What differs from
`train_steps`: NO second copy of the weights sits on the device through
the window (the same call makes them again when the change and the
reference need them); the router's picks that `route_mismatch_share`
reads come from the program's forward on those weights after the window,
not from the timed step. The window's steps state the rows the expert loop walked and the
assignments it held, a MoE layer each (`moe_rows_walked`,
`moe_assignments_held`): they stay on the device through the window and
are read once after it (`expert_window`).
"""
from __future__ import annotations

import gc
import time

import numpy as np

import common
import compare
from common import log


def id_map(vocab: int, map_seed: int) -> np.ndarray:
    """The rank -> id map: a permutation of the vocabulary drawn from
    `map_seed`."""
    return np.random.default_rng([map_seed, 11]).permutation(vocab)


def token_batch(vocab: int, batch: int, length: int, seed: int, index: int,
                exponent: float, map_seed: int) -> np.ndarray:
    """(batch, length) int32 ids, a function of (seed, index) and the
    rank -> id map: ranks from a Zipf law p(rank) ~ rank^-exponent over
    the vocabulary, drawn from (seed, index); the map `id_map(vocab,
    map_seed)`."""
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -exponent
    cdf = np.cumsum(p / p.sum())
    u = np.random.default_rng([seed, 12, index]).random((batch, length))
    ranks = np.minimum(np.searchsorted(cdf, u), vocab - 1)
    return id_map(vocab, map_seed)[ranks].astype(np.int32)


def shape_of(ctx):
    traffic = ctx["traffic"]
    part = traffic["dry"] if ctx["dry"] else traffic
    return part["batch"], part["length"]


def cell_batch(ctx, index: int) -> np.ndarray:
    """The run's token batch `index`: ranks from `--seed`, ids by the
    cell's map (`common.weights_seed`)."""
    batch, length = shape_of(ctx)
    return token_batch(ctx["built"]["cfg"].vocab_size, batch, length, ctx["seed"], index,
                       ctx["traffic"]["zipf_exponent"], common.weights_seed(ctx))


def reference_hp(ctx) -> dict:
    cfg, config = ctx["built"]["cfg"], ctx["config"]
    blocks = ({"attn_block": 0, "ff_block": 0, "loss_block": 0} if ctx["dry"]
              else config["reference"])
    return {"heads": cfg.num_attention_heads, "nope": cfg.qk_nope_head_dim,
            "rope": cfg.qk_rope_head_dim, "dv": cfg.v_head_dim,
            "lora": cfg.kv_lora_rank, "eps": cfg.rms_norm_eps,
            "theta": float(cfg.rope_theta), "top_k": cfg.num_experts_per_tok,
            "scaling": cfg.routed_scaling_factor, "norm_topk": cfg.norm_topk_prob,
            "held": tuple(cfg.held), "lr": ctx["built"]["tcfg"].learning_rate,
            "bias_rate": cfg.bias_update_rate, **blocks}


#: the projections that end a residual branch: `scaled_init_layers`
#: narrows them (the configuration file's `assumed.initializer`)
_BRANCH_ENDS = ("o", "down")


def param_maker(shapes, assumed: dict):
    """The jitted key -> weights on the device, by each leaf's role and the
    configuration file's `assumed_values`: `table` and `w` N(0,
    initializer_range), the `w` that ends a residual branch (`o`, every
    `down`) N(0, initializer_range / sqrt(2 * scaled_init_layers)),
    RMSNorm `scale` 1, the router's selection `bias` 0. `shapes` is a
    tree of ShapeDtypeStructs: the layout the program reads, and nothing
    else of it."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    std = assumed["initializer_range"]
    narrow = std / (2.0 * assumed["scaled_init_layers"]) ** 0.5

    def build(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            path = [common.key_name(k) for k in path]
            role = path[-1]
            if role in ("table", "w"):
                scale = narrow if role == "w" and path[-2] in _BRANCH_ENDS else std
                v = scale * jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                              jnp.float32)
            elif role == "scale":
                v = jnp.ones(leaf.shape, jnp.float32)
            elif role == "bias":
                v = jnp.zeros(leaf.shape, jnp.float32)
            else:
                raise ValueError(f"no rule for parameter leaf {'/'.join(path)}")
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
        self.make = param_maker(self.shapes, ctx["config"]["assumed_values"])

    def __call__(self):
        return self.make(self.key)


class Runner:
    """The timed path: the compiled step, its state, its feed."""

    def __init__(self, ctx, state, compiled):
        self.ctx, self.state, self.compiled = ctx, state, compiled
        self.batch = shape_of(ctx)[0]
        self.index = 0
        self.dispatched_at = 0.0
        self.metrics = None

    def feed(self):
        import jax

        tokens = cell_batch(self.ctx, self.index)
        self.index += 1
        fed = tokens
        if self.ctx.get("fault") == "half_batch":  # tests only
            # the timed path trains on the first half of its sequences
            # alone (twice); the reference is handed the whole batch
            half = tokens[:self.batch // 2]
            fed = np.concatenate([half, half])
        return tokens, jax.device_put({"tokens": fed[None]})

    def step(self):
        """One optimizer step through the compiled, donated call; returns
        the host tokens and the fetched loss."""
        import jax

        tokens, dev = self.feed()
        rng = jax.random.fold_in(jax.random.PRNGKey(1), self.index)
        if self.ctx.get("fault") == "state_unchanged":  # tests only
            kept = jax.tree_util.tree_map(lambda t: t.copy(), self.state)
            _, self.metrics = self.compiled(self.state, dev, rng)
            self.state = kept
        else:
            self.state, self.metrics = self.compiled(self.state, dev, rng)
        self.dispatched_at = time.perf_counter()
        return tokens, float(np.asarray(self.metrics["loss"]))


def build_runner(ctx, setup, weights):
    import jax
    import jax.numpy as jnp

    from alphafold2_tpu.training.harness import make_optimizer, make_train_step
    from alphafold2_tpu.training.lm import lm_aux_update, lm_loss_fn

    cfg, tcfg = ctx["built"]["cfg"], ctx["built"]["tcfg"]
    params = weights()
    state = {"params": params,
             "opt_state": jax.jit(make_optimizer(tcfg).init)(params),
             "step": jnp.zeros((), jnp.int32)}
    jax.block_until_ready(state)
    setup.mark("weights_and_state_on_device")
    step = make_train_step(cfg, tcfg, loss_fn=lm_loss_fn,
                           aux_update=lm_aux_update(cfg))
    batch, length = shape_of(ctx)
    example = {"tokens": np.zeros((1, batch, length), np.int32)}
    compiled = (jax.jit(step, donate_argnums=(0,))
                .lower(state, example, jax.random.PRNGKey(1)).compile())
    setup.mark("trace_and_compile_or_cache_load")
    return Runner(ctx, state, compiled)


def first_steps(runner, weights, n):
    """The first n steps through the window's own call: each loss, the
    first gradient's norms (from Adam's first moment after one step: mu =
    (1 - b1) g), and the norms of the parameters' change after n against
    the seed's weights made again."""
    import jax

    batches, losses, grad = [], [], None
    for i in range(n):
        t_step = time.perf_counter()
        tokens, value = runner.step()
        log(f"first step {i + 1} through the timed call: {time.perf_counter() - t_step:.4f} s")
        batches.append(tokens)
        losses.append(value)
        if i == 0:
            mu = compare.find_mu(runner.state["opt_state"])
            grad = [g / 0.1 for g in compare.norms(mu)]
    params0 = weights()
    change = compare.delta_norms(runner.state["params"], params0)
    del params0
    jax.block_until_ready(runner.state)
    return {"batches": batches, "losses": losses, "grad": grad, "change": change}


def program_picks(ctx, weights, tokens):
    """The experts the program's router picks for `tokens` on the cell's
    weights, (MoE layers, tokens, top_k): the program's forward at the
    timed sizes and precision, outside the timed step."""
    import jax

    from alphafold2_tpu.models.decoder import decoder_apply

    cfg = ctx["built"]["cfg"]
    picks = jax.jit(lambda p, t: decoder_apply(p, cfg, t)[1]["picks"])(
        weights(), jax.device_put(tokens))
    return np.asarray(picks)


def memory_line(devices, where):
    stats = devices[0].memory_stats() or {}
    log(f"memory after the {where}: {stats.get('bytes_in_use', 0) / 1e9:.2f} GB in use, "
        f"peak {stats.get('peak_bytes_in_use', 0) / 1e9:.2f} GB")


def follow_reference(ctx, weights, batches, q=None):
    """The plain reference over the same first steps from the same
    weights: losses, the first step's picks, first gradient's norms,
    change norms. It
    walks the UNSTACKED parameters (`decoder_lm.value_and_grad_layers`)
    and keeps Adam's moments on the host while a gradient is computed:
    that is what fits beside float32 activations."""
    import jax

    from reference import decoder_lm

    hp = reference_hp(ctx)

    def leaves(tree):
        return jax.tree_util.tree_leaves(tree)

    def fresh():
        return decoder_lm.unstack(weights())

    outer, layers, kinds = fresh()
    memory_line(ctx["devices"], "reference's weights")
    opt = None
    losses, picks, grad = [], None, None
    for i, tokens in enumerate(batches):
        t_step = time.perf_counter()
        value, grads, idx, load = decoder_lm.value_and_grad_layers(
            outer, layers, kinds, jax.device_put(tokens), hp, q)
        losses.append(float(value))
        log(f"reference step {i + 1}: {time.perf_counter() - t_step:.1f} s")
        if i == 0:
            picks = np.asarray(idx)
            grad = leaves(decoder_lm.stacked_norms(grads[0], grads[1], kinds))
        opt = (decoder_lm.adam_init((outer, layers)) if opt is None
               else jax.device_put(opt))
        (outer, layers), opt = decoder_lm.train_step_layers(
            outer, layers, kinds, opt, grads, load, hp)
        del grads
        if i + 1 < len(batches):
            opt = jax.device_get(opt)  # off the device for the next gradient
    del opt
    outer0, layers0, _ = fresh()
    sub = jax.jit(lambda a, b: jax.tree_util.tree_map(lambda x, y: x - y, a, b))
    change = decoder_lm.stacked_norms(
        sub(outer, outer0), [sub(a, b) for a, b in zip(layers, layers0)], kinds)
    return {"losses": losses, "picks": picks, "grad": grad, "change": leaves(change)}


def route_mismatch_share(prog_picks, ref_picks) -> float:
    """Share of the program's token-expert picks of the first step that
    the reference did not make for the same token and layer."""
    same = (prog_picks[..., :, None] == ref_picks[..., None, :]).any(-1)
    return float(1.0 - same.mean())


def compared_numbers(prog, ref, names):
    """{name: value} of every number `correct` holds, and which leaf."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        out[f"loss{i + 1}_gap"] = compare.rel(a, b)
    gaps = compare.leaf_gaps(prog["grad"], ref["grad"])
    for i in sorted(range(len(gaps)), key=lambda i: -gaps[i])[:4]:
        log(f"gradient leaf {names[i]}: gap {gaps[i]:.4g} prog {prog['grad'][i]:.6g} "
            f"ref {ref['grad'][i]:.6g}")
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
    out["route_mismatch_share"] = route_mismatch_share(prog["picks"], ref["picks"])
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


def expert_window(step_metrics) -> dict:
    """The window's expert loop from its steps' own metrics, read from the
    device once: rows walked and assignments held, (steps, MoE layers),
    under the facts' names `moe_rows_walked_by_step` / `moe_held_by_step`;
    each layer's held load and the blocks it walked are logged. Empty
    where the steps state no rows walked (a tree before the loop)."""
    import jax

    if not step_metrics or "moe_rows_walked" not in step_metrics[0]:
        return {}
    rows, held = (np.stack(a) for a in zip(*jax.device_get(
        [(m["moe_rows_walked"], m["moe_assignments_held"]) for m in step_metrics])))
    for layer in range(held.shape[1]):
        walked, count = np.unique(rows[:, layer], return_counts=True)
        log(f"window's expert layer {layer}: held mean {held[:, layer].mean():.1f} "
            f"min {held[:, layer].min():.0f} max {held[:, layer].max():.0f}; rows walked "
            f"{dict(zip(walked.astype(int).tolist(), count.tolist()))} of {len(rows)} steps")
    return {"moe_rows_walked_by_step": rows, "moe_held_by_step": held}


def made_up_expert_window(cfg, shape) -> dict:
    """`expert_window`'s facts for `dry_facts()`: 4 steps of 2 MoE layers
    at 0.8 of a block of the cell's plan, one layer-step at 1.2 (it walks
    a second block)."""
    block = common.module("readers", "moe_extra_block_share").block_rows(cfg, shape)
    held = np.full((4, 2), 0.8 * block)
    held[3, 1] = 1.2 * block
    return {"moe_rows_walked_by_step": np.ceil(held / block) * block,
            "moe_held_by_step": held}


def dry_facts(config, traffic):
    """Facts of the shape `run()` hands on, at the toy sizes of `--dry`
    with made-up times and a made-up scope table: what the tests of the
    result line give the readers."""
    cfg = common.module("builders", config["builder"]).build(config, True)["cfg"]
    batch, length = traffic["dry"]["batch"], traffic["dry"]["length"]
    return common.made_up_facts(
        ("mla_attn/attn_core", "mla_attn/qkv_proj", "moe/experts", "moe/router",
         "dense_mlp", "lm_head_loss", "decoder_layers"),
        {"forward": 0.01, "reconstruct": 0.0, "remat": 0.01, "backward": 0.02,
         "other": 0.0},
        model_cfg=cfg, lm_shape=(batch, length), trace_steps=traffic["trace_steps"],
        assignments_held=0.75 * batch * length, moe_load_max_over_mean=1.3,
        **made_up_expert_window(cfg, (batch, length)))


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
        f"mean {skew:.4f}")

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
