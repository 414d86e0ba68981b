"""Read the first gradient of a trunk training cell leaf by leaf, on the
chip at the cell's own size, from every side that `grad_gap` could hold
it against: the program (one step of the timed call, from Adam's first
moment), the plain reference at `Precision.HIGH` (as the cell ships) and
at `HIGHEST`, and the control (the reference with fp8 operands). One
process, one compiled step, so a dozen seeds cost one set-up. Every
leaf's norm goes to chiprun_out/records/leaf_probe_<workload>.jsonl (one
line a seed and side); the leaves named are printed by seed.

    python benchmarks/tools/leaf_probe.py <workload> <leaf>[,<leaf>...] \
        [--highest N] [--fp8 N] [--dry] <seed> [<seed> ...]

`--highest N` / `--fp8 N`: read that side on the first N seeds only (each
side compiles its own blocks, two to three minutes cold; warm, a gradient
takes 11 s at either precision: PR 31, call a1).
"""
import argparse
import gc
import json
import os
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import common  # noqa: E402
import compare  # noqa: E402
import traffic_gen  # noqa: E402
from common import log  # noqa: E402


def reference_grad(ctx, loss, params0, batch, precision, q=None):
    """(loss, every leaf's gradient norm) of the plain reference's first
    step at `precision`."""
    import jax

    from reference import af2

    blocks = {"attn_block": 0, "ff_block": 0, "cross_block": 0, "atom_block": 0}
    if not ctx["dry"]:
        blocks = dict(ctx["config"]["reference"])
        blocks.pop("precision", None)
    af2.set_precision(precision)
    # the precision is read when a block is traced, and the blocks are
    # jitted with `hp` static: a key that names it keeps the two apart
    hp = dict(loss.reference_hp(ctx["built"], blocks), traced_at=precision)
    dev = {k: jax.device_put(v) for k, v in batch.items()}
    value, grads = loss.reference_value_and_grad(params0, dev, hp, q)
    return float(value), compare.norms(grads)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("leaves")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--highest", type=int, default=None)
    ap.add_argument("--fp8", type=int, default=0)
    ap.add_argument("--dry", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from kinds import train_steps
    from reference import lowprec

    ctx = common.context(args.workload, args.seeds[0], None, False, args.dry, None, T0)
    loss = common.module("losses", ctx["traffic"]["loss"])
    runner, _ = train_steps.build_runner(ctx, common.Setup(T0))
    prog, compiled, shape = runner.prog, runner.compiled, runner.shape
    names = compare.leaf_paths(runner.state["params"])
    watched = [names.index(leaf) for leaf in args.leaves.split(",")]
    del runner
    out_dir = os.path.join(common.ROOT, "chiprun_out", "records")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"leaf_probe_{args.workload}.jsonl")

    def keep(record):
        with open(path, "a") as f:
            f.write(json.dumps(record) + "\n")

    def gaps_line(seed, side, a, other, b):
        gaps = compare.leaf_gaps(a, b)
        rest = [g for i, (g, k) in enumerate(zip(gaps, compare.larger_half(b)))
                if k and i not in watched]
        print(f"seed {seed} {side} against {other}: "
              + ", ".join(f"{names[i]} {gaps[i]:.4f} ({a[i]:.4g} / {b[i]:.4g})"
                          for i in watched)
              + f"; worst other leaf of the larger half {max(rest):.4f}", flush=True)

    keep({"names": names, "leaves": args.leaves, "device": jax.devices()[0].device_kind})
    sides = {}
    n_highest = len(args.seeds) if args.highest is None else args.highest
    for precision in ("high", "highest"):
        for at, seed in enumerate(args.seeds):
            if precision == "highest" and at >= n_highest:
                continue
            t = time.perf_counter()
            params, params0 = common.make_params(
                prog["param_shapes"], common.seed_key(seed), stacked=prog["stacked"],
                copies=2)
            batch = traffic_gen.train_batch(shape, seed, 0)
            if precision == "high":
                state = {"params": params,
                         "opt_state": jax.jit(prog["optimizer"].init)(params),
                         "step": jnp.zeros((), jnp.int32)}
                run = train_steps.Runner(dict(ctx, seed=seed), loss, prog, state,
                                         compiled, shape)
                first = train_steps.first_steps(run, params0, 1)
                sides[seed, "program"] = first["grad"]
                keep({"seed": seed, "side": "program", "loss": first["losses"][0],
                      "grad": first["grad"]})
                del run, state, first
                gc.collect()
            value, grad = reference_grad(ctx, loss, params0, batch, precision)
            sides[seed, precision] = grad
            keep({"seed": seed, "side": precision, "loss": value, "grad": grad,
                  "seconds": round(time.perf_counter() - t, 1)})
            log(f"seed {seed} reference at {precision}: {time.perf_counter() - t:.1f} s")
            gaps_line(seed, "program", sides[seed, "program"], precision, grad)
            if precision == "highest":
                gaps_line(seed, "high", sides[seed, "high"], "highest", grad)
            elif at < args.fp8:
                t = time.perf_counter()
                value, ctl = reference_grad(ctx, loss, params0, batch, "high", lowprec.fp8)
                keep({"seed": seed, "side": "fp8", "loss": value, "grad": ctl,
                      "seconds": round(time.perf_counter() - t, 1)})
                gaps_line(seed, "fp8 control", ctl, "high", grad)
            del params, params0
            gc.collect()


if __name__ == "__main__":
    main()
