"""Read a trunk cell's first gradient under programs that differ only in
where the GEGLU feed-forward runs, against the plain reference, on the
chip at the cell's own size: which rows take the kernel
(`ops/dispatch.py _GEGLU_KERNEL_MIN_ROWS`) and the lanes of its
value/gate blocks (`ops/geglu_kernel.py _LANES`, which moves only the
float32 order in which a row's output and dx are summed). One process:
each program compiles once and runs the first step of every seed given
it, then the reference runs once a seed.

For every program and seed it prints `grad_gap`, `grad_gap_median` and
the gap of the position tables as the cell computes them, and, for the
watched leaves, the whole gradient against the reference: the norm of
the difference over the reference's norm, and the cosine. Between two
programs on one seed it prints the norm of their difference over the
reference's norm: how far the change of arm moves the leaf, beside how
far a change of summation order moves it. Every number goes to
chiprun_out/records/arm_probe_<workload>.jsonl.

    python benchmarks/tools/arm_probe.py <workload> \\
        "<name>:<min_rows>:<lanes>:<n_seeds>;..." <seed> [<seed> ...] [--dry]

`n_seeds`: the program runs the first n seeds only (0: all).
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

WATCHED = ("model/pos_emb_ax/table", "model/pos_emb/table",
           "model/trunk/seq_ff/ff/proj_in/w", "model/trunk/seq_ff2/ff/proj_in/w",
           "model/trunk/msa_ff/ff/proj_in/w", "model/trunk/msa_ff2/ff/proj_in/w")


def _variants(spec):
    out = []
    for part in spec.split(";"):
        name, min_rows, lanes, n = part.split(":")
        out.append((name, int(min_rows), int(lanes), int(n)))
    return out


def _cell_numbers(prog, ref):
    """grad_gap, grad_gap_median as the cell computes them, and the worst
    of the two position tables' gaps."""
    gaps = compare.leaf_gaps(prog, ref)
    live = sorted(g for g, r in zip(gaps, ref) if r > 0)
    gap, _ = compare.worst_leaf_gap(prog, ref, compare.larger_half(ref))
    return gap, live[len(live) // 2], gaps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("variants")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--dry", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from alphafold2_tpu.ops import dispatch, geglu_kernel
    from kinds import train_steps

    out_dir = os.path.join(common.ROOT, "chiprun_out", "records")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"arm_probe_{args.workload}.jsonl")

    def keep(record):
        with open(path, "a") as f:
            f.write(json.dumps(record) + "\n")

    ctx = common.context(args.workload, args.seeds[0], None, False, args.dry,
                         None, T0)
    loss = common.module("losses", ctx["traffic"]["loss"])
    progs, vecs, names, watched = {}, {}, None, []
    for name, min_rows, lanes, n in _variants(args.variants):
        dispatch._GEGLU_KERNEL_MIN_ROWS = min_rows
        geglu_kernel._LANES = lanes
        dispatch.reset_decisions()
        t = time.perf_counter()
        runner, _ = train_steps.build_runner(ctx, common.Setup(T0))
        decided = sorted(k for k in dispatch.decisions() if k.startswith("geglu_ff"))
        log(f"program {name}: built in {time.perf_counter() - t:.1f} s; {decided}")
        keep({"program": name, "min_rows": min_rows, "lanes": lanes,
              "decisions": decided, "build_s": round(time.perf_counter() - t, 1)})
        prog, compiled, shape = runner.prog, runner.compiled, runner.shape
        if names is None:
            shapes = {k: prog[k] for k in ("param_shapes", "stacked")}
            names = compare.leaf_paths(runner.state["params"])
            watched = [names.index(w) for w in WATCHED if w in names]
        del runner
        gc.collect()
        for seed in args.seeds[:n or None]:
            params, params0 = common.make_params(
                prog["param_shapes"], common.seed_key(seed), stacked=prog["stacked"],
                copies=2)
            state = {"params": params,
                     "opt_state": jax.jit(prog["optimizer"].init)(params),
                     "step": jnp.zeros((), jnp.int32)}
            run = train_steps.Runner(dict(ctx, seed=seed), loss, prog, state,
                                     compiled, shape)
            first = train_steps.first_steps(run, params0, 1)
            mu = jax.tree_util.tree_leaves(compare.find_mu(run.state["opt_state"]))
            vecs[name, seed] = {i: np.asarray(mu[i], np.float64) / 0.1 for i in watched}
            progs[name, seed] = first["grad"]
            keep({"program": name, "seed": seed, "loss": first["losses"][0],
                  "grad": first["grad"]})
            del run, state, first, mu, params, params0
            gc.collect()
        del prog, compiled
        gc.collect()

    from reference import af2

    blocks = {"attn_block": 0, "ff_block": 0, "cross_block": 0, "atom_block": 0}
    if not ctx["dry"]:
        blocks = dict(ctx["config"]["reference"])
        blocks.pop("precision", None)
    af2.set_precision("high")  # as the cell ships
    hp = loss.reference_hp(ctx["built"], blocks)
    order = [v[0] for v in _variants(args.variants)]
    for seed in args.seeds:
        t = time.perf_counter()
        _, params0 = common.make_params(
            shapes["param_shapes"], common.seed_key(seed), stacked=shapes["stacked"],
            copies=2)
        batch = traffic_gen.train_batch(
            train_steps.example_shape(ctx["built"], ctx["traffic"]), seed, 0)
        dev = {k: jax.device_put(v) for k, v in batch.items()}
        value, grads = loss.reference_value_and_grad(params0, dev, hp, None)
        grad = compare.norms(grads)
        leaves = jax.tree_util.tree_leaves(grads)
        rvec = {i: np.asarray(leaves[i], np.float64) for i in watched}
        del grads, leaves, dev, params0
        gc.collect()
        keep({"program": "reference_high", "seed": seed, "loss": float(value),
              "grad": grad, "seconds": round(time.perf_counter() - t, 1)})
        ran = [v for v in order if (v, seed) in progs]
        for v in ran:
            gap, median, gaps = _cell_numbers(progs[v, seed], grad)
            leaf = {}
            for i in watched:
                p, r = vecs[v, seed][i].ravel(), rvec[i].ravel()
                rn = float(np.linalg.norm(r))
                leaf[names[i]] = {
                    "gap": gaps[i],
                    "diff_over_ref": float(np.linalg.norm(p - r)) / rn,
                    "cos": float(p @ r) / (float(np.linalg.norm(p)) * rn),
                    "prog_norm": float(np.linalg.norm(p)), "ref_norm": rn}
            moved = {}
            for w in ran[ran.index(v) + 1:]:
                moved[w] = {names[i]: float(np.linalg.norm(
                    vecs[v, seed][i] - vecs[w, seed][i])) / float(np.linalg.norm(rvec[i]))
                    for i in watched}
            keep({"program": v, "seed": seed, "grad_gap": gap,
                  "grad_gap_median": median, "leaves": leaf, "moved_against": moved})
            tables = max((gaps[i] for i in watched if "pos_emb" in names[i]),
                         default=float("nan"))
            print(f"seed {seed} {v}: grad_gap {gap:.4f} median {median:.5f} "
                  f"tables {tables:.4f} | "
                  + ", ".join(f"{k.split('/')[2] if 'trunk' in k else k.split('/')[1]} "
                              f"diff {d['diff_over_ref']:.4f} cos {d['cos']:.5f}"
                              for k, d in leaf.items())
                  + " | moved against "
                  + "; ".join(f"{w}: " + ", ".join(f"{m:.4f}" for m in d.values())
                              for w, d in moved.items()), flush=True)
        log(f"seed {seed} reference: {time.perf_counter() - t:.1f} s")


if __name__ == "__main__":
    main()
