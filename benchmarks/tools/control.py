"""Read the control of a cell on the chip at the cell's own size: the
plain reference put in the program's place and computed in 8-bit floating
point (reference/lowprec.py), compared with the reference itself by the
same arithmetic that decides `correct` and held to the limits the cell
ships with: `correct` has to come out false. One JSON line per seed goes
to chiprun_out/records/control_<workload>.jsonl.

    python benchmarks/tools/control.py <workload> <seed> [<seed> ...]
"""
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
from reference import lowprec  # noqa: E402


def train_control(ctx, q):
    from kinds import train_steps

    traffic, built = ctx["traffic"], ctx["built"]
    loss = common.module("losses", traffic["loss"])
    prog = loss.program(built)
    params0 = common.make_params(prog["param_shapes"], common.seed_key(ctx["seed"]),
                                 stacked=prog["stacked"])
    shape = train_steps.example_shape(built, traffic)
    batches = [traffic_gen.train_batch(shape, ctx["seed"], i)
               for i in range(traffic["check_steps"])]
    ref = train_steps.follow_reference(ctx, loss, params0, batches)
    ctl = train_steps.follow_reference(ctx, loss, params0, batches, q)
    common.log("losses control", ctl["losses"], "reference", ref["losses"])
    return train_steps.compared_numbers(ctl, ref, compare.leaf_paths(params0))


def main():
    workload, seeds = sys.argv[1], [int(a) for a in sys.argv[2:]]
    out_dir = os.path.join(common.ROOT, "chiprun_out", "records")
    os.makedirs(out_dir, exist_ok=True)
    for seed in seeds:
        t = time.perf_counter()
        ctx = common.context(workload, seed, None, False, False, None, T0)
        values = train_control(ctx, lowprec.fp8)
        limits = ctx["limits"]
        correct, rows = common.judge({k: (v, limits[k]) for k, v in values.items()
                                      if k in limits})
        record = {"workload": workload, "seed": seed, "control": "fp8",
                  "correct": correct, "compared": rows, "numbers": values,
                  "seconds": round(time.perf_counter() - t, 1)}
        print(json.dumps(record), flush=True)
        with open(os.path.join(out_dir, f"control_{workload}.jsonl"), "a") as f:
            f.write(json.dumps(record) + "\n")


if __name__ == "__main__":
    main()
