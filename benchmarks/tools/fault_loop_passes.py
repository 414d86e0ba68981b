"""The fault a looped decoder can have and no other cell can show: a timed
path that runs its stack one pass too few. The cell's own kind runs
(`kinds/<kind>.py run(ctx)`: the timed call, its first steps, the plain
reference, the same compared numbers and limits) with the program's
configuration rebuilt with `total_ut_steps` one less than published, while
the reference still answers from the published configuration: `correct`
has to come out false. One JSON line per seed goes to
chiprun_out/records/fault_loop_passes_<workload>.jsonl.

    python benchmarks/tools/fault_loop_passes.py <workload> <seed> [<seed> ...] [--dry] [--seconds S]
"""
import argparse
import dataclasses
import json
import os
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import common  # noqa: E402


def drop_a_pass(built: dict) -> dict:
    """`built` with the program's stack run one pass fewer and the
    reference's hp still made from the configuration as it was built."""
    published, reference_hp = built["cfg"], built["reference_hp"]
    passes = getattr(published, "total_ut_steps", 1)
    if passes < 2:
        raise SystemExit("the cell's configuration has no second pass to drop")
    return {**built, "cfg": dataclasses.replace(published, total_ut_steps=passes - 1),
            "reference_hp": lambda cfg, tcfg: reference_hp(published, tcfg)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--dry", action="store_true", help="toy shapes, any platform")
    ap.add_argument("--seconds", type=float, default=10.0, help="the timed window")
    args = ap.parse_args()
    workload, dry = args.workload, args.dry
    out_dir = os.path.join(common.ROOT, "chiprun_out", "records")
    os.makedirs(out_dir, exist_ok=True)
    for seed in args.seeds:
        t = time.perf_counter()
        ctx = common.context(workload, seed, args.seconds, False, dry, None, T0)
        ctx["built"] = drop_a_pass(ctx["built"])
        out = common.module("kinds", ctx["traffic"]["kind"]).run(ctx)
        record = {"workload": workload, "seed": seed, "fault": "loop_passes",
                  "dry": dry, "correct": out["correct"], "compared": out["compared"],
                  "seconds": round(time.perf_counter() - t, 1)}
        print(json.dumps(record), flush=True)
        if not dry:
            with open(os.path.join(out_dir, f"fault_loop_passes_{workload}.jsonl"), "a") as f:
                f.write(json.dumps(record) + "\n")


if __name__ == "__main__":
    main()
