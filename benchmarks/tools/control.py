"""Read the control of a cell on the chip at the cell's own size: the
plain reference put in the program's place and computed in 8-bit floating
point (reference/lowprec.py), compared with the reference itself by the
same arithmetic that decides `correct` (the cell's kind brings it:
`kinds/<kind>.py control(ctx, q)`) and held to the limits the cell ships
with: `correct` has to come out false. One JSON line per seed goes to
chiprun_out/records/control_<workload>.jsonl.

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
from reference import lowprec  # noqa: E402


def main():
    workload, seeds = sys.argv[1], [int(a) for a in sys.argv[2:]]
    out_dir = os.path.join(common.ROOT, "chiprun_out", "records")
    os.makedirs(out_dir, exist_ok=True)
    for seed in seeds:
        t = time.perf_counter()
        ctx = common.context(workload, seed, None, False, False, None, T0)
        values = common.module("kinds", ctx["traffic"]["kind"]).control(ctx, lowprec.fp8)
        correct, rows = common.judge_values(values, ctx["limits"])
        record = {"workload": workload, "seed": seed, "control": "fp8",
                  "correct": correct, "compared": rows, "numbers": values,
                  "seconds": round(time.perf_counter() - t, 1)}
        print(json.dumps(record), flush=True)
        with open(os.path.join(out_dir, f"control_{workload}.jsonl"), "a") as f:
            f.write(json.dumps(record) + "\n")


if __name__ == "__main__":
    main()
