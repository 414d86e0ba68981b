"""Device seconds by named scope and phase, for a cell or for any capture.

    python benchmarks/tools/scopes.py --workload <cell> --seed <n> [--seconds <s>]
    python benchmarks/tools/scopes.py --xplane <file or directory> [--window-span <name>]

With `--workload` the cell's kind runs with tracing on, through
`common.context`, `kinds/<kind>.run` and `run.reduce_trace_once` exactly as
`run.py --trace 1` runs it (a TPU is required): the same reduction the
result line's per-layer metrics read, kept whole. The table goes to
standard output, the record (call, table, the metrics of `metrics/*.json`
that read `facts["scopes"]`, what tracing cost) to `--out`, by default a
new file under `benchmarks/records/`.

With `--xplane` any capture is reduced: a trainer's `--profile-dir`, a
server's `/profilez`. Its window is the whole trace unless `--window-span`
names a host span in it (`train.step`, say).
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import common  # noqa: E402
import run  # noqa: E402
import scope_reduce  # noqa: E402


def scope_metrics(facts: dict) -> dict:
    """{metric: percent} of every metrics/*.json read by `scope_share`."""
    out = {}
    for path in sorted(glob.glob(os.path.join(BENCH, "metrics", "*.json"))):
        name = os.path.basename(path)[:-len(".json")]
        if common.load_json("metrics", name + ".json")["reader"] != "scope_share":
            continue
        value = run.read_metric(name, facts)
        if value is not None:
            out[name] = value
    return out


def run_cell(args):
    """(record, scope table, facts) of one traced run of the cell."""
    ctx = common.context(args.workload, args.seed, args.seconds, True, args.dry,
                         None, T_PROCESS_START)
    out = common.module("kinds", ctx["traffic"]["kind"]).run(ctx)
    facts = out["facts"]
    reduced = run.reduce_trace_once(facts, ctx["traffic"])
    record = {"workload": args.workload, "seed": args.seed,
              "device": out["device"], "correct": out["correct"]}
    steps = ctx["traffic"].get("trace_steps")
    if steps and "train_step_s" in facts:
        # the same compiled step, profiler on (inside bench.window) and off
        record["tracing_cost"] = {
            "train_step_s_traced": reduced["window_s"] / steps,
            "train_step_s_timed": facts["train_step_s"], "trace_steps": steps}
    return record, reduced, facts


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--dry", action="store_true",
                    help="toy shapes, any platform: rehearses the control flow")
    ap.add_argument("--xplane", help="an .xplane.pb, or a directory that holds one")
    ap.add_argument("--window-span", default="bench.window")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if bool(args.workload) == bool(args.xplane):
        ap.error("give --workload or --xplane")

    if args.xplane:
        reduced = scope_reduce.reduce_scopes(args.xplane, args.window_span)
        record, facts = {"xplane": args.xplane, "window_span": args.window_span}, \
            {"scopes": reduced}
    else:
        try:
            record, reduced, facts = run_cell(args)
        except RuntimeError as e:
            if not args.dry:
                raise
            # a CPU's capture has no /device:TPU plane: the rehearsal ends here
            print(json.dumps({"dry": True, "workload": args.workload, "reduced": str(e)}))
            return 0
    record.update(call=" ".join(sys.argv), scopes=reduced,
                  metrics=scope_metrics(facts))
    print(scope_reduce.format_table(reduced))
    for name, value in record["metrics"].items():
        print(f"{name} = {value:.3f} %")
    if "tracing_cost" in record:
        c = record["tracing_cost"]
        print(f"train_step_s with the profiler on {c['train_step_s_traced']:.4f} "
              f"({c['trace_steps']} steps inside bench.window), off "
              f"{c['train_step_s_timed']:.4f}")
    out = args.out
    if out is None and args.workload and not args.dry:
        out = os.path.join(BENCH, "records", f"scopes_{args.workload}_seed{args.seed}.json")
    if out:
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
        print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
