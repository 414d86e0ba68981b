"""Run one cell of BENCHMARK.json once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own that this program finds by the
name BENCHMARK.json gives (see benchmarks/README.md). The measured path
needs a TPU; `--dry` rehearses every cell's control flow at toy shapes on
any platform and prints no metric.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import common  # noqa: E402


def read_metric(name: str, facts: dict):
    """The per-layer metric `name` by its own reader; None where the
    reader finds nothing to read."""
    spec = common.load_json("metrics", name + ".json")
    return common.module("readers", spec["reader"]).read(facts, spec.get("args", {}))


def metrics_of(bench: dict, cell_name: str, group: str, facts: dict) -> dict:
    """The cell's metrics of one group as the result line carries them: an
    end-to-end metric is a fact of the run under its own name, a per-layer
    metric is what its reader finds (left out where it finds nothing)."""
    out = {}
    for m in common.cell_metrics(bench, cell_name, group):
        value = (facts[m["name"]] if group == "end_to_end"
                 else read_metric(m["name"], facts))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def reduce_trace_once(facts: dict, traffic: dict):
    """The traced run's one reduction (`scope_reduce.reduce_scopes`), made
    before the trace is removed: the scope x phase table for the readers,
    busy and idle time for the `device` entry, the `breakdown`."""
    import scope_reduce

    try:
        reduced = scope_reduce.reduce_scopes(facts["trace_dir"])
    finally:
        shutil.rmtree(facts["trace_dir"], ignore_errors=True)
    common.log(scope_reduce.format_table(reduced))
    for label, scope_key, phase, secs in reduced["top_paths"][:16]:
        common.log(f"top path {secs:.4f} s {scope_key} {phase}: {label[-150:]}")
    for label, scope_key, phase, secs in reduced["top_unscoped"][:8]:
        common.log(f"unscoped {secs:.4f} s {phase}: {label[-150:]}")
    facts["trace"] = reduced
    facts.setdefault("scopes", reduced)
    facts.setdefault("trace_steps", traffic.get("trace_steps"))
    return reduced


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--dry", action="store_true",
                    help="toy shapes, any platform, no metric printed")
    ap.add_argument("--fault", default=None,
                    help="break the timed path underneath (tests only)")
    args = ap.parse_args()

    ctx = common.context(args.workload, args.seed, args.seconds, bool(args.trace),
                         args.dry, args.fault, T_PROCESS_START)
    bench, cell, devices = ctx["bench"], ctx["cell"], ctx["devices"]
    out = common.module("kinds", ctx["traffic"]["kind"]).run(ctx)
    facts = out["facts"]

    if args.dry:
        shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
        print(json.dumps({"dry": True, "workload": cell["name"],
                          "correct": out["correct"], "compared": out["compared"]}))
        return 0

    facts["device_kind"] = devices[0].device_kind
    device, reduced = dict(out["device"]), None
    if args.trace:
        reduced = reduce_trace_once(facts, ctx["traffic"])
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    group = "per_layer" if args.trace else "end_to_end"
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"],
              "metrics": metrics_of(bench, cell["name"], group, facts),
              "device": device}
    if reduced:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    common.finish(result, out["compared"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
