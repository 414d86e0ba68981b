"""Condense what the calls of the chip tool brought back under
chiprun_out/records/ into benchmarks/records/: one JSON line per run with
the cell, the seed, the call, the result line and what the run said
about its set-up, its window and its comparison. Sweeps and controls are
copied as they are.

    python benchmarks/tools/collect.py [<name of the runs' file> [<prefix>]]

(`runs.jsonl` where no name is given: PR 24's; a later PR names its own,
`runs_pr31.jsonl`, and the prefix its calls' labels share, so that it
overwrites no earlier record and takes in no earlier call.)
"""
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
KEEP = ("setup phases", "first step", "window", "reference:", "losses ", "worst ", "answer len",
        "comparison info", "memory after the", "SystemExit", "Error", "Failed run",
        " step ", "STALLED", "heartbeat", "grad_gap", "read, not held", "compared ")


def main():
    runs_file = sys.argv[1] if len(sys.argv) > 1 else "runs.jsonl"
    prefix = sys.argv[2] if len(sys.argv) > 2 else ""
    src = os.path.join(ROOT, "chiprun_out", "records")
    dst = os.path.join(ROOT, "benchmarks", "records")
    os.makedirs(dst, exist_ok=True)
    runs = []
    for path in sorted(glob.glob(os.path.join(src, prefix + "*.jsonl"))):
        name = os.path.basename(path)
        with open(path) as f:
            lines = [json.loads(l) for l in f if l.strip()]
        if not lines or "said" not in lines[0]:
            with open(os.path.join(dst, name), "w") as f:
                for rec in lines:
                    f.write(json.dumps(rec) + "\n")
            continue
        for rec in lines:
            said = [s[:6000 if "STALLED" in s else 600] for s in rec.pop("said")
                    if any(k in s for k in KEEP)]
            runs.append(dict(rec, said=said[-30:]))
    with open(os.path.join(dst, runs_file), "w") as f:
        for rec in runs:
            f.write(json.dumps(rec) + "\n")
    print(f"{len(runs)} runs -> benchmarks/records/{runs_file}")


if __name__ == "__main__":
    main()
