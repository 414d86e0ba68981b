"""Run several cells one after another on the machine this is started on,
each as a process of its own (this parent never touches JAX), and keep a
record of each: the result line, what the run said before it, and how
long the process took. Records go to chiprun_out/records/<label>.jsonl.

    python benchmarks/tools/session.py <label> <workload>:<seed>:<seconds>:<trace>[:extra args] ...
"""
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    label, specs = sys.argv[1], sys.argv[2:]
    out_dir = os.path.join(ROOT, "chiprun_out", "records")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, label + ".jsonl")
    for spec in specs:
        workload, seed, seconds, trace, *extra = spec.split(":")
        cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
               "--workload", workload, "--seed", seed, "--seconds", seconds,
               "--trace", trace] + [a for e in extra for a in e.split()]
        t0 = time.time()
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        took = time.time() - t0
        lines = proc.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1]) if lines else None
        except ValueError:
            result = None
        said = [l for l in proc.stderr.splitlines()
                if not l.startswith(("E0", "W0", "I0")) and "cpu_aot" not in l]
        record = {"call": label, "workload": workload, "seed": int(seed),
                  "seconds": float(seconds), "trace": int(trace), "extra": extra,
                  "exit": proc.returncode, "process_s": round(took, 2),
                  "result": result, "said": said[-150:]}
        with open(path, "a") as f:
            f.write(json.dumps(record) + "\n")
        print(f"--- {spec}: exit {proc.returncode} in {took:.1f} s")
        for l in said[-14:]:
            print("   ", l[:400])
        if result:
            print("    metrics", json.dumps(result.get("metrics")),
                  "correct", result.get("correct"))
            print("    device", json.dumps(result.get("device")))
        sys.stdout.flush()


if __name__ == "__main__":
    main()
