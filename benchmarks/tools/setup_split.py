"""One run of a cell, as `run.py` makes it, and the compile recorder's split
of its set-up (`alphafold2_tpu/telemetry/compile_record.py`).

    python benchmarks/tools/setup_split.py --workload <cell> --seed <n> [--seconds s] [--trace 0|1] [--dry]

The arguments are `run.py`'s, and its output comes first. The last line of
standard output is this tool's: `{"setup_split": ...}` with

  * `after_device`: the five numbers that per-layer metrics of set-up would
    read (`split`) over the window of `setup.after_device_s`, from the end
    of `imports_and_device` to the last mark of `common.Setup`: seconds the
    program traced, lowered, compiled with XLA and loaded from the compile
    cache (each the union of that phase's spans a thread), and the number
    of programs compiled;
  * `union_s`, `counts`, `top`: all phases together, the count of each and
    the ten heaviest (phase, function) pairs of that window;
  * `phases`: the same for each phase of `common.Setup`, with `covered`,
    the share of the phase's seconds that the recorder's spans cover.

The ten heaviest functions also go to standard error. A program without the
recorder prints `{"setup_split": null}`.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402  (first: it notes the process's start)
import common  # noqa: E402


def split(snap: dict) -> dict:
    """The five numbers of set-up from one `compile_record.snapshot`."""
    return {"setup.jaxpr_trace_s": snap["seconds"]["trace"],
            "setup.lower_s": snap["seconds"]["lower"],
            "setup.xla_compile_s": snap["seconds"]["xla_compile"],
            "setup.cache_load_s": snap["seconds"]["cache_load"],
            "setup.programs_compiled": snap["counts"]["xla_compile"]}


def phase_windows(setup) -> list:
    """(name, start, end) of each phase of a `common.Setup`, on its clock."""
    out, at = [], setup.t0
    for name, secs in setup.phases:
        out.append((name, at, at + secs))
        at += secs
    return out


def report(setup, recorder) -> dict:
    """The split of `setup`'s phases by what `recorder` (anything with
    `snapshot(since, until, top)`) recorded in each."""
    phases = phase_windows(setup)
    after = recorder.snapshot(phases[0][2], setup.last, top=10)
    by_phase = {}
    for name, lo, hi in phases:
        snap = recorder.snapshot(lo, hi, top=3)
        by_phase[name] = {"s": round(hi - lo, 4),
                          "covered": round(snap["union_s"] / (hi - lo), 4) if hi > lo else None,
                          "seconds": snap["seconds"], "counts": snap["counts"],
                          "top": snap["top"]}
    return {"after_device": split(after), "union_s": after["union_s"],
            "counts": after["counts"], "top": after["top"], "phases": by_phase}


def main() -> int:
    held = {}
    context = common.context

    def keep(*args, **kwargs):
        held["ctx"] = context(*args, **kwargs)
        return held["ctx"]

    common.context = keep
    rc = run.main()
    try:
        from alphafold2_tpu.telemetry import compile_record
    except ImportError:
        print(json.dumps({"setup_split": None}), flush=True)
        return rc
    rep = report(held["ctx"]["setup"], compile_record)
    for t in rep["top"]:
        common.log(f"set-up {t['phase']:<11} {t['s']:9.4f} s in {t['n']:4d}: {t['fun']}")
    print(json.dumps({"setup_split": rep}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
