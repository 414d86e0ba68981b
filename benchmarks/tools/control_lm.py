"""Read the control of a cell whose kind brings its own (`kinds/<kind>.py
control(ctx, q)`), on the chip at the cell's own size. Everything but that
one call is `tools/control.py`'s: the seeds' loop, the judgement by the
limits the cell ships with (`correct` has to come out false), the record
in chiprun_out/records/control_<workload>.jsonl.

    python benchmarks/tools/control_lm.py <workload> <seed> [<seed> ...]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import control  # noqa: E402


def kind_control(ctx, q):
    return control.common.module("kinds", ctx["traffic"]["kind"]).control(ctx, q)


if __name__ == "__main__":
    control.train_control = kind_control
    control.main()
