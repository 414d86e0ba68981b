"""The primitives of the reduction from a profiler trace to the device's
busy time, its idle gaps and the operations that took most of it.
`scope_reduce.reduce_scopes` is the one reduction built from them.

Device planes are `/device:TPU:<n>`; their `XLA Ops` line holds one event
per executed operation and `XLA Modules` one per executed program. Host
spans written with `jax.profiler.TraceAnnotation` (`bench.*`, and the
program tracer's `train.*` and the like) are events of `/host:CPU`. Busy
is the union of the operation intervals inside the window, averaged over
the device planes; a gap is attributed to the host span that covers most
of it. The device's and the host's clocks differ by about a millisecond in
these traces, so gaps shorter than a few milliseconds are not worth a name.
"""
from __future__ import annotations

import glob
import os
import re


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def short_name(name: str) -> str:
    """`%fusion.3 = bf16[...] fusion(...)` -> `fusion.3`."""
    m = re.match(r"\s*%?([^\s=]+)", name)
    return m.group(1) if m else name


def module_name(name: str) -> str:
    """`jit_run(123456)` -> `jit_run`."""
    return re.sub(r"\(\d+\)$", "", name)


def union(intervals):
    """Merged, sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_and_gaps(ops, window):
    """Seconds busy inside `window` = (start, end), and the idle gaps."""
    lo, hi = window
    merged = union((max(s, lo), min(e, hi)) for s, e, _ in ops if e > lo and s < hi)
    busy = sum(e - s for s, e in merged)
    gaps, at = [], lo
    for s, e in merged:
        if s > at:
            gaps.append((at, s))
        at = e
    if hi > at:
        gaps.append((at, hi))
    return busy, gaps


def attribute(gap, host_spans) -> str:
    """The bench.* span that covers most of the gap, or `unattributed`."""
    best, best_cover = "unattributed", 0.0
    for s, e, name in host_spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > best_cover:
            best, best_cover = name, cover
    return best if best_cover >= 0.5 * (gap[1] - gap[0]) else "unattributed"


#: the scope key of an operation under no documented name
UNSCOPED = "unscoped"


def op_label(scope_key: str, phase: str, program: str, hlo_name: str) -> str:
    """The name an operation's device time is summed under in the result
    line's `breakdown`: `<scope path> <phase>` (`seq_ff/geglu backward`)
    where the operation's `tf_op` puts it under a documented scope, the
    vocabulary of `telemetry/profiling.py SCOPES`; `<program>/<HLO name>`
    where it does not."""
    if scope_key.split("/", 1)[0] == UNSCOPED:
        return f"{module_name(program)}/{short_name(hlo_name)}"
    return f"{scope_key} {phase}"
