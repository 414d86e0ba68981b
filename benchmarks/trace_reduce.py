"""From a profiler trace to the device's busy time, its idle gaps and the
operations that took most of it.

Reads the `.xplane.pb` the JAX profiler writes with `jax.profiler.
ProfileData` alone. Device planes are `/device:TPU:<n>`; their `XLA Ops`
line holds one event per executed operation and `XLA Modules` one per
executed program. Host spans written with `jax.profiler.TraceAnnotation`
(`bench.*`) are events of `/host:CPU`. Busy is the union of the
operation intervals inside the window, averaged over the device planes;
a gap is attributed to the `bench.*` host span that covers most of it.
The device's and the host's clocks differ by about a millisecond in these
traces, so gaps shorter than a few milliseconds are not worth a name.
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


def read_planes(path: str):
    """({device plane: {"ops": [(start, end, name)], "modules": [...]}},
    [(start, end, name)] of the host's bench.* spans), times in seconds."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            entry = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    start = e.start_ns * 1e-9
                    entry[key].append((start, start + e.duration_ns * 1e-9, e.name))
            devices[plane.name] = entry
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        start = e.start_ns * 1e-9
                        host.append((start, start + e.duration_ns * 1e-9, e.name))
    return devices, sorted(host)


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


def top_ops(entry, window, k=10):
    """[name, seconds] of the operations with most device time, named
    `<program>/<operation>`."""
    lo, hi = window
    modules = sorted(entry["modules"])
    totals = {}
    mi = 0
    for s, e, name in sorted(entry["ops"]):
        if e <= lo or s >= hi:
            continue
        while mi + 1 < len(modules) and modules[mi + 1][0] <= s:
            mi += 1
        prog = module_name(modules[mi][2]) if modules and modules[mi][0] <= s else "?"
        key = f"{prog}/{short_name(name)}"
        totals[key] = totals.get(key, 0.0) + (min(e, hi) - max(s, lo))
    return [[k_, v] for k_, v in sorted(totals.items(), key=lambda kv: -kv[1])[:k]]


def reduce_trace(trace_dir_or_file: str, window_span: str = "bench.window"):
    """busy_s, window_s, idle share, top operations and longest idle gaps
    of the window that the host span `window_span` marks (the whole trace
    where there is no such span)."""
    path = (trace_dir_or_file if trace_dir_or_file.endswith(".pb")
            else find_xplane(trace_dir_or_file))
    devices, host = read_planes(path)
    if not devices:
        raise RuntimeError("the trace holds no /device:TPU plane")
    marks = [(s, e) for s, e, name in host if name == window_span]
    if marks:
        window = (min(s for s, _ in marks), max(e for _, e in marks))
    else:
        every = [t for d in devices.values() for s, e, _ in d["ops"] for t in (s, e)]
        window = (min(every), max(every))
    spans = [h for h in host if h[2] != window_span]
    busy, gaps_named, ops = [], [], []
    for entry in devices.values():
        b, gaps = busy_and_gaps(entry["ops"], window)
        busy.append(b)
        gaps_named.extend([attribute(g, spans), g[1] - g[0]] for g in gaps)
        ops.extend(top_ops(entry, window))
    window_s = window[1] - window[0]
    busy_s = sum(busy) / len(busy)
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "device_ops": sorted(ops, key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(gaps_named, key=lambda kv: -kv[1])[:10],
        "n_ops": sum(len(d["ops"]) for d in devices.values()),
    }
