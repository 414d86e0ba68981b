"""From a profiler trace to device seconds by named scope and phase.

The program names the parts of its training step with `jax.named_scope`
(the documented list: `alphafold2_tpu.telemetry.profiling.SCOPES`). JAX
writes the name stack into each operation's `op_name`, which the profiler
keeps as `tf_op` in the METADATA of every `XLA Ops` event
(`xplane.metadata_of`). One operation's path, as found on the v5e
(PERF.md section 3 has one of each phase):

    jit(train_step)/while/body/closed_call/transpose(jvp(trunk))/
      reversible_bwd/while/body/closed_call/jvp(seq_attn)/while/body/
      closed_call/attn_core/while/body/closed_call/checkpoint/dot_general

**Scope** is the innermost OUTER name on the path (`seq_attn`; JAX wraps it
as `jvp(seq_attn)` / `transpose(jvp(seq_attn))`), plus the innermost INNER
name after it (`seq_attn/attn_core`). An operation under no documented
name is `unscoped`: reported, never dropped. **Phase**, from JAX's own
wrappers and the program's one marker:

    remat        `rematted_computation` on the path: the forward that
                 `jax.checkpoint` runs again inside a backward
    backward     a `transpose(...)` element (after `reversible_bwd`, where
                 that marker is on the path: the marker itself sits under
                 `transpose(jvp(trunk))`)
    reconstruct  under `reversible_bwd` with neither: the reversible
                 trunk recomputing an op's forward to invert the layer
    forward      a `jvp(...)` element, or a model / tail scope with none
                 of the above (a forward-only program)
    other        the optimizer, and anything unscoped outside autodiff

**Self time only.** A `while` / `call` / `conditional` event lasts as long
as the events nested inside it on the same line; an event's self time is
its duration less what its children cover, so the table adds up to the
device's busy time (`residue_s` says how far it does not).

`xla_flops` / `xla_bytes` are the sums of the events' own `flops` and
`bytes_accessed` (XLA's cost analysis of each executed operation, a
container's left out), printed as achieved TFLOP/s and GB/s UNDER XLA'S
NAME: the benchmark's analytic count (`flops.py`) stays the yardstick for
any share of a peak.

Idle gaps are named by the host span that covers most of them, over
every documented prefix: the benchmark's `bench.*` and, since an enabled
`telemetry.Tracer` writes its spans on the profiler's clock, the
program's own.

This is the ONE reduction of a traced run (`run.py` makes it once, before
it removes the trace): the same pass gives the result line's `busy_s`,
`window_s`, idle share and `breakdown`, whose `device_ops` are self
seconds summed by `trace_reduce.op_label` (`seq_attn/attn_core backward`;
the HLO name where an operation has no documented scope).
"""
from __future__ import annotations

import re
import time

import trace_reduce
import xplane

#: host spans that may name an idle gap: the benchmark's own and the
#: program tracer's (docs/OBSERVABILITY.md "Span taxonomy")
HOST_SPAN_PREFIXES = ("bench.", "train.", "serving.", "fleet.", "featurize.",
                      "predict.")
PHASES = ("forward", "reconstruct", "remat", "backward", "other")
UNSCOPED = trace_reduce.UNSCOPED
_CONTAINERS = ("while", "call", "conditional")
# the wrappers JAX's transformations put around a name-stack element
_WRAPPED = re.compile(r"^(?:(?:jvp|transpose|vmap)\()+([A-Za-z0-9_]*)\)+$")


def scope_names():
    """(outer names, inner names, the reversible marker, the optimizer's
    name) as the program documents them."""
    from alphafold2_tpu.telemetry import profiling

    return (frozenset(profiling.OUTER_SCOPES), frozenset(profiling.INNER_SCOPES),
            profiling.REVERSIBLE_BWD_SCOPE, profiling.OPTIMIZER_SCOPE)


def path_of(tf_op: str) -> list:
    """`jit(f)/a/jvp(b)/mul;jit(f)/a/add:` -> [`jit(f)`, `a`, `jvp(b)`,
    `mul`]: the first of the names a fusion merged, without the `:type`
    suffix the profiler appends."""
    name = tf_op.split(";", 1)[0]
    if ":" in name:
        name = name.rpartition(":")[0]
    return name.split("/")


def unwrap(element: str) -> str:
    """`transpose(jvp(seq_attn))` -> `seq_attn`; an element with no such
    wrapper (`jit(mds)`, `while`) comes back unchanged."""
    m = _WRAPPED.match(element)
    return m.group(1) if m else element


def classify(tf_op, names=None):
    """(scope key, phase) of one operation's `tf_op`."""
    outer_names, inner_names, marker, optimizer = names or scope_names()
    if not tf_op:
        return UNSCOPED, "other"
    path = path_of(tf_op)
    outer, inner, after_marker = None, None, 0
    for i, element in enumerate(path):
        bare = unwrap(element)
        if bare in outer_names:
            outer, inner = bare, None
        elif bare in inner_names:
            inner = bare
        elif bare == marker:
            after_marker = i + 1
    key = (outer or UNSCOPED) + (f"/{inner}" if inner else "")
    tail = path[after_marker:]
    if "rematted_computation" in tail:
        phase = "remat"
    elif any(e.startswith("transpose(") for e in tail):
        phase = "backward"
    elif after_marker:
        # the glue of the hand-written backward (inverting a residual,
        # adding cotangents) carries no jvp(...) of its own
        phase = "reconstruct" if any(e.startswith("jvp(") for e in tail) else "backward"
    elif outer == optimizer:
        phase = "other"
    elif outer or any(e.startswith("jvp(") for e in path):
        phase = "forward"
    else:
        phase = "other"
    return key, phase


def self_times(events, window):
    """[(self seconds, index)] of `events` = [(start, end, ...)], clipped
    to `window`: each event's duration less what the events nested inside
    it cover. An event that only overlaps its predecessor (not nested) is
    cut at the predecessor's end, so that no instant is counted twice."""
    lo, hi = window
    clipped = sorted(
        ((max(ev[0], lo), min(ev[1], hi), i) for i, ev in enumerate(events)
         if ev[1] > lo and ev[0] < hi),
        key=lambda t: (t[0], -t[1]))
    out = [0.0] * len(clipped)
    stack = []  # (end, position in `out`) of the events still open
    for pos, (s, e, _) in enumerate(clipped):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            e = min(e, stack[-1][0])
            out[stack[-1][1]] -= e - s
        out[pos] += e - s
        stack.append((e, pos))
    return [(t, clipped[pos][2]) for pos, t in enumerate(out)]


def read_planes(path: str):
    """({device plane: {"ops": [(start, end, metadata row)], "modules":
    [(start, end, name)]}}, host spans [(start, end, name)] of every
    documented prefix), times in seconds."""
    space = xplane.read(path)
    devices, host = {}, []
    for plane in space.planes:
        if plane.name.startswith("/device:TPU:"):
            md = xplane.metadata_of(plane)
            entry = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    entry["ops"] = [(s, e, md[i]) for s, e, i in xplane.events_of(line)]
                elif line.name == "XLA Modules":
                    entry["modules"] = [(s, e, md[i]["name"])
                                        for s, e, i in xplane.events_of(line)]
            devices[plane.name] = entry
        elif plane.name == "/host:CPU":
            md = xplane.metadata_of(plane)
            for line in plane.lines:
                for s, e, i in xplane.events_of(line):
                    if md[i]["name"].startswith(HOST_SPAN_PREFIXES):
                        host.append((s, e, md[i]["name"]))
    return devices, sorted(host)


def is_container(row: dict) -> bool:
    return (row.get("hlo_category") in _CONTAINERS
            or row.get("display_name", "").split(".")[0] in _CONTAINERS)


def reduce_scopes(trace_dir_or_file: str, window_span: str = "bench.window"):
    """The scope x phase table of the window that the host span
    `window_span` marks (the whole trace where there is none), averaged
    over the device planes; see the module's docstring."""
    names = scope_names()  # imports the program (and JAX): not the reduction's time
    t0 = time.perf_counter()
    path = (trace_dir_or_file if trace_dir_or_file.endswith(".pb")
            else trace_reduce.find_xplane(trace_dir_or_file))
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
    n = len(devices)
    table, xla, cache, by_path, by_category, by_label = {}, {}, {}, {}, {}, {}
    busy_s = sum_self = 0.0
    gaps_named, n_ops = [], 0
    for entry in devices.values():
        modules, mi = sorted(entry["modules"]), 0
        busy, gaps = trace_reduce.busy_and_gaps(entry["ops"], window)
        busy_s += busy / n
        gaps_named.extend([trace_reduce.attribute(g, spans), g[1] - g[0]] for g in gaps)
        n_ops += len(entry["ops"])
        for seconds, i in self_times(entry["ops"], window):  # in order of start
            start, _, row = entry["ops"][i]
            while mi + 1 < len(modules) and modules[mi + 1][0] <= start:
                mi += 1
            program = modules[mi][2] if modules and modules[mi][0] <= start else "?"
            tf_op = row.get("tf_op", "")
            if tf_op not in cache:
                cache[tf_op] = classify(tf_op, names)
            key, phase = cache[tf_op]
            cell = table.setdefault(key, dict.fromkeys(PHASES, 0.0))
            cell[phase] += seconds / n
            sum_self += seconds / n
            category = row.get("hlo_category", "?")
            label = tf_op or f"<no tf_op: {category}>"
            by_path[label] = by_path.get(label, 0.0) + seconds / n
            label = trace_reduce.op_label(key, phase, program, row["name"])
            by_label[label] = by_label.get(label, 0.0) + seconds / n
            cats = by_category.setdefault(key, {})
            cats[category] = cats.get(category, 0.0) + seconds / n
            if not is_container(row):
                x = xla.setdefault(key, {"xla_flops": 0.0, "xla_bytes": 0.0})
                x["xla_flops"] += (row.get("flops") or 0) / n
                x["xla_bytes"] += (row.get("bytes_accessed") or 0) / n
    for key, x in xla.items():
        seconds = sum(table[key].values())
        x["xla_tflops_per_s"] = x["xla_flops"] / seconds * 1e-12 if seconds else None
        x["xla_gb_per_s"] = x["xla_bytes"] / seconds * 1e-9 if seconds else None
    paths = sorted(([label, *cache.get(label, (UNSCOPED, "other")), seconds]
                    for label, seconds in by_path.items()), key=lambda r: -r[3])
    window_s = window[1] - window[0]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        # [name, self seconds] of the ten heaviest: the result line's breakdown
        "device_ops": [[k, v] for k, v in
                       sorted(by_label.items(), key=lambda kv: -kv[1])[:10]],
        "sum_self_s": sum_self,
        "residue_s": busy_s - sum_self,
        "scopes": table,
        "xla": xla,
        # {scope: {XLA's hlo_category: seconds}}: matmuls (`convolution
        # fusion`) apart from elementwise passes (`loop fusion`) and from a
        # `while`'s own time
        "categories": by_category,
        # [tf_op, scope, phase, seconds]: where a share comes from, and
        # which names to add when `unscoped` grows
        "top_paths": paths[:40],
        "top_unscoped": [r for r in paths if r[1] == UNSCOPED][:10],
        "idle_gaps": sorted(gaps_named, key=lambda kv: -kv[1])[:10],
        "n_ops": n_ops,
        "reduce_seconds": time.perf_counter() - t0,
    }


def seconds_of(table: dict, outer=None, phases=PHASES) -> float:
    """Seconds of the table's cells whose outer name is one of `outer`
    (`seq_attn` covers `seq_attn/attn_core`; every cell where None), over
    `phases`."""
    return sum(cell[p] for key, cell in table.items()
               if outer is None or key.split("/", 1)[0] in outer
               for p in phases)


def format_table(reduced: dict) -> str:
    """The table as text: one row a scope, seconds by phase, the share of
    busy time, XLA's own achieved rates."""
    busy = reduced["busy_s"]
    unit, scale = ("s", 1.0) if busy >= 1.0 else ("ms", 1e3)
    rows = sorted(reduced["scopes"].items(), key=lambda kv: -sum(kv[1].values()))
    head = (f"{'scope (' + unit + ')':<28}" + "".join(f"{p:>12}" for p in PHASES)
            + f"{'total':>10}{'share':>8}{'xla_TF/s':>10}{'xla_GB/s':>10}")
    lines = [head]
    for key, cell in rows:
        total = sum(cell.values())
        x = reduced["xla"].get(key, {})
        tf, gb = x.get("xla_tflops_per_s"), x.get("xla_gb_per_s")
        lines.append(
            f"{key:<28}" + "".join(f"{scale * cell[p]:>12.4f}" for p in PHASES)
            + f"{scale * total:>10.4f}{100 * total / busy if busy else 0:>7.2f}%"
            + (f"{tf:>10.2f}" if tf is not None else f"{'-':>10}")
            + (f"{gb:>10.1f}" if gb is not None else f"{'-':>10}"))
    by_phase = {p: sum(c[p] for c in reduced["scopes"].values()) for p in PHASES}
    lines.append(f"{'all scopes':<28}"
                 + "".join(f"{scale * by_phase[p]:>12.4f}" for p in PHASES)
                 + f"{scale * reduced['sum_self_s']:>10.4f}")
    lines.append(
        f"busy {busy:.4f} s of a {reduced['window_s']:.4f} s window; self times "
        f"sum to {reduced['sum_self_s']:.4f} s (residue {reduced['residue_s']:.2e} s); "
        f"{reduced['n_ops']} events reduced in {reduced['reduce_seconds']:.1f} s")
    for name, seconds in reduced["idle_gaps"][:5]:
        lines.append(f"idle gap {seconds * 1e3:8.3f} ms under {name}")
    if seconds_of(reduced["scopes"], outer=(UNSCOPED,)) > 0.5 * busy:
        lines.append(
            "most of the busy time is under no documented name: the program "
            "has none (a forward of another model?), or its executable came "
            "from a compile cache filled before the names existed (JAX's cache "
            "key leaves metadata out: empty the cache directory and run again)")
    return "\n".join(lines)
