"""What every kind of run shares: the files a cell is made of, the device
check, the table of peaks, weights from the seed, and the result line."""
from __future__ import annotations

import importlib
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT):
    """The cell's entry of BENCHMARK.json with its configuration and
    traffic files, found by name."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")
    return bench, cell, config, traffic


def cell_metrics(bench, cell_name: str, group: str):
    """The `end_to_end` or `per_layer` entries this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def metric_names(bench, cell_name: str, group: str):
    return [m["name"] for m in cell_metrics(bench, cell_name, group)]


def module(kind: str, name: str):
    """benchmarks/<kind>/<name>.py, found by the name a data file gives."""
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    return importlib.import_module(f"{kind}.{name}")


def require_tpu(chips: int, dry: bool):
    """The devices of this run. The measured path takes TPUs only."""
    import jax

    devices = jax.devices()
    if dry:
        return devices[:1]
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"benchmarks/run.py needs a TPU; JAX found {devices[0].platform!r} "
            f"({devices[0].device_kind}). No result is printed without the chip "
            f"(--dry rehearses the control flow at toy shapes).")
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def peaks_for(device_kind: str) -> dict:
    table = load_json("peaks.json")["by_device_kind"]
    if device_kind not in table:
        raise SystemExit(f"no peaks recorded for device_kind {device_kind!r}; "
                         f"add it to benchmarks/peaks.json with its source")
    return table[device_kind]


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def weights_seed(ctx) -> int:
    """The seed of what a cell IS: its weights and anything else of the
    model it trains (a decoder cell's rank -> id map). The traffic file's
    `weights_seed` where it states one, so that every run of the cell
    trains one model and only what it is fed (drawn from `--seed`)
    differs; else the run's `--seed`."""
    return ctx["traffic"].get("weights_seed", ctx["seed"])


def key_name(k) -> str:
    """One step of a tree path as a plain string."""
    return str(getattr(k, "key", getattr(k, "idx", k)))


def make_params(shapes, key, stacked=(), copies=1):
    """Weights on the device in one jitted call, by each leaf's role:
    tables N(0, 1); linear `w` and `b` U(+-1/sqrt(fan_in)); LayerNorm
    scale 1, bias 0. `shapes` is a tree of ShapeDtypeStructs (the layout
    the program reads); `stacked` names top-level subtrees whose leaves
    carry a leading depth axis. With `copies` > 1 the same call is made
    again for each further copy (one compiled program, no per-leaf copies)
    and a list is returned."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    index = {tuple(key_name(k) for k in path): leaf for path, leaf in leaves}

    def fan_in(path):
        w = index.get(path[:-1] + ("w",))
        if w is None:
            return 1
        lead = 1 if any(path[:len(s)] == s for s in stacked) else 0
        return max(1, math.prod(w.shape[lead:-1]))

    def build(key):
        # two draws for the whole tree (one uniform, one normal), cut into
        # the leaves: a per-leaf draw compiles for most of a minute
        k_u, k_n = jax.random.split(key)
        sizes = [math.prod(leaf.shape) for leaf in index.values()]
        uniform = jax.random.uniform(k_u, (sum(sizes),), jnp.float32, -1.0, 1.0)
        tables = [n for path, n in zip(index, sizes) if path[-1] == "table"]
        normal = jax.random.normal(k_n, (max(1, sum(tables)),), jnp.float32)
        out, at_u, at_n = [], 0, 0
        for (path, leaf), n in zip(index.items(), sizes):
            role = path[-1]
            if role == "table":
                v = normal[at_n:at_n + n].reshape(leaf.shape)
                at_n += n
            elif role == "scale":
                v = jnp.ones(leaf.shape, jnp.float32)
            elif role == "bias":
                v = jnp.zeros(leaf.shape, jnp.float32)
            elif role in ("w", "b"):
                v = uniform[at_u:at_u + n].reshape(leaf.shape) / math.sqrt(fan_in(path))
            else:
                raise ValueError(f"no rule for parameter leaf {'/'.join(path)}")
            at_u += n
            out.append(v.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    fn = jax.jit(build)
    if copies == 1:
        return fn(key)
    return [fn(key) for _ in range(copies)]


def device_block(devices, planned_peak_bytes: int = 0) -> dict:
    """The `device` entry. `memory_peak_bytes` is the larger of the
    runtime's peak counter and the compiler's planned peak of the largest
    executable the window ran: on this runtime the counter sees live
    arrays only, not a program's temporaries."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peak, int(planned_peak_bytes))}


def planned_peak(compiled) -> int:
    ma = compiled.memory_analysis()
    peak = getattr(ma, "peak_memory_in_bytes", 0)
    if peak:
        return int(peak)
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def log(*parts):
    """An earlier line: to standard error, never the result line."""
    print(*parts, file=sys.stderr, flush=True)


#: the first phase of every run: imports, the compile cache, the devices
IMPORTS_PHASE = "imports_and_device"


class Setup:
    """Set-up time by phase, from the start of the process."""

    def __init__(self, t_process_start: float):
        self.t0 = t_process_start
        self.last = t_process_start
        self.phases = []

    def mark(self, name: str):
        now = time.perf_counter()
        self.phases.append((name, now - self.last))
        self.last = now

    def total(self) -> float:
        return self.last - self.t0

    def table(self) -> dict:
        return {name: round(s, 3) for name, s in self.phases}

    def facts(self) -> dict:
        """`setup_s` (process start -> first measured operation, all of it)
        and its two halves: importing JAX and the program and taking the
        chip, which the machine sets, and everything after, which the
        program can shorten."""
        held = dict(self.phases).get(IMPORTS_PHASE, 0.0)
        return {"setup_s": self.total(), "setup_imports_and_device_s": held,
                "setup_after_device_s": self.total() - held}


def judge(compared: dict):
    """`correct` and the printed comparison from {name: (value, limit)}:
    every number at or under its limit, and finite."""
    rows, ok = {}, True
    for name, (value, limit) in compared.items():
        good = value is not None and math.isfinite(value) and value <= limit
        ok = ok and good
        rows[name] = {"value": value, "limit": limit}
    return ok, rows


def judge_values(values: dict, limits: dict):
    """`judge` of the numbers a kind read against the cell's limits
    (`nonfinite_losses` is held at 0 everywhere); a number the cell's file
    gives no limit is logged as read, not held (limits/<cell>.json says
    why no upper reading separates it)."""
    limits = dict(limits, nonfinite_losses=0.0)
    for name in sorted(set(values) - set(limits)):
        log(f"read, not held: {name} = {values[name]}")
    return judge({k: (values[k], limits[k]) for k in values if k in limits})


def finish(result: dict, compared_rows: dict):
    """The comparison as the last lines of standard error, then the one
    result line, with the comparison as its last key."""
    for name, row in compared_rows.items():
        log(f"compared {name} = {row['value']} (limit {row['limit']})")
    result = dict(result)
    result["compared"] = compared_rows
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


class CompileWatch:
    """Counts programs compiled, or loaded from the persistent cache,
    while armed. A window that compiles is a failed run, not a slow one."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring as mon

        self.armed = False
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, _secs, **_kw):
        if self.armed and event == self._COMPILE:
            self.compiles += 1

    def _on_event(self, event, **_kw):
        if self.armed and event == self._HIT:
            self.cache_hits += 1

    def __enter__(self):
        self.armed = True
        return self

    def __exit__(self, *exc):
        self.armed = False

    def check(self, what: str):
        if self.compiles or self.cache_hits:
            raise SystemExit(
                f"{what}: {self.compiles} program(s) compiled and "
                f"{self.cache_hits} loaded from the cache inside the measured "
                f"window; every shape must be warm before it. Failed run.")


class Heartbeat:
    """A thread that sleeps `every` seconds at a time and keeps the longest
    it overslept since `take()` was last called. A process that was off the
    CPU for a second (its machine's host busy, the VM paused) oversleeps by
    that second; one that waited a second for a late device does not."""

    def __init__(self, every: float = 0.01):
        import threading

        self.every, self.late, self.alive = every, 0.0, True
        self.lock = threading.Lock()
        self.thread = threading.Thread(target=self._beat, daemon=True)
        self.thread.start()

    def _beat(self):
        while self.alive:
            t = time.perf_counter()
            time.sleep(self.every)
            with self.lock:
                self.late = max(self.late, time.perf_counter() - t - self.every)

    def take(self) -> float:
        with self.lock:
            late, self.late = self.late, 0.0
        return late

    def stop(self):
        self.alive = False
        self.thread.join()


#: (name, file, how its text gives seconds) of the kernel's counters of time
#: lost to the host: `runq`, this thread runnable but waiting for a CPU;
#: `steal`, the hypervisor running someone else on our CPUs; `pressure`,
#: some task of the machine stalled for a CPU
_HOST_WAITS = (
    ("runq", "/proc/thread-self/schedstat", lambda t: int(t.split()[1]) * 1e-9),
    ("steal", "/proc/stat",
     lambda t: int(t.split("\n", 1)[0].split()[8]) / os.sysconf("SC_CLK_TCK")),
    ("pressure", "/proc/pressure/cpu",
     lambda t: int(t.split("\n", 1)[0].rsplit("=", 1)[1]) * 1e-6),
)


def host_waits() -> dict:
    """Seconds the kernel says were lost to the host so far, by
    `_HOST_WAITS`. A counter this machine's kernel does not offer is left
    out (the sealed one-chip machine of PR 31 offered none)."""
    out = {}
    for name, path, seconds in _HOST_WAITS:
        try:
            with open(path) as f:
                out[name] = seconds(f.read())
        except (OSError, IndexError, ValueError):
            pass
    return out


def step_stats(step_times, window_s: float) -> dict:
    """The window's two quotients. `train_step_s` is the whole window over
    ALL its steps: what a trainer pays, stalls included.
    `train_step_less_slowest_s` leaves the window's slowest step out of
    both the time and the count: the steadier statistic beside it, equal to
    it to 0.1% on a window whose steps repeat, unmoved by ONE stalled step
    (two stalls, or a stall in every window, still show in it)."""
    steps = len(step_times)
    return {"steps": steps, "window_s": window_s,
            "train_step_s": window_s / steps,
            "train_step_less_slowest_s": ((window_s - max(step_times)) / (steps - 1)
                                          if steps > 1 else None)}


def timed_window(ctx, runner, after_traced_step=None, after_timed_step=None) -> dict:
    """The measured part of a training cell: with `--trace 1` first
    `trace_steps` steps under the profiler (`bench.window` / `bench.step`),
    then `runner.step()` until `--seconds` have passed, nothing compiled in
    either. `runner.step()` returns (its batch, the fetched loss) and keeps
    `runner.dispatched_at`, the clock when its dispatch returned. The two
    callbacks run after each traced and each timed step: a kind keeps
    there what the step returned on the device, to read after the window.

    Every timed step is logged by four clocks, so that a stalled step says
    where it stalled: `wall` seconds; `cpu`, this process's CPU seconds
    (`time.process_time`: all threads); `dispatch`, from the step's start
    to the dispatch's return (the feed and the call); `wait`, from there to
    the loss on the host (the device, and the way back); `late`, the most
    the heartbeat overslept in it (the process was off the CPU that long);
    `runq`, what the kernel says this thread waited for a CPU in it. The
    window's `host_waits` are logged whole.
    """
    import gc

    import jax

    traffic, seconds = ctx["traffic"], ctx["seconds"]
    watch = CompileWatch()
    gc.collect()
    gc.freeze()
    losses, clocks, traced = [], [], None
    with watch:
        if ctx["trace"]:
            traced = ctx["trace_dir"]
            jax.profiler.start_trace(traced)
            with jax.profiler.TraceAnnotation("bench.window"):
                for _ in range(traffic["trace_steps"]):
                    with jax.profiler.TraceAnnotation("bench.step"):
                        _, value = runner.step()
                    losses.append(value)
                    if after_traced_step:
                        after_traced_step()
            jax.profiler.stop_trace()
        beat = Heartbeat()  # after the profiler: it watches the timed steps alone
        waits0 = waits = host_waits()
        t0 = time.perf_counter()
        while True:
            t_step, cpu, runq = time.perf_counter(), time.process_time(), waits.get("runq")
            _, value = runner.step()
            now, waits = time.perf_counter(), host_waits()
            clocks.append({"wall": now - t_step, "cpu": time.process_time() - cpu,
                           "dispatch": runner.dispatched_at - t_step,
                           "wait": now - runner.dispatched_at, "late": beat.take(),
                           "runq": waits["runq"] - runq if "runq" in waits else None})
            losses.append(value)
            if after_timed_step:
                after_timed_step()
            if now - t0 >= seconds or ctx["dry"] and len(clocks) >= 2:
                break
        window_s = time.perf_counter() - t0
        beat.stop()
    watch.check(ctx["cell"]["name"])
    step_times = [c["wall"] for c in clocks]
    stats = step_stats(step_times, window_s)
    by_wall = sorted(range(len(clocks)), key=lambda i: step_times[i])
    median, slowest = by_wall[len(by_wall) // 2], by_wall[-1]
    log(f"window: {stats['steps']} steps in {window_s:.4f} s; train_step_s "
        f"{stats['train_step_s']:.5f}, less its slowest step "
        f"{stats['train_step_less_slowest_s']}; per-step min {min(step_times):.4f} "
        f"median {step_times[median]:.4f} max {step_times[slowest]:.4f}; each "
        f"{[round(t, 4) for t in step_times]}; last loss {losses[-1]:.5f}")
    for what, i in (("median", median), ("slowest", slowest)):
        log(f"{what} step {i + 1} by clock (s):",
            {k: v if v is None else round(v, 4) for k, v in clocks[i].items()})
    overslept = [c["late"] for c in clocks if c["late"] >= 0.05]
    log(f"heartbeat: overslept by 50 ms or more in {len(overslept)} of {len(clocks)} "
        f"steps, at most {max(c['late'] for c in clocks):.4f} s; the kernel's counters "
        f"over the window (s):", {k: round(waits[k] - waits0[k], 4) for k in waits})
    stalled = step_times[slowest] > 1.25 * step_times[median]
    if stalled:
        log(f"STALLED step {slowest + 1}: {step_times[slowest] - step_times[median]:.3f} s "
            f"over the median; every step's clocks: "
            + json.dumps([[c[k] if c[k] is None else round(c[k], 4)
                           for k in ("wall", "cpu", "dispatch", "wait", "late", "runq")]
                          for c in clocks]))
    return {**stats, "losses": losses, "trace_dir": traced, "stalled": stalled}


def made_up_facts(scope_keys, phases: dict, **facts) -> dict:
    """Facts for a kind's `dry_facts()`: the times every training kind
    reports, made up, and a made-up reduction of a trace in which each of
    `scope_keys` took `phases` seconds, the optimizer and `unscoped` a
    little; `facts` are the kind's own."""
    nothing = dict.fromkeys(phases, 0.0)
    scopes = {key: dict(phases) for key in scope_keys}
    scopes["optimizer"] = {**nothing, "other": 0.004}
    scopes["unscoped"] = {**nothing, "other": 0.002}
    busy = sum(sum(cell.values()) for cell in scopes.values())
    reduced = {"busy_s": busy, "window_s": 1.02 * busy, "idle_share": 1 - 1 / 1.02,
               "scopes": scopes}
    setup = {"setup_s": 60.0, "setup_imports_and_device_s": 12.0,
             "setup_after_device_s": 48.0}
    return {**step_stats([5.0] * 7 + [5.5], 40.5), **setup, "planned_hbm_bytes": 7.4e9,
            "scopes": reduced, "trace": reduced, **facts}


def context(workload, seed, seconds, trace, dry, fault, t_process_start):
    """Everything a kind of run is handed: the cell's files, the devices,
    the compile cache in its fixed place, the built configuration."""
    bench, cell, config, traffic = load_cell(workload)
    setup = Setup(t_process_start)
    from alphafold2_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = require_tpu(cell["chips"], dry)
    if not dry:
        peaks_for(devices[0].device_kind)  # an unknown chip fails now, not later
    setup.mark(IMPORTS_PHASE)
    seconds = seconds if seconds is not None else bench["run_seconds"]
    log(f"cell {cell['name']} seed {seed} seconds {seconds} trace {int(trace)} "
        f"cache {cache_dir} jax {jax.__version__}")
    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    limits = load_json("limits", cell["name"] + ".json")["limits"]
    return {
        "bench": bench, "cell": cell, "config": config, "traffic": traffic,
        "seed": seed, "seconds": seconds, "trace": trace, "dry": dry,
        "devices": devices, "setup": setup, "limits": limits,
        "trace_dir": trace_dir, "fault": fault,
        "built": module("builders", config["builder"]).build(config, dry),
    }
