"""The compile recorder: every jaxpr trace, MLIR lowering, XLA compile and
compile-cache load of the process, as a record with its function, its
thread, its start and end on `time.perf_counter` and the `Tracer` span it
fell under.

JAX emits each phase of a compile as a time span with the function's name
(`jax.monitoring` time-span events `/jax/core/compile/jaxpr_trace_duration`,
`.../jaxpr_to_mlir_module_duration`, `.../backend_compile_duration`), and
says on the compiling thread, inside the backend span, whether the
persistent cache served it (`/jax/compilation_cache/cache_hits`, or
`cache_misses` when the compiled program is written to the cache). The
recorder listens to those five names and nothing else, so it runs only
while JAX compiles: nothing per step.

A record's `phase` is `trace` (Python to jaxpr), `lower` (jaxpr to an MLIR
module), `xla_compile` (a backend span the cache did not serve) or
`cache_load` (one it did). Traces nest: a jitted function called while
another is traced is traced inside it. So a phase's seconds are the UNION
of its intervals on each thread, summed over threads, never the sum of its
spans; a function's own seconds (the `top` list) include what was traced
inside it.

One recorder a process (`RECORDER`), because JAX's listeners are the
process's: `install()` registers it once, and
`compile_cache.enable_compile_cache()` calls it, so every entry point has
it before its first compile. `attach(tracer)` also writes each record into
an enabled `Tracer`, as a span `compile.trace`, `compile.lower`,
`compile.xla` or `compile.cache_load` (cat `compile`, attribute `fun`) one
level under the span open on the compiling thread (`train.step`, a
`CompileTracker`'s span); `telemetry.tracer_from_args` attaches the CLIs'
tracer. `totals()` are the running totals since install, whatever the
retention; `snapshot(since, until)` the same totals over the retained
records that end inside [since, until], e.g. a benchmark's set-up.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from alphafold2_tpu.telemetry.trace import Tracer

#: the recorder's phases, in the order a program goes through them
PHASES = ("trace", "lower", "xla_compile", "cache_load")
#: the `Tracer` span each phase becomes
SPAN_NAMES = {"trace": "compile.trace", "lower": "compile.lower",
              "xla_compile": "compile.xla", "cache_load": "compile.cache_load"}

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
#: every JAX event name the recorder reads (tests hold JAX to them)
EVENTS = (TRACE_EVENT, LOWER_EVENT, BACKEND_EVENT, CACHE_HIT_EVENT,
          CACHE_MISS_EVENT)

_PHASE_OF = {TRACE_EVENT: "trace", LOWER_EVENT: "lower"}
#: disjoint intervals kept a (phase, thread) for the union; past it the
#: oldest goes (counted already; only a span enclosing it could miss it)
_MAX_INTERVALS = 4096


def _add_to_union(intervals: list, start: float, end: float) -> float:
    """Add [start, end] to `intervals` (sorted, disjoint, merged in place);
    the seconds it adds to their union."""
    i = len(intervals)
    while i and intervals[i - 1][1] >= start:
        i -= 1
    j, lo, hi, covered = i, start, end, 0.0
    while j < len(intervals) and intervals[j][0] <= end:
        a, b = intervals[j]
        lo, hi, covered = min(lo, a), max(hi, b), covered + (b - a)
        j += 1
    intervals[i:j] = [(lo, hi)]
    if len(intervals) > _MAX_INTERVALS:
        del intervals[0]
    return (hi - lo) - covered


class _Totals:
    """Seconds by phase (union per thread), by all phases together, counts
    by phase and seconds by (phase, function), over the records added."""

    def __init__(self):
        self.seconds = dict.fromkeys(PHASES, 0.0)
        self.counts = dict.fromkeys(PHASES, 0)
        self.union_s = 0.0
        self.by_fun: dict = {}
        self._intervals: dict = {}

    def add(self, rec: dict):
        phase, tid, start, end = rec["phase"], rec["thread"], rec["start"], rec["end"]
        self.seconds[phase] += _add_to_union(
            self._intervals.setdefault((phase, tid), []), start, end)
        self.union_s += _add_to_union(
            self._intervals.setdefault((None, tid), []), start, end)
        self.counts[phase] += 1
        fun = self.by_fun.setdefault((phase, rec["fun"]), [0.0, 0])
        fun[0] += end - start
        fun[1] += 1

    def as_dict(self, top: int) -> dict:
        heaviest = sorted(self.by_fun.items(), key=lambda kv: -kv[1][0])[:top]
        return {
            "seconds": {p: round(s, 6) for p, s in self.seconds.items()},
            "union_s": round(self.union_s, 6),
            "counts": dict(self.counts),
            "top": [{"phase": p, "fun": f, "s": round(s, 6), "n": n}
                    for (p, f), (s, n) in heaviest],
        }


class CompileRecorder:
    """Records JAX's compile phases once `install()`ed (module docstring).

    Args:
      max_records: retention bound; overflow increments `dropped` (the
        running totals still count it).
    """

    def __init__(self, max_records: int = 100_000):
        if max_records <= 0:
            raise ValueError(f"max_records must be positive, got {max_records}")
        self.max_records = max_records
        self.dropped = 0
        self._records: list = []
        self._totals = _Totals()
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._tracer: Optional[Tracer] = None
        self._installed = False

    def install(self) -> "CompileRecorder":
        """Register the listeners with JAX; a second call does nothing."""
        import jax.monitoring as mon

        with self._lock:
            if self._installed:
                return self
            self._installed = True
        mon.register_event_time_span_listener(self._on_span)
        mon.register_event_listener(self._on_event)
        return self

    def uninstall(self):
        """Take the listeners off again (a recorder made for one test)."""
        import jax.monitoring as mon

        with self._lock:
            if not self._installed:
                return
            self._installed = False
        mon.unregister_event_time_span_listener(self._on_span)
        mon.unregister_event_listener(self._on_event)

    def attach(self, tracer: Tracer):
        """Write every later record into `tracer` as a `compile.*` span too;
        a disabled tracer detaches."""
        self._tracer = tracer if tracer.enabled else None

    # ------------------------------------------------------------ listeners

    def _on_event(self, event: str, **_kw):
        if event == CACHE_HIT_EVENT:
            self._tls.hit = True
        elif event == CACHE_MISS_EVENT:
            self._tls.hit = False

    def _on_span(self, event: str, start_time: float, end_time: float, **kw):
        if event == BACKEND_EVENT:
            phase = "cache_load" if getattr(self._tls, "hit", False) else "xla_compile"
            self._tls.hit = False
        elif event in _PHASE_OF:
            phase = _PHASE_OF[event]
        else:
            return
        # JAX times the span by the wall clock; this is its end on ours
        end = time.perf_counter()
        dur = max(0.0, end_time - start_time)
        fun = str(kw.get("fun_name", "?"))
        tracer = self._tracer
        rec = {"phase": phase, "fun": fun, "start": end - dur, "end": end,
               "thread": threading.get_ident(),
               "parent": tracer.open_span() if tracer is not None else None}
        with self._lock:
            self._totals.add(rec)
            if len(self._records) >= self.max_records:
                self.dropped += 1
            else:
                self._records.append(rec)
        if tracer is not None:
            tracer.add(SPAN_NAMES[phase], dur, cat="compile", end_at=end, fun=fun)

    # ------------------------------------------------------------- reading

    def records(self) -> list:
        """Copies of the retained records, in the order they ended."""
        with self._lock:
            return [dict(r) for r in self._records]

    def totals(self, top: int = 10) -> dict:
        """Running totals since install: `seconds` by phase, `union_s`,
        `counts` by phase (`xla_compile` = programs compiled,
        `cache_load` = programs loaded from the cache), the `top`
        heaviest (phase, function) pairs, and `dropped`."""
        with self._lock:
            return dict(self._totals.as_dict(top), dropped=self.dropped)

    def snapshot(self, since: Optional[float] = None,
                 until: Optional[float] = None, top: int = 10) -> dict:
        """`totals()` over the retained records that end inside
        [since, until] (`time.perf_counter` values; None is open)."""
        lo = float("-inf") if since is None else since
        hi = float("inf") if until is None else until
        window = _Totals()
        with self._lock:
            for rec in self._records:
                if lo <= rec["end"] <= hi:
                    window.add(rec)
            return dict(window.as_dict(top), dropped=self.dropped)


#: the process's recorder
RECORDER = CompileRecorder()


def install() -> CompileRecorder:
    """Install the process's recorder (idempotent)."""
    return RECORDER.install()


def attach(tracer: Tracer):
    """Install the process's recorder and write its records into `tracer`."""
    install().attach(tracer)


def totals(top: int = 10) -> dict:
    return RECORDER.totals(top)


def snapshot(since: Optional[float] = None, until: Optional[float] = None,
             top: int = 10) -> dict:
    return RECORDER.snapshot(since, until, top)
