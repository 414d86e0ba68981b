"""Span-based structured tracing: where the time goes, as data.

The repo's phases — harness step (data fetch / compute / metrics fetch /
checkpoint save), serving request lifecycle (enqueue -> batch -> compile
-> execute -> respond), reliability restart/recovery episodes — were
observable only through `print` timestamps. A `Tracer` turns each phase
into a nestable, thread-safe span with attributes, exportable as:

  * Chrome trace-event JSON (`export_chrome`): open in Perfetto
    (https://ui.perfetto.dev) or chrome://tracing — per-thread timelines
    with nesting rendered from same-tid ts/dur containment;
  * an in-process summary (`summary()`): per-span-name count / total /
    mean / max seconds, the payload `ServingEngine.stats()` embeds.

One clock with the device: every span of an ENABLED tracer also enters a
`jax.profiler.TraceAnnotation` of the same name for its lifetime, so any
profiler capture taken while it runs (`--profile-dir`, `/profilez`) holds
the span on `/host:CPU` beside the device's operations, and
benchmarks/scope_reduce.py can name the device's idle gaps by it. Outside a
capture the annotation is a flag test in native code.

Cost contract: a DISABLED tracer is near-zero-cost — `span()` returns a
shared no-op singleton (no allocation, no lock, no record), so
instrumentation can stay in production code paths unconditionally. Use
the module-level `NULL_TRACER` as the default wiring value.

Every span records `parent`, the name of the span open on its thread
when it began (None at the top): the span that caused it. A span measured
elsewhere (`add`) takes the open span as its parent and sits one level
under it, so JAX's compile phases (`telemetry/compile_record.py`) land
inside the `train.step` or `serving_compile` that triggered them.

Memory is bounded: at most `max_spans` completed spans are retained;
further spans are counted in `dropped` (reported in `summary()` and the
Chrome export) rather than silently discarded — truncated data must
never read as complete data.

Trace correlation: `new_trace_id()` mints a request-scoped id at the
serving front door (fleet/engine `submit()`); `Tracer.bind_trace(id)`
binds it thread-locally so every span recorded on that thread while
bound carries a `trace_id` attribute, and multi-request phases (a batch,
a device dispatch) attach the explicit `trace_ids` list instead. The
same id travels queueing, dispatcher routing, requeues onto OTHER
replicas, and the response (`PredictionResult.trace_id`), so one grep
over an export reconstructs a request's whole cross-thread,
cross-replica life (docs/OBSERVABILITY.md "The operations plane").
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
import uuid
from typing import Optional

from jax.profiler import TraceAnnotation


def new_trace_id() -> str:
    """A fresh 16-hex-char request trace id (random, not time-derived:
    two fleets started in the same instant must not collide)."""
    return uuid.uuid4().hex[:16]


class _NullSpan:
    """Shared no-op span: the disabled-tracer fast path. Stateless and
    reentrant, so ONE module-level instance serves every call site."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, key, value):  # noqa: ARG002 — signature parity with _Span
        return self


_NULL_SPAN = _NullSpan()


class _Span:
    """One live span; created by `Tracer.span` and recorded on exit."""

    __slots__ = ("_tracer", "name", "cat", "attrs", "_t0", "_depth",
                 "_parent", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.attrs = attrs

    def set(self, key, value):
        """Attach/overwrite one attribute mid-span."""
        self.attrs[key] = value
        return self

    def __enter__(self):
        self._depth, self._parent = self._tracer._push(self.name)
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = self._tracer._clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = self._tracer._clock() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        self._tracer._pop()
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        self._tracer._record(self.name, self.cat, self._t0, dur, self._depth,
                             self._parent, self.attrs)
        return False


class Tracer:
    """Thread-safe collector of completed spans.

    Args:
      enabled: False gives the no-op fast path (see module docstring).
      max_spans: retention bound; overflow increments `dropped`.
      clock: injectable monotonic clock (tests pin time).
    """

    def __init__(self, enabled: bool = True, max_spans: int = 100_000,
                 clock=time.perf_counter):
        if max_spans <= 0:
            raise ValueError(f"max_spans must be positive, got {max_spans}")
        self.enabled = enabled
        self.max_spans = max_spans
        self._clock = clock
        self._t_origin = clock()
        self._lock = threading.Lock()
        self._spans: list = []
        self.dropped = 0
        self._tls = threading.local()

    # ------------------------------------------------------------ recording

    def span(self, name: str, cat: str = "app", **attrs):
        """Context manager for one timed phase; attributes are JSON leaves.

        ``with tracer.span("serving.batch", cat="serving", bucket=64):``
        """
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, cat, attrs)

    def add(self, name: str, duration_s: float, cat: str = "app",
            end_at: Optional[float] = None, **attrs):
        """Record a span measured elsewhere (e.g. queue wait computed from
        a request's submit timestamp): ends at `end_at` (default: now) on
        this tracer's clock, started `duration_s` earlier, one level under
        the span open on this thread, which is its `parent`. Such a span
        (`serving.queue_wait`, the pipelined `serving.execute`, the
        `compile.*` phases) is in this tracer's exports only: a profiler
        annotation cannot be written after the fact, so it is not on
        `/host:CPU` of a capture."""
        if not self.enabled:
            return
        end = self._clock() if end_at is None else end_at
        stack = self._stack()
        self._record(name, cat, end - duration_s, duration_s, len(stack),
                     stack[-1] if stack else None, attrs)

    @contextlib.contextmanager
    def bind_trace(self, trace):
        """Bind a trace identity to the CURRENT thread for the enclosed
        block: every span recorded here (nested spans included, helpers
        that never heard of tracing included — the AOT compile inside a
        device dispatch is the motivating case) inherits it unless the
        span set its own. `trace` is one id (str; spans gain `trace_id`)
        or a list of ids for batch-scoped work (spans gain `trace_ids`).
        No-op (beyond one boolean test) on a disabled tracer."""
        if not self.enabled or not trace:
            yield
            return
        prev = getattr(self._tls, "trace", None)
        self._tls.trace = trace
        try:
            yield
        finally:
            self._tls.trace = prev

    def current_trace_id(self) -> Optional[str]:
        """The single id bound to this thread, if any (None under a
        list binding — a batch has no one id)."""
        bound = getattr(self._tls, "trace", None)
        return bound if isinstance(bound, str) else None

    def open_span(self) -> Optional[str]:
        """The name of the innermost span open on this thread, if any."""
        stack = self._stack()
        return stack[-1] if stack else None

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _push(self, name: str):
        """Open `name` on this thread: its (depth, parent)."""
        stack = self._stack()
        parent = stack[-1] if stack else None
        stack.append(name)
        return len(stack) - 1, parent

    def _pop(self):
        self._stack().pop()

    def _record(self, name, cat, t0, dur, depth, parent, attrs):
        bound = getattr(self._tls, "trace", None)
        if isinstance(bound, str):
            if "trace_id" not in attrs:
                attrs["trace_id"] = bound
        elif bound and "trace_ids" not in attrs:
            attrs["trace_ids"] = list(bound)
        rec = {
            "name": name,
            "cat": cat,
            "ts_s": t0 - self._t_origin,
            "dur_s": dur,
            "depth": depth,
            "parent": parent,
            "tid": threading.get_ident(),
            "thread": threading.current_thread().name,
            "attrs": attrs,
        }
        with self._lock:
            if len(self._spans) >= self.max_spans:
                self.dropped += 1
            else:
                self._spans.append(rec)

    # ------------------------------------------------------------- reading

    def spans(self, last: Optional[int] = None) -> list:
        """Snapshot (shallow copies) of the completed spans; `last=N`
        copies only the N most recent (the flight recorder's bundle
        tail — copying 100k spans per incident would be the outage
        amplifying itself)."""
        with self._lock:
            if last is None:
                src = self._spans
            else:
                # [-last:] with last=0 is the WHOLE list, not none of it
                src = self._spans[-last:] if last > 0 else []
            return [dict(s) for s in src]

    @property
    def span_count(self) -> int:
        """Retained-span count without copying the records."""
        with self._lock:
            return len(self._spans)

    def summary(self) -> dict:
        """Per-span-name aggregate: {name: {count, total_s, mean_s, max_s}}
        plus a `dropped` count when retention overflowed."""
        agg: dict = {}
        with self._lock:
            spans = list(self._spans)
            dropped = self.dropped
        for s in spans:
            a = agg.setdefault(
                s["name"], {"count": 0, "total_s": 0.0, "max_s": 0.0}
            )
            a["count"] += 1
            a["total_s"] += s["dur_s"]
            if s["dur_s"] > a["max_s"]:
                a["max_s"] = s["dur_s"]
        for a in agg.values():
            a["mean_s"] = a["total_s"] / a["count"]
            a["total_s"] = round(a["total_s"], 6)
            a["mean_s"] = round(a["mean_s"], 6)
            a["max_s"] = round(a["max_s"], 6)
        if dropped:
            agg["_dropped"] = dropped
        return agg

    # ------------------------------------------------------------ exporters

    def chrome_trace(self) -> dict:
        """Chrome trace-event JSON object format: complete ("ph": "X")
        events in microseconds, one per span, plus thread-name metadata so
        Perfetto labels the worker/client timelines. Same-tid ts/dur
        containment renders the stack; `args.parent` names it."""
        with self._lock:
            spans = list(self._spans)
            dropped = self.dropped
        events = []
        threads_seen = {}
        for s in spans:
            tid = s["tid"]
            if tid not in threads_seen:
                threads_seen[tid] = s["thread"]
                events.append({
                    "name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
                    "args": {"name": s["thread"]},
                })
            events.append({
                "name": s["name"],
                "cat": s["cat"],
                "ph": "X",
                # clamp: a retro-recorded span (Tracer.add) can nominally
                # start before the tracer existed; viewers expect ts >= 0
                "ts": round(max(0.0, s["ts_s"]) * 1e6, 3),
                "dur": round(s["dur_s"] * 1e6, 3),
                "pid": 1,
                "tid": tid,
                "args": {**s["attrs"], "depth": s["depth"],
                         "parent": s["parent"]},
            })
        out = {"traceEvents": events, "displayTimeUnit": "ms"}
        if dropped:
            out["otherData"] = {"dropped_spans": dropped}
        return out

    def export_chrome(self, path: str):
        """Write the Chrome trace-event JSON; open in Perfetto or
        chrome://tracing (docs/OBSERVABILITY.md)."""
        with open(path, "w") as fh:
            json.dump(self.chrome_trace(), fh)


#: shared disabled tracer — the default for every instrumented call site,
#: so production paths pay one `if not enabled` per span and nothing else
NULL_TRACER = Tracer(enabled=False, max_spans=1)
