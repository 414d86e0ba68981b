"""The live operations plane: HTTP observability + incident flight recorder.

Everything the telemetry subsystem measures was, until this module,
post-hoc — metrics rode end-of-run `stats()` dumps and spans rode
`--trace-out` exports. This module makes the stack OPERABLE while it
runs, with two cooperating pieces, both stdlib-only (an inference fleet
must not grow an HTTP-framework dependency for three read-only
endpoints):

`OpsServer` — a threaded `http.server` exposing:

  * ``/metrics``  Prometheus text exposition (v0.0.4) of one registry —
                  the scrape target; round-trips through
                  `registry.parse_prometheus_text`.
  * ``/healthz``  liveness JSON from the serving tier's `health()`
                  (HealthMonitor states + replica-up view for the
                  fleet, worker/breaker state for one engine). HTTP 200
                  while status is "ok"/"degraded", 503 when "down" —
                  load balancers need the status CODE, not JSON parsing.
  * ``/statusz``  the deep-dive JSON: health + full stats snapshot +
                  registry snapshot + span summary + SLO state + flight
                  recorder state. A store-armed fleet's stats carry the
                  ``artifact_store`` (hit/miss/corrupt/byte view) and
                  ``frontdoor`` (in-flight keys, waiting followers)
                  sections — the first place to look when the cache hit
                  rate moves (docs/OPERATIONS.md runbook). Fleet servers
                  also carry a ``backpressure`` section (the queue /
                  per-pool / retry-budget ``retry_after_s`` horizons an
                  HTTP front end quotes next to its 429 +
                  ``Retry-After`` sheds).
  * ``/explainz`` exemplar flight lookup (`?trace_id=<id>`): the full
                  per-request flight record from a `telemetry.costs.
                  FlightBook` — every lifecycle event across featurize
                  tier, admission, and replicas. Cache provenance rides
                  the terminal event: an artifact-store hit finishes
                  with ``cache_tier="artifact_store"`` + its level
                  (memory/disk), a coalesced follower with
                  ``coalesced=true`` + its leader's trace_id, and
                  store-served features note ``features_from_store``.
                  Without a trace_id it answers 400 with the most
                  recent ids; an unknown id is 404. Absent entirely (no
                  flight book wired) it is 404.
  * ``/profilez`` on-demand `jax.profiler` capture (`?duration_s=N`,
                  bounded and rate-limited — see `ProfileCapturer`):
                  200 with the capture directory when started, 409 while
                  one is already running, 429 inside the rate-limit
                  window — so the next healthy TPU probe can be profiled
                  WITHOUT redeploying the fleet.
  * ``/threadz``  every live thread (name, daemon flag, current stack
                  via ``sys._current_frames()``) — the first diagnostic
                  for a suspected deadlock; thread names follow the
                  stable ``af2-*`` scheme so the owner of each stack is
                  readable at a glance.

  plus a background TICKER thread that drives the periodic work live
  observability needs: `SloEngine.evaluate()`, `FlightRecorder.poll()`
  (metric-delta events), and any extra `add_tick` callables (serve.py
  adds host-memory gauges). Construction binds the socket (port 0 =
  ephemeral, `.port` reports the real one) but nothing runs until
  `start()`.

`FlightRecorder` — the incident black box. A bounded in-memory ring of
recent operational events (incidents, SLO transitions, metric deltas)
rides along for free; when an incident TRIPS — breaker open, replica
drain, watchdog fire, SLO page, all wired through the existing
reliability seams (`ServingEngine(incident_hook=)`,
`ServingFleet(incident_hook=)`, `SloEngine(on_page=)`) — it snapshots a
forensic bundle to disk: the event ring, the tail of the span stream
(trace_ids included, so the victim request's cross-replica life is in
the bundle), the registry snapshot, and an optional stats payload.
Bundles are rate-limited per incident kind (`min_interval_s`): a breaker
flapping at 10 Hz must not turn the recorder into a disk-filling
incident of its own (suppressed bundles are still ring events and
counted).

Wiring: `serve.py --ops-port/--flight-dir/--slo-config`, helpers
`ops_server_for_engine` / `ops_server_for_fleet` below; the TRAINERS
mount the same server through `telemetry.goodput.build_train_telemetry`
(`train_pre.py` / `train_end2end.py --ops-port`, with the goodput
ledger's progress watchdog as `/healthz` and — on a pod — the federated
`process`-labeled registry view as `/metrics`).
docs/OBSERVABILITY.md "The operations plane" is the operator guide;
docs/OPERATIONS.md maps each alert to its first diagnostic step.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, List, Optional
from urllib.parse import parse_qs, urlsplit

from alphafold2_tpu.telemetry.registry import MetricRegistry
from alphafold2_tpu.telemetry.trace import NULL_TRACER, Tracer

#: incident kinds the stack's seams report today (an unknown kind is
#: still recorded — this list is documentation, not a gate)
KNOWN_INCIDENT_KINDS = (
    "breaker_open",     # engine circuit transitioned to open
    "replica_drain",    # fleet health monitor took a replica out
    "watchdog_fire",    # hung-batch watchdog abandoned a dispatch
    "slo_page",         # an SLO objective started firing
    "scale_up",         # autoscaler grew the replica pool
    "scale_down",       # autoscaler retired a replica
    "featurize_worker_death",  # a featurize worker thread died (respawned)
    "train_straggler",  # one pod process's step time diverged from the rest
    "train_data_stall",  # the input pipeline stalled training (local fetch
    #                      share or pod fetch skew past threshold)
)


class FlightRecorder:
    """Bounded event ring + incident bundle writer (see module docstring).

    Args:
      out_dir: where bundles land (created lazily on first incident).
      tracer: span source for the bundle tail (`NULL_TRACER` = no spans).
      registry: metric source for delta events and bundle snapshots; the
        recorder also counts itself here (`flight_incidents_total{kind}`,
        `flight_bundles_written_total`). None disables both.
      stats_fn: optional zero-arg callable whose JSON-ready return value
        is embedded in each bundle (an engine/fleet `stats`).
      capacity: event-ring bound.
      span_tail: how many of the most recent spans a bundle carries.
      min_interval_s: per-kind bundle rate limit; suppressed incidents
        are ring events only.
      clock: wall clock for bundle timestamps (injectable for tests).
    """

    def __init__(self, out_dir: str, *, tracer: Tracer = NULL_TRACER,
                 registry: Optional[MetricRegistry] = None, stats_fn=None,
                 capacity: int = 1024, span_tail: int = 512,
                 min_interval_s: float = 5.0, clock=time.time):
        if capacity < 1 or span_tail < 0:
            raise ValueError(
                f"capacity must be >= 1 and span_tail >= 0, got "
                f"{capacity}/{span_tail}"
            )
        self.out_dir = out_dir
        self._tracer = tracer
        self._registry = registry
        self._stats_fn = stats_fn
        self._span_tail = span_tail
        self._min_interval_s = min_interval_s
        self._clock = clock
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._capacity = capacity
        self._seq = 0                  # bundle sequence number
        self._last_bundle_at = {}      # kind -> wall ts of last bundle
        self._bundles: List[str] = []  # paths written this process
        self._suppressed = 0
        self._last_counters = None     # poll() delta baseline

    def bind(self, *, registry: Optional[MetricRegistry] = None,
             stats_fn=None):
        """Late wiring for the construction-order cycle: the recorder
        must exist BEFORE the engine/fleet (it is their incident_hook),
        but the engine owns the registry and stats the bundles embed."""
        if registry is not None:
            self._registry = registry
        if stats_fn is not None:
            self._stats_fn = stats_fn

    # ------------------------------------------------------------- events

    def note(self, kind: str, **attrs):
        """Append one event to the ring (no disk I/O)."""
        with self._lock:
            self._events.append(
                {"ts": self._clock(), "kind": kind, "attrs": attrs}
            )
            if len(self._events) > self._capacity:
                del self._events[: len(self._events) - self._capacity]

    def poll(self):
        """Ticker hook: record which counters moved since the last poll
        as one `metrics_delta` ring event — the bundle's answer to "what
        was happening in the minute before the incident" even when spans
        are off."""
        if self._registry is None:
            return
        current = {}
        for name, (kind, series) in self._registry.collect().items():
            if kind != "counter":
                continue
            for key, metric in series.items():
                current[(name, key)] = metric.value
        with self._lock:
            last, self._last_counters = self._last_counters, current
        if last is None:
            return
        deltas = {}
        for (name, key), v in current.items():
            d = v - last.get((name, key), 0.0)
            if d:
                label = name + "".join(f"{{{k}={val}}}" for k, val in key)
                deltas[label] = d
        if deltas:
            self.note("metrics_delta", deltas=deltas)

    # ----------------------------------------------------------- incidents

    def incident(self, kind: str, **attrs) -> Optional[str]:
        """One incident: ring event + (rate limits permitting) a bundle
        on disk. Returns the bundle path, or None when suppressed.
        Never raises — the recorder is called from reliability seams
        that must keep serving through a full disk."""
        now = self._clock()
        self.note("incident:" + kind, **attrs)
        if self._registry is not None:
            self._registry.counter(
                "flight_incidents_total", help="incidents by kind",
                kind=kind).inc()
        with self._lock:
            last = self._last_bundle_at.get(kind)
            if last is not None and now - last < self._min_interval_s:
                self._suppressed += 1
                return None
            self._last_bundle_at[kind] = now
            self._seq += 1
            seq = self._seq
        try:
            return self._write_bundle(seq, kind, attrs, now)
        except Exception:  # noqa: BLE001 — see docstring
            traceback.print_exc()
            return None

    def _write_bundle(self, seq: int, kind: str, attrs: dict,
                      now: float) -> str:
        bundle = {
            "incident": {"seq": seq, "kind": kind, "ts": now,
                         "attrs": attrs},
            "events": None,   # filled under the lock below
            "spans": self._tracer.spans(last=self._span_tail),
        }
        with self._lock:
            bundle["events"] = list(self._events)
        if self._registry is not None:
            bundle["metrics"] = self._registry.snapshot()
        if self._stats_fn is not None:
            try:
                bundle["stats"] = self._stats_fn()
            except Exception:  # noqa: BLE001 — a failing stats provider
                # must not cost the rest of the bundle
                bundle["stats_error"] = traceback.format_exc()
        os.makedirs(self.out_dir, exist_ok=True)
        path = os.path.join(self.out_dir, f"incident-{seq:03d}-{kind}.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(bundle, fh, indent=1, default=str)
        os.replace(tmp, path)  # atomic: a reader never sees a torn bundle
        if self._registry is not None:
            self._registry.counter(
                "flight_bundles_written_total",
                help="forensic bundles snapshotted to disk").inc()
        with self._lock:
            self._bundles.append(path)
        return path

    def slo_page_hook(self, objective: str, transition: str, info: dict):
        """Adapter matching `SloEngine(on_page=...)`: a FIRING transition
        is an incident (bundle), a RESOLVED transition is a ring event."""
        # info already carries objective/transition keys (slo.py builds
        # it that way) — merge rather than re-pass, or the duplicate
        # kwarg would TypeError and the page would never bundle
        attrs = dict(info)
        attrs.setdefault("objective", objective)
        if transition == "firing":
            self.incident("slo_page", **attrs)
        else:
            self.note("slo_" + transition, **attrs)

    # -------------------------------------------------------------- stats

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "dir": self.out_dir,
                "events": len(self._events),
                "bundles": list(self._bundles),
                "suppressed_bundles": self._suppressed,
            }


class ProfileCapturer:
    """On-demand, duration-bounded, rate-limited `jax.profiler` capture
    (the `/profilez` backing; module docstring).

    One capture at a time: `start()` raises `ProfileBusyError` while a
    capture runs (HTTP 409) and `ProfileRateLimitedError` inside
    `min_interval_s` of the previous start (HTTP 429) — an operator
    hammering the endpoint must not turn the profiler into the overload.
    The capture itself runs on a daemon thread: `jax.profiler.
    start_trace` into a fresh `profile-<seq>` directory under `out_dir`,
    stopped after `duration_s` (clamped to `max_duration_s`). Outcomes
    are counted (`profilez_captures_total{outcome}`) so abuse is itself
    scrapeable.
    """

    def __init__(self, out_dir: str, *,
                 registry: Optional[MetricRegistry] = None,
                 max_duration_s: float = 30.0, min_interval_s: float = 30.0,
                 clock=time.monotonic):
        if max_duration_s <= 0 or min_interval_s < 0:
            raise ValueError(
                f"max_duration_s must be > 0 and min_interval_s >= 0, got "
                f"{max_duration_s}/{min_interval_s}")
        self.out_dir = out_dir
        self._registry = registry
        self.max_duration_s = max_duration_s
        self.min_interval_s = min_interval_s
        self._clock = clock
        self._lock = threading.Lock()
        self._running: Optional[dict] = None
        self._last_start: Optional[float] = None
        self._seq = 0
        self._captures: List[dict] = []
        self._abort = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _count(self, outcome: str):
        if self._registry is not None:
            self._registry.counter(
                "profilez_captures_total",
                help="/profilez capture requests by outcome",
                outcome=outcome).inc()

    def start(self, duration_s: float = 2.0) -> dict:
        """Begin one capture; returns {"dir", "duration_s", "seq"}.
        Raises ProfileBusyError / ProfileRateLimitedError /
        ValueError(duration) — the HTTP layer maps them to 409/429/400.

        The capture itself (start_trace -> bounded wait -> stop_trace)
        runs ENTIRELY on one NON-daemon worker thread, asynchronously:

          * asynchronously, because `jax.profiler.start_trace` can block
            for seconds behind an in-flight XLA compile — an HTTP
            handler must answer now, not when the compiler yields;
          * one thread for both ends, NON-daemon, because any daemon
            thread still inside the profiler (blocked start OR pending
            stop) at interpreter teardown SEGFAULTS in native code
            (reproduced on the CPU backend): threading._shutdown joins
            non-daemon threads BEFORE teardown, and close() — wired
            into OpsServer.stop — aborts the wait early so exit never
            stalls a full capture window.

        A start_trace failure is counted (`outcome="failed"`) and
        surfaced in `snapshot()` rather than the HTTP response (the
        request already returned)."""
        if duration_s <= 0:
            raise ValueError(
                f"duration_s must be positive, got {duration_s}")
        duration_s = min(float(duration_s), self.max_duration_s)
        now = self._clock()
        with self._lock:
            if self._running is not None:
                self._count("rejected_busy")
                raise ProfileBusyError(
                    f"a profile capture is already running "
                    f"(dir {self._running['dir']})")
            if (self._last_start is not None
                    and now - self._last_start < self.min_interval_s):
                self._count("rejected_rate_limited")
                raise ProfileRateLimitedError(
                    f"last capture started "
                    f"{now - self._last_start:.1f}s ago; minimum interval "
                    f"is {self.min_interval_s}s")
            self._seq += 1
            seq = self._seq
            path = os.path.join(self.out_dir, f"profile-{seq:03d}")
            info = {"seq": seq, "dir": path, "duration_s": duration_s}
            self._running = info
            self._last_start = now
        self._abort.clear()

        def capture():
            try:
                import jax

                os.makedirs(path, exist_ok=True)
                jax.profiler.start_trace(path)
            except Exception:  # noqa: BLE001 — surfaced via snapshot
                traceback.print_exc()
                self._count("failed")
                info["error"] = "start_trace failed (see server log)"
                with self._lock:
                    self._running = None
                    self._captures.append(dict(info))
                return
            self._count("started")
            self._abort.wait(duration_s)
            try:
                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001 — a failing stop must not
                # kill the capture thread silently mid-serving
                traceback.print_exc()
                info["error"] = "stop_trace failed (see server log)"
            finally:
                with self._lock:
                    self._running = None
                    self._captures.append(dict(info))

        self._thread = threading.Thread(
            target=capture, name="af2-profilez-capture", daemon=False)
        self._thread.start()
        return dict(info)

    def close(self, timeout: Optional[float] = 30.0):
        """Abort any in-flight capture and join the capture thread —
        called from `OpsServer.stop()` so a capture can never be left
        racing process teardown (a blocked start_trace can hold the
        join up to roughly one compile; the non-daemon thread covers
        the exit path even if this times out). Idempotent."""
        self._abort.set()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout)
        self._thread = None

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "dir": self.out_dir,
                "running": dict(self._running) if self._running else None,
                "captures": [dict(c) for c in self._captures],
                "max_duration_s": self.max_duration_s,
                "min_interval_s": self.min_interval_s,
            }


class ProfileBusyError(RuntimeError):
    """A capture is already in flight (HTTP 409)."""


class ProfileRateLimitedError(RuntimeError):
    """Too soon after the previous capture (HTTP 429)."""


class _Handler(BaseHTTPRequestHandler):
    """One request; the server instance carries the providers."""

    server_version = "af2-ops/1"
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):  # noqa: ARG002 — silence stdout;
        # scrape-per-second access logs are noise in a serving console
        pass

    def _send(self, code: int, body: bytes, content_type: str):
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _send_json(self, code: int, payload):
        self._send(code, json.dumps(payload, indent=1, default=str)
                   .encode("utf-8"), "application/json")

    def do_GET(self):  # noqa: N802 — http.server API
        ops: "OpsServer" = self.server.ops  # type: ignore[attr-defined]
        parts = urlsplit(self.path)
        path = parts.path.rstrip("/") or "/"
        query = parse_qs(parts.query)
        try:
            if path == "/metrics":
                body = ops.registry.to_prometheus().encode("utf-8")
                ops.registry.counter(
                    "ops_scrapes_total",
                    help="/metrics scrapes served").inc()
                self._send(200, body,
                           "text/plain; version=0.0.4; charset=utf-8")
            elif path == "/healthz":
                payload = ops.health()
                code = 503 if payload.get("status") == "down" else 200
                self._send_json(code, payload)
            elif path == "/statusz":
                self._send_json(200, ops.statusz())
            elif path == "/explainz":
                code, payload = ops.explainz(
                    query.get("trace_id", [None])[0])
                self._send_json(code, payload)
            elif path == "/profilez":
                code, payload = ops.profilez(
                    query.get("duration_s", [None])[0])
                self._send_json(code, payload)
            elif path == "/threadz":
                self._send_json(200, ops.threadz())
            elif path == "/":
                self._send_json(200, {"endpoints": [
                    "/metrics", "/healthz", "/statusz", "/explainz",
                    "/profilez", "/threadz"]})
            else:
                self._send_json(404, {"error": f"no such endpoint {path!r}"})
        except Exception:  # noqa: BLE001 — a handler bug must answer 500,
            # not silently drop the connection
            self._send(500, traceback.format_exc().encode("utf-8"),
                       "text/plain; charset=utf-8")


class OpsServer:
    """The observability HTTP server + periodic ticker (module docstring).

    Construction BINDS the port (so `.port` is real immediately and a
    bind failure surfaces at build, not mid-traffic) but serves nothing
    until `start()`. `stop()` is idempotent and joins both threads.
    """

    def __init__(self, *, registry: MetricRegistry,
                 health_fn: Optional[Callable[[], dict]] = None,
                 stats_fn: Optional[Callable[[], dict]] = None,
                 backpressure_fn: Optional[Callable[[], dict]] = None,
                 tracer: Tracer = NULL_TRACER,
                 slo=None, recorder: Optional[FlightRecorder] = None,
                 flights=None, profiler: Optional[ProfileCapturer] = None,
                 host: str = "127.0.0.1", port: int = 0,
                 tick_interval_s: float = 1.0):
        if tick_interval_s <= 0:
            raise ValueError(
                f"tick_interval_s must be positive, got {tick_interval_s}"
            )
        self.registry = registry
        self._health_fn = health_fn
        self._stats_fn = stats_fn
        # shed-advice provider (ServingFleet.backpressure): the queue /
        # per-pool / retry-budget retry_after_s horizons a 429-emitting
        # HTTP front end quotes in Retry-After headers
        self._backpressure_fn = backpressure_fn
        self._tracer = tracer
        self.slo = slo
        self.recorder = recorder
        self.flights = flights      # telemetry.costs.FlightBook (/explainz)
        self.profiler = profiler    # ProfileCapturer (/profilez)
        self._dropped_seen = 0
        if tracer.enabled:
            # registered eagerly at 0 so span loss is alertable from the
            # first scrape (the ticker publishes increments; before this
            # counter, retention overflow was visible only in summary()
            # and the Chrome export's otherData)
            registry.counter(
                "trace_spans_dropped_total",
                help="spans lost to the tracer retention bound "
                     "(max_spans) — raise --trace-max-spans if nonzero")
        self._tick_interval_s = tick_interval_s
        self._extra_ticks: List[Callable[[], None]] = []
        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self._httpd.ops = self  # type: ignore[attr-defined]
        self._serve_thread: Optional[threading.Thread] = None
        self._tick_thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # ------------------------------------------------------------ address

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def url(self) -> str:
        host = self._httpd.server_address[0]
        return f"http://{host}:{self.port}"

    # ----------------------------------------------------------- payloads

    def health(self) -> dict:
        if self._health_fn is None:
            return {"status": "ok"}
        return self._health_fn()

    def statusz(self) -> dict:
        out = {
            "health": self.health(),
            "metrics": self.registry.snapshot(),
            "spans": self._tracer.summary(),
        }
        if self._stats_fn is not None:
            out["stats"] = self._stats_fn()
        if self._backpressure_fn is not None:
            out["backpressure"] = self._backpressure_fn()
        if self.slo is not None:
            out["slo"] = self.slo.snapshot()
        if self.recorder is not None:
            out["flight_recorder"] = self.recorder.snapshot()
        if self.flights is not None:
            out["flights"] = self.flights.snapshot()
        if self.profiler is not None:
            out["profiler"] = self.profiler.snapshot()
        return out

    def explainz(self, trace_id: Optional[str]):
        """(code, payload) for `/explainz?trace_id=` — the exemplar
        flight lookup (telemetry/costs.py FlightBook)."""
        if self.flights is None:
            return 404, {"error": "no flight book wired on this server"}
        if not trace_id:
            return 400, {
                "error": "pass ?trace_id=<id>",
                "recent_trace_ids": self.flights.recent(),
            }
        rec = self.flights.get(trace_id)
        if rec is None:
            return 404, {
                "error": f"no flight recorded for trace_id {trace_id!r} "
                         f"(evicted, or never seen)",
                "recent_trace_ids": self.flights.recent(),
            }
        return 200, rec

    def threadz(self) -> dict:
        """`/threadz` payload: every live thread with its current stack
        (`sys._current_frames()`) — the FIRST diagnostic for a suspected
        deadlock or hang: two threads parked in `acquire` with crossed
        lock owners is a lock-order inversion caught red-handed (the
        static side of the same contract is af2lint's concurrency pass).
        Served by one of the HTTP pool's own threads, so even a fully
        wedged serving tier still answers."""
        frames = sys._current_frames()
        threads = []
        for t in threading.enumerate():
            frame = frames.get(t.ident)
            stack = [ln.rstrip() for ln in
                     traceback.format_stack(frame)] if frame else []
            threads.append({
                "name": t.name,
                "ident": t.ident,
                "daemon": t.daemon,
                "alive": t.is_alive(),
                "stack": stack,
            })
        threads.sort(key=lambda e: str(e["name"]))
        return {"count": len(threads), "threads": threads}

    def profilez(self, duration_s):
        """(code, payload) for `/profilez?duration_s=` — start one
        bounded jax.profiler capture (409 busy / 429 rate-limited)."""
        if self.profiler is None:
            return 404, {"error": "no profiler wired on this server "
                                  "(serve.py arms it with --flight-dir)"}
        try:
            duration = float(duration_s) if duration_s is not None else 2.0
        except ValueError:
            return 400, {"error": f"duration_s must be a number, got "
                                  f"{duration_s!r}"}
        try:
            info = self.profiler.start(duration)
        except ProfileBusyError as e:
            return 409, {"error": str(e)}
        except ProfileRateLimitedError as e:
            return 429, {"error": str(e)}
        except ValueError as e:
            return 400, {"error": str(e)}
        return 200, {"status": "capturing", **info}

    # ------------------------------------------------------------ lifecycle

    def add_tick(self, fn: Callable[[], None]):
        """Register an extra periodic callable on the ticker thread."""
        self._extra_ticks.append(fn)

    def tick(self):
        """One ticker pass (tests call it directly; the thread loops it).
        Each hook is isolated: one raising hook must not starve the
        others or kill the ticker."""
        hooks: List[Callable[[], None]] = []
        if self.slo is not None:
            hooks.append(self.slo.evaluate)
        if self.recorder is not None:
            hooks.append(self.recorder.poll)
        if self._tracer.enabled:
            hooks.append(self._sync_dropped_spans)
        hooks.extend(self._extra_ticks)
        for fn in hooks:
            try:
                fn()
            except Exception:  # noqa: BLE001 — see docstring
                traceback.print_exc()

    def _sync_dropped_spans(self):
        """Ticker hook: publish tracer retention overflow as the
        monotone `trace_spans_dropped_total` counter (increment-based so
        the counter only grows across tracer instances)."""
        dropped = self._tracer.dropped
        delta = dropped - self._dropped_seen
        if delta > 0:
            self._dropped_seen = dropped
            self.registry.counter(
                "trace_spans_dropped_total",
                help="spans lost to the tracer retention bound "
                     "(max_spans) — raise --trace-max-spans if nonzero"
            ).inc(delta)

    def start(self):
        if self._serve_thread is not None:
            return
        self._stop.clear()
        self._serve_thread = threading.Thread(
            target=self._httpd.serve_forever, name="af2-ops-http",
            daemon=True)
        self._serve_thread.start()

        def tick_loop():
            while not self._stop.wait(self._tick_interval_s):
                self.tick()

        self._tick_thread = threading.Thread(
            target=tick_loop, name="af2-ops-ticker", daemon=True)
        self._tick_thread.start()

    def stop(self, timeout: Optional[float] = 5.0):
        self._stop.set()
        if self.profiler is not None:
            # an in-flight /profilez capture must resolve before the
            # process can tear down (see ProfileCapturer.close)
            self.profiler.close()
        if self._tick_thread is not None:
            self._tick_thread.join(timeout)
            self._tick_thread = None
        if self._serve_thread is not None:
            # shutdown() blocks on an event only serve_forever() sets —
            # calling it on a built-but-never-started server deadlocks
            self._httpd.shutdown()
            self._serve_thread.join(timeout)
            self._serve_thread = None
        self._httpd.server_close()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def ops_server_for_engine(engine, *, tracer: Tracer = NULL_TRACER,
                          slo=None, recorder: Optional[FlightRecorder] = None,
                          profiler: Optional[ProfileCapturer] = None,
                          host: str = "127.0.0.1", port: int = 0,
                          tick_interval_s: float = 1.0) -> OpsServer:
    """Wire an `OpsServer` over one `ServingEngine`: its metrics
    registry, `health()`, `stats()`, and its flight book (/explainz)."""
    return OpsServer(
        registry=engine.metrics.registry, health_fn=engine.health,
        stats_fn=engine.stats, tracer=tracer, slo=slo, recorder=recorder,
        flights=getattr(engine, "flights", None), profiler=profiler,
        host=host, port=port, tick_interval_s=tick_interval_s,
    )


def ops_server_for_fleet(fleet, *, tracer: Tracer = NULL_TRACER,
                         slo=None, recorder: Optional[FlightRecorder] = None,
                         profiler: Optional[ProfileCapturer] = None,
                         host: str = "127.0.0.1", port: int = 0,
                         tick_interval_s: float = 1.0) -> OpsServer:
    """Wire an `OpsServer` over a `ServingFleet`: the fleet registry
    (fleet_* families + SLO/flight metrics), `health()` (HealthMonitor +
    replica-up view), the full fleet `stats()`, and the fleet's flight
    book (/explainz)."""
    return OpsServer(
        registry=fleet.registry, health_fn=fleet.health,
        stats_fn=fleet.stats, tracer=tracer, slo=slo, recorder=recorder,
        backpressure_fn=getattr(fleet, "backpressure", None),
        flights=getattr(fleet, "flights", None), profiler=profiler,
        host=host, port=port, tick_interval_s=tick_interval_s,
    )
