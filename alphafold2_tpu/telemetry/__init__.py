"""Telemetry subsystem: tracing, metrics, profiling hooks, regression gate.

One unified observability layer (ISSUE 4; ScaleFold arxiv 2404.11068 and
ParaFold arxiv 2111.06340 both credit phase-level measurement for their
scaling results):

  * `trace`     — span-based tracer; Chrome trace-event / Perfetto JSON
                  exporter; `NULL_TRACER` no-op default.
  * `compile_record` — the process's one listener on JAX's compile
                  phases (trace, lower, XLA compile or cache load), as
                  records, running totals and `compile.*` spans.
  * `registry`  — counters / gauges / histograms with Prometheus text
                  exposition and JSON snapshots; `LatencyHistogram` lives
                  here now.
  * `logger`    — `MetricsLogger`, the step-cadence JSONL stream
                  (migrated from utils/observability.py).
  * `profiling` — compile-event tracking, device-memory / host-memory /
                  analytic-FLOPs gauges, the jax.profiler `profile_trace`
                  wrapper.
  * `check`     — perf-regression gate CLI
                  (`python -m alphafold2_tpu.telemetry.check`).
  * `slo`       — declarative SLO objectives evaluated as fast/slow
                  burn rates over registry deltas; alerts land back in
                  the registry and in a structured event log.
  * `ops_plane` — the LIVE operations plane: stdlib HTTP server
                  (`/metrics`, `/healthz`, `/statusz`) + the incident
                  flight recorder (`serve.py --ops-port/--flight-dir`).
  * `goodput`   — the TRAINING observability plane: wall-clock goodput/
                  badput ledger, pod-wide metric federation with a
                  `process` label, straggler/data-stall detection, and
                  the trainer ops-plane wiring (`train_*.py --ops-port`).
  * `costs`     — the SERVING cost plane: per-executable chip-cost
                  ledger (analytic FLOPs x priced residency x measured
                  EMA per (pool, bucket, schedule, arm, dtype) cell),
                  per-replica serve-goodput ledger, and the exemplar
                  flight book behind `/explainz` — the capacity model
                  the fleet's headroom gauges and the autoscaler's
                  `up_headroom` trigger consume.

Everything is disabled-by-default at the call sites: an engine or
trainer built without a tracer/registry runs the shared no-op singletons
and pays one boolean test per instrumentation point.

docs/OBSERVABILITY.md is the operator guide (span taxonomy, metric
names, how to open traces, how the gate reads baselines).
"""

from alphafold2_tpu.telemetry.goodput import (
    BUCKETS,
    NULL_TRAIN_TELEMETRY,
    FederatedRegistryView,
    GoodputLedger,
    MetricFederation,
    StragglerDetector,
    TrainTelemetry,
    add_observability_args,
    build_train_telemetry,
    observability_enabled,
    relabeled_exposition,
)
from alphafold2_tpu.telemetry.logger import (
    MetricsLogger,
    per_process_metrics_path,
)
from alphafold2_tpu.telemetry.costs import (
    SERVE_CAUSES,
    ExecutableCostLedger,
    FlightBook,
    ServeGoodputLedger,
)
from alphafold2_tpu.telemetry.ops_plane import (
    FlightRecorder,
    OpsServer,
    ProfileBusyError,
    ProfileCapturer,
    ProfileRateLimitedError,
    ops_server_for_engine,
    ops_server_for_fleet,
)
from alphafold2_tpu.telemetry.profiling import (
    CompileTracker,
    device_memory_gauges,
    flops_gauges,
    host_memory_gauges,
    profile_trace,
)
from alphafold2_tpu.telemetry.registry import (
    NULL_REGISTRY,
    Counter,
    Gauge,
    Histogram,
    LatencyHistogram,
    MetricRegistry,
    flatten_snapshot,
    parse_prometheus_text,
)
from alphafold2_tpu.telemetry.slo import (
    SloConfig,
    SloEngine,
    SloObjective,
    default_slo_config,
)
from alphafold2_tpu.telemetry.trace import NULL_TRACER, Tracer, new_trace_id
from alphafold2_tpu.telemetry import compile_record


def add_telemetry_args(ap):
    """The telemetry argparse block shared by train_pre.py,
    train_end2end.py, serve.py, and predict.py — one place to add the
    next knob."""
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of this run's "
                         "phase spans here (open in Perfetto / "
                         "chrome://tracing); tracing is off (near-zero "
                         "cost) when unset")
    ap.add_argument("--trace-max-spans", type=int, default=100_000,
                    help="span retention bound; overflow is counted, "
                         "not silently discarded")


def tracer_from_args(args) -> Tracer:
    """A live tracer when --trace-out or (the trainers') --profile-dir was
    given, NULL_TRACER otherwise: a profiler capture then holds the host
    spans beside the device's operations (telemetry/trace.py). A live
    tracer gets the process's compile recorder attached, so every later
    trace, lowering, compile and cache load is a `compile.*` span under
    the span that caused it (telemetry/compile_record.py)."""
    if getattr(args, "trace_out", None) or getattr(args, "profile_dir", None):
        tracer = Tracer(enabled=True, max_spans=args.trace_max_spans)
        compile_record.attach(tracer)  # JAX's compile phases as its spans
        return tracer
    return NULL_TRACER


def finish_trace(tracer: Tracer, args):
    """Export the trace at the end of a CLI run (no-op without
    --trace-out)."""
    if getattr(args, "trace_out", None) and tracer.enabled:
        tracer.export_chrome(args.trace_out)
        n = tracer.span_count
        print(f"wrote {args.trace_out} ({n} span(s)"
              + (f", {tracer.dropped} dropped" if tracer.dropped else "")
              + ")")


__all__ = [
    "BUCKETS",
    "CompileTracker",
    "Counter",
    "ExecutableCostLedger",
    "FederatedRegistryView",
    "FlightBook",
    "FlightRecorder",
    "Gauge",
    "GoodputLedger",
    "Histogram",
    "LatencyHistogram",
    "MetricFederation",
    "MetricRegistry",
    "MetricsLogger",
    "NULL_REGISTRY",
    "NULL_TRACER",
    "NULL_TRAIN_TELEMETRY",
    "OpsServer",
    "ProfileBusyError",
    "ProfileCapturer",
    "ProfileRateLimitedError",
    "SERVE_CAUSES",
    "ServeGoodputLedger",
    "StragglerDetector",
    "TrainTelemetry",
    "SloConfig",
    "SloEngine",
    "SloObjective",
    "Tracer",
    "add_observability_args",
    "add_telemetry_args",
    "build_train_telemetry",
    "compile_record",
    "default_slo_config",
    "device_memory_gauges",
    "finish_trace",
    "flatten_snapshot",
    "flops_gauges",
    "host_memory_gauges",
    "new_trace_id",
    "observability_enabled",
    "ops_server_for_engine",
    "ops_server_for_fleet",
    "parse_prometheus_text",
    "per_process_metrics_path",
    "profile_trace",
    "relabeled_exposition",
    "tracer_from_args",
]
