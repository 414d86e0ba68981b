"""Profiling hooks: compile-event tracking, device-memory and FLOPs gauges.

ScaleFold (arxiv 2404.11068) got its 10-hour AlphaFold training largely
by measuring and then deleting per-step overheads; the biggest invisible
overheads in this stack are XLA compiles (30+ s per serving bucket, once
per shape) and device-memory pressure. This module makes both visible
through the metric registry and the span tracer:

  * `CompileTracker` — a context manager around any compile site
    (the serving AOT cache, a trainer's warmup step): per-key compile
    count + wall seconds as registry metrics, plus a `compile` span.
  * `device_memory_gauges` — `device.memory_stats()` (TPU/GPU backends;
    returns None on CPU) into `device_memory_bytes{kind=...}` gauges.
  * `flops_gauges` — the analytic model-FLOP count from `utils/flops.py`
    (XLA's own cost analysis undercounts scanned trunks ~100x) as gauges,
    so MFU can be derived from any metrics scrape.
  * `profile_trace` — the jax.profiler context manager (migrated from
    utils/observability.py; re-exported there for back-compat).
  * `SCOPES` / `scope` / `scoped` — the names the training step carries
    into every device trace (`jax.named_scope`): trace-time metadata
    only, the compiled program is the same with or without them.
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional

import jax

from alphafold2_tpu.telemetry.registry import MetricRegistry
from alphafold2_tpu.telemetry.trace import NULL_TRACER, Tracer


# --- names inside the program ------------------------------------------------
#
# Two levels, no third. An OUTER name says which part of the step an
# operation belongs to; an INNER name says which piece of an attention or
# feed-forward block. The reduction (benchmarks/scope_reduce.py) keys device
# time by the innermost outer name on an operation's name stack, plus the
# innermost inner name after it: `seq_attn/attn_core`, `seq_ff/geglu`,
# `mds`. The tuples are the documented list: the code below, the reduction,
# its tests and docs/OBSERVABILITY.md all read them.

#: the eight trunk ops, named by the parameter key each already has
TRUNK_OP_SCOPES = (
    "seq_attn", "seq_ff", "msa_attn", "msa_ff",
    "seq_cross", "seq_ff2", "msa_cross", "msa_ff2",
)
#: around the trunk (models/alphafold2.py); `trunk` holds the eight ops
MODEL_SCOPES = ("embed", "template_tower", "trunk", "distogram_head")
#: the structure tail of the end-to-end loss (training/e2e.py)
TAIL_SCOPES = (
    "center_distogram", "mds", "sidechain_lift", "refiner", "kabsch_loss",
    "dispersion",
)
#: the decoder language models (models/decoder.py, training/lm.py):
#: `decoder_layers` is the scans over the layers (what is left to it: the
#: slices of the stacked parameters, the gradient's write-back), `mla_attn`
#: (`deepseek_v3`), `cca_attn` (`zaya`) or `gqa_attn` (`mellum`), `dense_mlp`
#: and `moe` a layer's halves, `residual_scale` the `zaya` block's a * h + c,
#: `lm_head_loss` the final norm, the head (tied or not) and the cross-entropy
DECODER_SCOPES = ("lm_embed", "decoder_layers", "mla_attn", "dense_mlp", "moe",
                  "lm_head_loss", "cca_attn", "residual_scale", "gqa_attn")
#: opt.update + apply_updates + global_norm (training/harness.py)
OPTIMIZER_SCOPE = "optimizer"
OUTER_SCOPES = (MODEL_SCOPES + TRUNK_OP_SCOPES + TAIL_SCOPES + DECODER_SCOPES
                + (OPTIMIZER_SCOPE,))
#: inside attention (ops/attention.py) and feed-forward (ops/feedforward.py)
TRUNK_INNER_SCOPES = ("qkv_proj", "attn_core", "out_proj", "kv_compress", "geglu")
#: inside latent attention (models/decoder.py; it shares `qkv_proj`,
#: `attn_core` and `out_proj`), inside the expert layer (ops/moe.py), and
#: inside compressed convolutional attention (`conv_mix`: both convolutions
#: and the q-k mean; `qk_norm_rope`; `value_shift`: the values, half of them
#: the previous token's); `attn_core_window` is the causal core under a
#: sliding window (ops/flash.py: `mellum`'s window layers), `attn_core`
#: the core without one
DECODER_INNER_SCOPES = ("kv_down_up", "rope", "router", "dispatch", "experts",
                        "combine", "shared_expert", "conv_mix", "qk_norm_rope",
                        "value_shift", "attn_core_window")
INNER_SCOPES = TRUNK_INNER_SCOPES + DECODER_INNER_SCOPES
#: phase marker: the body of the reversible trunk's hand-written backward,
#: so that a `jvp(...)` under it reads as the reconstruction and not as the
#: primal forward (models/reversible.py)
REVERSIBLE_BWD_SCOPE = "reversible_bwd"
SCOPES = OUTER_SCOPES + INNER_SCOPES + (REVERSIBLE_BWD_SCOPE,)


# the one place a name enters the program; tests swap it for a no-op to
# show that the lowered program is the same without the names
_named_scope = jax.named_scope


def scope(name: str):
    """`jax.named_scope(name)` for a documented name; any other name is a
    ValueError at trace time, so the list above cannot fall behind the
    code. Costs nothing per step: it runs only while a function is traced."""
    if name not in SCOPES:
        raise ValueError(
            f"{name!r} is not a documented scope; add it to "
            f"telemetry/profiling.py SCOPES (and docs/OBSERVABILITY.md)")
    return _named_scope(name)


def scoped(name: str, fn, *args, **kwargs):
    """`fn(*args, **kwargs)` under `scope(name)`."""
    with scope(name):
        return fn(*args, **kwargs)


@contextlib.contextmanager
def profile_trace(log_dir: str, enabled: bool = True):
    """Capture a jax.profiler trace (XLA device timelines included) into
    `log_dir` for the enclosed step window; view with TensorBoard's profile
    plugin or Perfetto."""
    if not enabled:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class CompileTracker:
    """Compile-event accounting around an AOT cache or a jit warmup.

    ``with tracker.track(bucket=256): exe = jit(f).lower(...).compile()``
    lands, per label set:
      * counter  `<prefix>_total`          — COMPLETED compile events
      * gauge    `<prefix>_seconds_total`  — cumulative wall seconds
      * gauge    `<prefix>_last_seconds`   — most recent compile
      * counter  `<prefix>_failed_total`   — compiles that raised
    and one `compile` span (cat="compile") on the tracer. A failed
    compile (XLA OOM, lowering error) must not read as a completed one —
    only the failure counter moves, and the span carries the `error`
    attribute; the exception propagates unchanged.
    """

    def __init__(self, registry: MetricRegistry, tracer: Tracer = NULL_TRACER,
                 prefix: str = "compile"):
        self.registry = registry
        self.tracer = tracer
        self.prefix = prefix

    @contextlib.contextmanager
    def track(self, **labels):
        with self.tracer.span(self.prefix, cat="compile", **labels):
            t0 = time.perf_counter()
            try:
                yield
            except BaseException:
                self.registry.counter(
                    f"{self.prefix}_failed_total",
                    help="compile attempts that raised", **labels).inc()
                raise
            dt = time.perf_counter() - t0
            self.registry.counter(
                f"{self.prefix}_total",
                help="completed compile events", **labels).inc()
            self.registry.gauge(
                f"{self.prefix}_seconds_total",
                help="cumulative compile wall seconds", **labels).inc(dt)
            self.registry.gauge(
                f"{self.prefix}_last_seconds",
                help="wall seconds of the most recent compile",
                **labels).set(dt)


def host_memory_gauges(registry: MetricRegistry) -> dict:
    """Portable process-memory gauges: `host_memory_bytes{kind=rss}`
    (current resident set, /proc when available) and `{kind=peak_rss}`
    (lifetime peak via `resource.getrusage`). Unlike
    `device_memory_gauges` this NEVER returns None — CPU-only runs get
    host pressure where `device.memory_stats()` is blind — and costs two
    syscalls, so the ops-plane ticker can call it every second.

    Returns {"rss_bytes": ..., "peak_rss_bytes": ...} (0.0 for a field
    the platform cannot report — absence is explicit, never a crash)."""
    peak = rss = 0.0
    try:
        import resource
        import sys

        ru = resource.getrusage(resource.RUSAGE_SELF)
        # ru_maxrss is kilobytes on Linux, bytes on macOS
        peak = float(ru.ru_maxrss) * (1.0 if sys.platform == "darwin"
                                      else 1024.0)
    except (ImportError, OSError):  # resource is POSIX-only
        pass
    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith("VmRSS:"):
                    rss = float(line.split()[1]) * 1024.0  # kB field
                    break
    except OSError:
        rss = peak  # no procfs: peak is the honest upper bound we have
    out = {"rss_bytes": rss, "peak_rss_bytes": peak}
    help_ = "process host memory (resource.getrusage / /proc/self/status)"
    registry.gauge("host_memory_bytes", help=help_, kind="rss").set(rss)
    registry.gauge("host_memory_bytes", help=help_, kind="peak_rss").set(peak)
    return out


def device_memory_gauges(registry: MetricRegistry,
                         device=None) -> Optional[dict]:
    """Record `device.memory_stats()` into gauges; returns the raw stats
    dict, or None when the backend exposes none (CPU) — callers must not
    treat absence as zero memory."""
    dev = device if device is not None else jax.local_devices()[0]
    stats = getattr(dev, "memory_stats", lambda: None)()
    if not stats:
        return None
    for kind, value in stats.items():
        if isinstance(value, (int, float)) and not isinstance(value, bool):
            registry.gauge(
                "device_memory_bytes",
                help="device.memory_stats() fields",
                device=str(dev.id), kind=str(kind),
            ).set(float(value))
    return dict(stats)


def flops_gauges(registry: MetricRegistry, model_cfg, n: int, r: int, c: int,
                 grad_accum: int = 1) -> dict:
    """Analytic per-step FLOP gauges for the configured model workload
    (pair side n, MSA r x c): `model_train_step_flops` and
    `model_forward_flops`. Paired with a measured steps/sec these give
    MFU without trusting XLA's scan-blind cost analysis."""
    from alphafold2_tpu.utils.flops import model_fwd_flops, train_step_flops

    fwd = model_fwd_flops(model_cfg, n, r, c)
    step = train_step_flops(model_cfg, n, r, c, grad_accum=grad_accum)
    registry.gauge(
        "model_forward_flops",
        help="analytic matmul FLOPs of one forward (utils/flops.py)",
    ).set(fwd)
    registry.gauge(
        "model_train_step_flops",
        help="analytic matmul FLOPs of one optimizer step",
    ).set(step)
    return {"forward_flops": fwd, "train_step_flops": step}
