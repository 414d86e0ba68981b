"""The compile recorder (telemetry/compile_record.py): JAX's compile phases
as records, their totals by phase, and their spans in the `Tracer`."""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from alphafold2_tpu.telemetry import NULL_TRACER, CompileTracker, MetricRegistry, Tracer
from alphafold2_tpu.telemetry import compile_record
from alphafold2_tpu.telemetry.compile_record import (
    EVENTS,
    PHASES,
    CompileRecorder,
    _add_to_union,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def recorder():
    """A recorder of this test's own, taken off JAX again after it."""
    rec = CompileRecorder().install()
    try:
        yield rec
    finally:
        rec.uninstall()


def _fresh(scale: float):
    """A function no cache has seen (a new object, so a new trace)."""
    def scaled_sum(x):
        return jnp.sum(x * scale)
    return scaled_sum


def _count(rec, fun_part):
    out = dict.fromkeys(PHASES, 0)
    for r in rec.records():
        if fun_part in r["fun"]:
            out[r["phase"]] += 1
    return out


def test_one_jit_is_one_trace_one_lowering_one_compile(recorder):
    x = jnp.ones((4,))
    t0 = time.perf_counter()
    jax.jit(_fresh(3.0))(x).block_until_ready()
    t1 = time.perf_counter()
    assert _count(recorder, "scaled_sum") == {"trace": 1, "lower": 1,
                                              "xla_compile": 1, "cache_load": 0}
    mine = [r for r in recorder.records() if "scaled_sum" in r["fun"]]
    for r in mine:
        assert t0 <= r["start"] <= r["end"] <= t1
        assert r["parent"] is None and r["thread"] == threading.get_ident()
    # trace, then lower, then compile: one after the other on one thread
    by_phase = {r["phase"]: r for r in mine}
    assert (by_phase["trace"]["end"] <= by_phase["lower"]["start"] + 1e-3
            and by_phase["lower"]["end"] <= by_phase["xla_compile"]["start"] + 1e-3)
    totals = recorder.totals()
    assert totals["counts"]["xla_compile"] >= 1 and totals["dropped"] == 0


def test_a_nested_trace_counts_once(recorder):
    inner = jax.jit(_fresh(2.0))

    def outer_fn(x):
        return inner(x) + inner(x + 1.0)

    x = jnp.ones((4,))
    t0 = time.perf_counter()
    jax.jit(outer_fn)(x).block_until_ready()
    snap = recorder.snapshot(t0, time.perf_counter())
    traces = [r for r in recorder.records()
              if r["phase"] == "trace" and t0 <= r["start"]]
    outer = [r for r in traces if "outer_fn" in r["fun"]]
    assert len(outer) == 1 and any("scaled_sum" in r["fun"] for r in traces)
    assert all(outer[0]["start"] <= r["start"] <= r["end"] <= outer[0]["end"]
               for r in traces)
    # the union is the outer trace alone; the sum would count the inner twice
    assert snap["seconds"]["trace"] == pytest.approx(
        outer[0]["end"] - outer[0]["start"], abs=1e-6)
    assert snap["seconds"]["trace"] < sum(r["end"] - r["start"] for r in traces)


@pytest.mark.parametrize("spans,want", [
    ([(0, 1), (2, 3)], 2.0),           # disjoint
    ([(1, 2), (0, 3)], 3.0),           # nested: the inner one ends first
    ([(0, 2), (1, 3)], 3.0),           # overlapping
    ([(1, 2), (4, 5), (0, 6)], 6.0),   # one span encloses two
    ([(0, 1), (0, 1)], 1.0),           # the same twice
])
def test_union_of_one_threads_spans(spans, want):
    intervals, total = [], 0.0
    for start, end in spans:
        total += _add_to_union(intervals, float(start), float(end))
    assert total == pytest.approx(want)
    assert intervals == sorted(intervals)


def test_compile_phases_nest_under_the_open_span(recorder, tmp_path):
    tracer = Tracer()
    recorder.attach(tracer)
    with tracer.span("train.step", cat="train", step=0):
        jax.jit(_fresh(5.0))(jnp.ones((3,))).block_until_ready()
    path = str(tmp_path / "trace.json")
    tracer.export_chrome(path)
    events = [e for e in json.load(open(path))["traceEvents"] if e["ph"] == "X"]
    (step,) = [e for e in events if e["name"] == "train.step"]
    phases = [e for e in events if e["cat"] == "compile"
              and "scaled_sum" in e["args"]["fun"]]
    assert sorted(e["name"] for e in phases) == ["compile.lower", "compile.trace",
                                                 "compile.xla"]
    for e in phases:
        assert e["args"]["parent"] == "train.step" and e["args"]["depth"] == 1
        assert e["tid"] == step["tid"]
        assert step["ts"] <= e["ts"] and e["ts"] + e["dur"] <= step["ts"] + step["dur"]
    assert all(r["parent"] == "train.step" for r in recorder.records()
               if "scaled_sum" in r["fun"])


def test_a_compile_trackers_span_is_their_parent(recorder):
    tracer = Tracer()
    recorder.attach(tracer)
    tracker = CompileTracker(MetricRegistry(), tracer=tracer, prefix="serving_compile")
    with tracker.track(bucket="8"):
        jax.jit(_fresh(7.0)).lower(jnp.ones((8,))).compile()
    # the tracker's own span is cat `compile` too, without `fun`
    spans = [s for s in tracer.spans()
             if s["cat"] == "compile" and "scaled_sum" in s["attrs"].get("fun", "")]
    assert {s["name"] for s in spans} == {"compile.trace", "compile.lower", "compile.xla"}
    assert {s["parent"] for s in spans} == {"serving_compile"}


def test_the_null_tracer_gets_no_spans_and_the_totals_still_count(recorder):
    recorder.attach(NULL_TRACER)
    x = jnp.ones((2,))
    before = recorder.totals()["counts"]["xla_compile"]
    jax.jit(_fresh(11.0))(x).block_until_ready()
    assert NULL_TRACER.spans() == [] and NULL_TRACER.span_count == 0
    assert recorder.totals()["counts"]["xla_compile"] == before + 1
    assert recorder.totals()["seconds"]["xla_compile"] > 0


def test_a_snapshot_keeps_what_ended_inside_its_window(recorder):
    x, y = jnp.ones((5,)), jnp.ones((6,))
    t0 = time.perf_counter()
    jax.jit(_fresh(13.0))(x).block_until_ready()
    t1 = time.perf_counter()
    jax.jit(_fresh(17.0))(y).block_until_ready()
    t2 = time.perf_counter()
    first, both = recorder.snapshot(t0, t1), recorder.snapshot(t0, t2)
    # jnp.sum and the product are traced inside the function's own trace
    assert first["counts"]["trace"] >= 1 and first["counts"]["cache_load"] == 0
    assert first["counts"]["lower"] == first["counts"]["xla_compile"] == 1
    assert both["counts"]["xla_compile"] == 2
    assert recorder.snapshot(t1, t2)["counts"]["xla_compile"] == 1
    assert recorder.snapshot(since=t2)["counts"]["xla_compile"] == 0
    assert {t["phase"] for t in first["top"] if "scaled_sum" in t["fun"]} == {
        "trace", "lower", "xla_compile"}
    assert 0 < first["union_s"] <= t1 - t0


def test_retention_is_bounded_and_the_totals_are_not():
    x = jnp.ones((7,))
    rec = CompileRecorder(max_records=2).install()
    try:
        jax.jit(_fresh(19.0))(x).block_until_ready()
    finally:
        rec.uninstall()
    counts = rec.totals()["counts"]
    assert counts["lower"] == counts["xla_compile"] == 1
    assert len(rec.records()) == 2 and rec.dropped == sum(counts.values()) - 2
    assert rec.snapshot()["dropped"] == rec.dropped


def test_install_is_idempotent_and_the_cache_setup_installs_it(monkeypatch, tmp_path):
    import jax._src.monitoring as mon

    from alphafold2_tpu.compile_cache import ENV_VAR, enable_compile_cache

    # a placed cache: the function returns before it touches JAX's config
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    n = len(mon.get_event_time_span_listeners())
    enable_compile_cache()
    compile_record.install()
    enable_compile_cache()
    listeners = mon.get_event_time_span_listeners()
    assert compile_record.RECORDER._on_span in listeners
    assert len(listeners) <= n + 1
    assert listeners.count(compile_record.RECORDER._on_span) == 1


_TWICE = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {root!r})
    import jax, jax.numpy as jnp
    import jax.monitoring as mon
    from alphafold2_tpu.telemetry import compile_record

    seen = set()
    mon.register_event_listener(lambda e, **kw: seen.add(e))
    mon.register_event_time_span_listener(lambda e, s, t, **kw: seen.add(e))
    jax.config.update("jax_compilation_cache_dir", {cache!r})
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    rec = compile_record.install()

    def twice_cached(x):
        return jnp.tanh(x) @ x.T

    jax.jit(twice_cached)(jnp.ones((8, 8))).block_until_ready()
    jax.clear_caches()
    jax.jit(twice_cached)(jnp.ones((8, 8))).block_until_ready()
    backend = [r["phase"] for r in rec.records()
               if "twice_cached" in r["fun"] and r["phase"] in ("xla_compile", "cache_load")]
    print(json.dumps({{"backend": backend, "seen": sorted(seen)}}))
""")


@pytest.fixture(scope="module")
def cached_twice(tmp_path_factory):
    """One process that compiles a function, drops the in-memory caches and
    compiles it again, with the persistent cache in a directory of its own
    at no minimum compile time."""
    cache = str(tmp_path_factory.mktemp("jax_cache"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-c", _TWICE.format(root=ROOT, cache=cache)],
        env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_a_second_compile_reads_as_a_cache_load(cached_twice):
    assert cached_twice["backend"] == ["xla_compile", "cache_load"]


def test_jax_still_emits_every_event_the_recorder_reads(cached_twice):
    """A JAX that renames one of them fails here instead of reading 0."""
    assert set(EVENTS) <= set(cached_twice["seen"])
