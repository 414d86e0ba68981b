"""Telemetry subsystem tests (tier-1, CPU): span tracer + Chrome export,
metric registry + Prometheus round-trip, disabled no-op contract,
regression gate, and the serving/training phase-span integrations the
ISSUE acceptance criteria name (enqueue->batch->execute for serving,
data->step->checkpoint for training)."""

import json
import threading

import jax
import numpy as np
import pytest

from alphafold2_tpu.telemetry import (
    NULL_REGISTRY,
    NULL_TRACER,
    MetricRegistry,
    Tracer,
    flatten_snapshot,
    parse_prometheus_text,
)
from alphafold2_tpu.telemetry.check import check
from alphafold2_tpu.telemetry.check import main as check_main
from alphafold2_tpu.telemetry.trace import _NULL_SPAN

# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def _schema_check_chrome(doc):
    """Minimal trace-event schema: the invariants Perfetto/chrome://tracing
    need to render the file at all."""
    assert isinstance(doc, dict) and isinstance(doc["traceEvents"], list)
    for e in doc["traceEvents"]:
        assert e["ph"] in ("X", "M")
        assert isinstance(e["name"], str) and e["name"]
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
            assert isinstance(e["args"], dict)


class TestTracer:
    def test_nested_spans_and_summary(self):
        t = [0.0]
        tr = Tracer(clock=lambda: t[0])
        with tr.span("outer", cat="c", k=1) as sp:
            t[0] += 1.0
            with tr.span("inner"):
                t[0] += 0.25
            sp.set("late", "yes")
        spans = tr.spans()
        by_name = {s["name"]: s for s in spans}
        assert by_name["outer"]["dur_s"] == pytest.approx(1.25)
        assert by_name["inner"]["dur_s"] == pytest.approx(0.25)
        assert by_name["inner"]["depth"] == 1  # nested under outer
        assert by_name["outer"]["attrs"] == {"k": 1, "late": "yes"}
        summary = tr.summary()
        assert summary["outer"]["count"] == 1
        assert summary["outer"]["total_s"] == pytest.approx(1.25)

    def test_exception_exits_span_with_error_attr(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("x")
        (span,) = tr.spans()
        assert span["attrs"]["error"] == "RuntimeError"

    def test_chrome_export_is_valid_and_parseable(self, tmp_path):
        tr = Tracer()
        with tr.span("a", cat="x", bucket=8):
            pass
        tr.add("queued", 0.5, cat="x")
        path = str(tmp_path / "trace.json")
        tr.export_chrome(path)
        doc = json.load(open(path))
        _schema_check_chrome(doc)
        names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
        assert set(names) == {"a", "queued"}
        # thread metadata present so Perfetto labels the timeline
        assert any(e["ph"] == "M" and e["name"] == "thread_name"
                   for e in doc["traceEvents"])

    def test_every_span_records_its_parent(self):
        tr = Tracer()
        with tr.span("train.step"):
            with tr.span("inner"):
                tr.add("measured", 0.001, cat="x")
            tr.add("beside", 0.001)
            assert tr.open_span() == "train.step"
        tr.add("top", 0.001)
        assert tr.open_span() is None
        by_name = {s["name"]: s for s in tr.spans()}
        assert {n: (s["parent"], s["depth"]) for n, s in by_name.items()} == {
            "train.step": (None, 0), "inner": ("train.step", 1),
            "measured": ("inner", 2), "beside": ("train.step", 1), "top": (None, 0)}
        args = {e["name"]: e["args"] for e in tr.chrome_trace()["traceEvents"]
                if e["ph"] == "X"}
        assert args["measured"]["parent"] == "inner"

    def test_retention_bound_counts_drops(self):
        tr = Tracer(max_spans=2)
        for i in range(5):
            with tr.span(f"s{i}"):
                pass
        assert len(tr.spans()) == 2
        assert tr.dropped == 3
        assert tr.summary()["_dropped"] == 3
        assert tr.chrome_trace()["otherData"]["dropped_spans"] == 3

    def test_threaded_spans_keep_their_tid(self):
        tr = Tracer()

        def work():
            with tr.span("worker_side"):
                pass

        th = threading.Thread(target=work, name="side")
        th.start()
        th.join()
        with tr.span("main_side"):
            pass
        tids = {s["name"]: s["tid"] for s in tr.spans()}
        assert tids["worker_side"] != tids["main_side"]


class TestDisabledNoOpPath:
    def test_disabled_tracer_allocates_nothing_and_records_nothing(self):
        tr = Tracer(enabled=False)
        # the SAME singleton comes back for every call: no per-span
        # allocation on the disabled path
        assert tr.span("a", k=1) is tr.span("b") is _NULL_SPAN
        with tr.span("x") as sp:
            sp.set("k", "v")
        tr.add("y", 1.0)
        assert tr.spans() == []
        assert tr.summary() == {}
        assert NULL_TRACER.span("z") is _NULL_SPAN

    def test_disabled_registry_hands_out_shared_noop_metric(self):
        r = MetricRegistry(enabled=False)
        c = r.counter("a_total")
        g = r.gauge("b")
        h = r.histogram("c_seconds")
        assert c is g is h  # one shared no-op object, no allocation
        c.inc(5)
        g.set(3)
        h.observe(1.0)
        assert c.value == 0.0 and h.snapshot() == {}
        assert r.snapshot() == {"counters": {}, "gauges": {},
                                "histograms": {}}
        assert r.to_prometheus() == ""
        assert NULL_REGISTRY.counter("x") is c


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_get_or_create_identity(self):
        r = MetricRegistry()
        assert r.counter("x_total", code="a") is r.counter("x_total",
                                                           code="a")
        assert r.counter("x_total", code="a") is not r.counter("x_total",
                                                               code="b")

    def test_type_conflict_raises(self):
        r = MetricRegistry()
        r.counter("x_total")
        with pytest.raises(ValueError, match="already registered"):
            r.gauge("x_total")

    def test_invalid_names_rejected(self):
        r = MetricRegistry()
        with pytest.raises(ValueError):
            r.counter("bad name")
        with pytest.raises(ValueError):
            r.counter("ok_total", **{"bad-label": "v"})

    def test_prometheus_roundtrip(self):
        r = MetricRegistry()
        r.counter("req_total", help="requests", outcome="ok").inc(3)
        r.counter("req_total", outcome="failed").inc(1)
        r.gauge("queue_depth").set(7)
        h = r.histogram("lat_seconds", help="latency")
        for v in (0.1, 0.2, 0.4):
            h.observe(v)
        text = r.to_prometheus()
        assert "# TYPE lat_seconds histogram" in text
        parsed = parse_prometheus_text(text)
        assert parsed[("req_total", (("outcome", "ok"),))] == 3.0
        assert parsed[("req_total", (("outcome", "failed"),))] == 1.0
        assert parsed[("queue_depth", ())] == 7.0
        # real cumulative buckets (not summary-quantile gauges): each
        # le bound carries the count of observations <= it, +Inf = count
        assert parsed[("lat_seconds_bucket", (("le", "0.05"),))] == 0.0
        assert parsed[("lat_seconds_bucket", (("le", "0.1"),))] == 1.0
        assert parsed[("lat_seconds_bucket", (("le", "0.25"),))] == 2.0
        assert parsed[("lat_seconds_bucket", (("le", "0.5"),))] == 3.0
        assert parsed[("lat_seconds_bucket", (("le", "+Inf"),))] == 3.0
        assert parsed[("lat_seconds_count", ())] == 3.0
        assert parsed[("lat_seconds_sum", ())] == pytest.approx(0.7)
        # the scrape agrees with the in-process snapshot, bucket by bucket
        snap = r.snapshot()["histograms"]["lat_seconds"]
        for le, cum in snap["buckets"].items():
            assert parsed[("lat_seconds_bucket", (("le", le),))] == cum

    def test_histogram_buckets_cumulative_and_monotonic(self):
        """Buckets are LIFETIME cumulative counters: the sliding window
        evicting old observations must never rewind a bucket count, and
        counts are monotone in le."""
        from alphafold2_tpu.telemetry.registry import Histogram

        h = Histogram(window=4, bounds=(1.0, 2.0, 5.0))
        for _ in range(10):
            h.observe(0.5)
        h.observe(10.0)  # lands only in +Inf
        b = h.buckets()
        assert b == {"1": 10, "2": 10, "5": 10, "+Inf": 11}
        # window only holds 4 values but lifetime buckets kept all 11
        assert h.snapshot()["window"] == 4
        # boundary value counts into its own bucket (le is inclusive)
        h.observe(2.0)
        assert h.buckets()["2"] == 11
        with pytest.raises(ValueError, match="increasing"):
            Histogram(bounds=(1.0, 1.0, 2.0))

    def test_prometheus_label_escaping_roundtrips(self):
        r = MetricRegistry()
        tricky = 'quo"te\\slash\nnewline'
        r.counter("esc_total", path=tricky).inc()
        parsed = parse_prometheus_text(r.to_prometheus())
        assert parsed[("esc_total", (("path", tricky),))] == 1.0

    def test_parser_rejects_garbage(self):
        with pytest.raises(ValueError, match="line 1"):
            parse_prometheus_text("{not a sample}")

    def test_compile_tracker_failure_counts_separately(self):
        """A compile that raises must not read as a completed compile —
        only <prefix>_failed_total moves; the exception propagates."""
        from alphafold2_tpu.telemetry import CompileTracker

        r = MetricRegistry()
        tracker = CompileTracker(r, prefix="c")
        with pytest.raises(RuntimeError):
            with tracker.track(bucket="8"):
                raise RuntimeError("xla oom")
        snap = r.snapshot()
        assert snap["counters"]['c_failed_total{bucket="8"}'] == 1
        assert 'c_total{bucket="8"}' not in snap["counters"]
        assert snap["gauges"] == {}
        with tracker.track(bucket="8"):
            pass
        assert r.snapshot()["counters"]['c_total{bucket="8"}'] == 1

    def test_snapshot_and_flatten(self):
        r = MetricRegistry()
        r.counter("a_total").inc(2)
        r.gauge("b", bucket="8").set(1.5)
        snap = r.snapshot()
        assert snap["counters"]["a_total"] == 2.0
        assert snap["gauges"]['b{bucket="8"}'] == 1.5
        flat = flatten_snapshot(snap)
        assert flat["counters.a_total"] == 2.0


# ---------------------------------------------------------------------------
# regression gate
# ---------------------------------------------------------------------------


def _bench_line(value, **extras):
    return {"metric": "e2e_steps_per_sec", "value": value, "unit": "x",
            **extras}


class TestRegressionGate:
    def test_equal_snapshots_pass(self):
        ok, rows = check(_bench_line(1.0), _bench_line(1.0))
        assert ok and rows[0]["status"] == "ok"

    def test_injected_regression_fails(self):
        # the acceptance fixture: a 50% throughput drop must gate
        ok, rows = check(_bench_line(0.5), _bench_line(1.0))
        assert not ok
        (row,) = [r for r in rows if r["metric"] == "e2e_steps_per_sec"]
        assert row["status"] == "regressed" and row["direction"] == "higher"

    def test_improvement_and_within_tolerance_pass(self):
        assert check(_bench_line(2.0), _bench_line(1.0))[0]  # improvement
        assert check(_bench_line(0.95), _bench_line(1.0))[0]  # within 10%

    def test_lower_is_better_metrics(self):
        cur = _bench_line(1.0, sec_per_step=2.0)
        base = _bench_line(1.0, sec_per_step=1.0)
        ok, rows = check(cur, base)
        assert not ok
        (row,) = [r for r in rows if r["metric"] == "sec_per_step"]
        assert row["direction"] == "lower" and row["status"] == "regressed"

    def test_driver_artifact_and_nested_stats_formats(self):
        art = {"n": 3, "cmd": "python bench.py",
               "parsed": _bench_line(1.0, sec_per_step=1.0)}
        ok, rows = check(art, art)
        assert ok and len(rows) >= 2
        stats = {"latency": {"p50": 0.2, "p95": 0.5},
                 "requests": {"completed": 10}}
        worse = {"latency": {"p50": 0.9, "p95": 0.5},
                 "requests": {"completed": 10}}
        ok, rows = check(worse, stats)
        assert not ok
        (p50,) = [r for r in rows if r["metric"] == "latency.p50"]
        assert p50["status"] == "regressed"

    def test_empty_baseline_gates_nothing(self):
        ok, rows = check(_bench_line(1.0), {"published": {}})
        assert ok and rows == []

    def test_unknown_direction_is_informational(self):
        ok, rows = check({"weird_quantity": 5.0}, {"weird_quantity": 1.0})
        assert ok and rows[0]["status"] == "ungated"

    def test_volume_counts_never_gate(self):
        """Absolute counts/windows/sums scale with traffic volume, not
        performance: a longer current run must not fail the gate."""
        base = {"latency": {"count": 24, "window": 24, "sum": 10.0,
                            "p50": 0.2},
                "compiles": {"count": 1}, "uptime_s": 5.0,
                "serving_requests_total": 24}
        cur = {"latency": {"count": 36, "window": 36, "sum": 15.0,
                           "p50": 0.2},
               "compiles": {"count": 2}, "uptime_s": 9.0,
               "serving_requests_total": 36}
        ok, rows = check(cur, base)
        assert ok
        gated = {r["metric"] for r in rows if r["direction"] is not None}
        assert gated == {"latency.p50"}

    def test_cli_exit_codes(self, tmp_path, capsys):
        cur = tmp_path / "cur.json"
        base = tmp_path / "base.json"
        base.write_text(json.dumps(_bench_line(1.0)))
        cur.write_text(json.dumps(_bench_line(1.0)))
        assert check_main(["--current", str(cur), "--baseline",
                           str(base)]) == 0
        cur.write_text(json.dumps(_bench_line(0.2)))
        assert check_main(["--current", str(cur), "--baseline",
                           str(base)]) == 1
        capsys.readouterr()
        assert check_main(["--current", str(cur), "--baseline",
                           str(tmp_path / "missing.json")]) == 2

    def test_cli_rule_override_and_require_overlap(self, tmp_path, capsys):
        cur = tmp_path / "cur.json"
        base = tmp_path / "base.json"
        base.write_text(json.dumps({"weird_quantity": 1.0}))
        cur.write_text(json.dumps({"weird_quantity": 0.2}))
        argv = ["--current", str(cur), "--baseline", str(base)]
        assert check_main(argv) == 0  # ungated by default
        assert check_main(argv + ["--require-overlap"]) == 1
        assert check_main(argv + ["--rule",
                                  "weird_quantity=higher:0.1"]) == 1
        capsys.readouterr()

    def test_smoke_against_committed_baselines(self, capsys):
        """The CI smoke the ISSUE asks for: the gate must run clean over
        the repo's own committed perf artifacts (BASELINE.json publishes
        nothing yet -> nothing gated; BENCH rounds compare sanely)."""
        import os

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        baseline = os.path.join(root, "BASELINE.json")
        bench = os.path.join(root, "BENCH_r05.json")
        assert check_main(["--current", bench, "--baseline", baseline]) == 0
        # a BENCH round against itself must always pass
        assert check_main(["--current", bench, "--baseline", bench]) == 0
        capsys.readouterr()


# ---------------------------------------------------------------------------
# serving integration: enqueue -> (queue_wait) -> batch -> execute -> respond
# ---------------------------------------------------------------------------

from alphafold2_tpu.constants import AA_ORDER  # noqa: E402
from alphafold2_tpu.models import Alphafold2Config, alphafold2_init  # noqa: E402
from alphafold2_tpu.serving import ServingConfig, ServingEngine  # noqa: E402

TINY = Alphafold2Config(dim=16, depth=1, heads=2, dim_head=8, max_seq_len=16)


class FakeModelEngine(ServingEngine):
    """Device call stubbed at the documented `_call_executable` seam (same
    pattern as tests/test_serving.py): lifecycle spans in milliseconds,
    zero XLA compiles."""

    def _call_executable(self, bucket, tokens, mask, msa=None, msa_mask=None):
        B, Lb = tokens.shape
        return {
            "coords": np.zeros((B, Lb, 3), np.float32),
            "confidence": np.full((B, Lb), 0.5, np.float32),
            "stress": np.zeros((B,), np.float32),
        }


@pytest.fixture(scope="module")
def tiny_params():
    return alphafold2_init(jax.random.PRNGKey(0), TINY)


def _seq(length, offset=0):
    aa = AA_ORDER.replace("W", "")
    return "".join(aa[(offset + i) % len(aa)] for i in range(length))


class TestServingTraceIntegration:
    def test_request_lifecycle_spans_cover_enqueue_batch_execute(
            self, tiny_params, tmp_path):
        tracer = Tracer()
        eng = FakeModelEngine(
            tiny_params, TINY,
            ServingConfig(buckets=(8, 16), max_batch=2, max_wait_s=0.01,
                          mds_iters=2),
            tracer=tracer,
        )
        with eng:
            for i in range(4):
                eng.predict(_seq(6 + i))
        names = {s["name"] for s in tracer.spans()}
        # the acceptance criterion: enqueue -> batch -> execute present
        # (plus the queue phase and the respond tail)
        assert {"serving.enqueue", "serving.queue_wait", "serving.batch",
                "serving.execute", "serving.respond"} <= names
        # the export is a valid Chrome trace
        path = str(tmp_path / "serving_trace.json")
        tracer.export_chrome(path)
        _schema_check_chrome(json.load(open(path)))
        # per-phase aggregates ride the stats payload
        stats = eng.stats()
        assert stats["telemetry"]["spans"]["serving.batch"]["count"] >= 1
        counters = stats["telemetry"]["metrics"]["counters"]
        assert counters['serving_requests_total{outcome="submitted"}'] == 4
        assert counters['serving_requests_total{outcome="completed"}'] == 4

    def test_rejection_exits_enqueue_span_with_error(self, tiny_params):
        from alphafold2_tpu.serving import InvalidSequenceError

        tracer = Tracer()
        eng = FakeModelEngine(
            tiny_params, TINY, ServingConfig(buckets=(8,), max_batch=1),
            tracer=tracer,
        )
        with eng:
            with pytest.raises(InvalidSequenceError):
                eng.submit("XYZ123")
        enq = [s for s in tracer.spans() if s["name"] == "serving.enqueue"]
        assert enq and enq[0]["attrs"]["error"] == "InvalidSequenceError"

    def test_real_engine_records_compile_spans_and_gauges(self, tiny_params):
        """One REAL AOT compile: the serving_compile span fires and the
        per-bucket compile count/seconds gauges land in stats() under both
        the legacy `compiles` section and the registry view."""
        tracer = Tracer()
        eng = ServingEngine(
            tiny_params, TINY,
            ServingConfig(buckets=(8,), max_batch=1, mds_iters=2),
            tracer=tracer,
        )
        with eng:
            eng.predict(_seq(5))
        spans = [s for s in tracer.spans() if s["name"] == "serving_compile"]
        assert len(spans) == 1 and spans[0]["attrs"]["bucket"] == "8"
        stats = eng.stats()
        assert stats["compiles"]["count"] == 1
        assert stats["compiles"]["seconds_by_bucket"]["8"] > 0
        counters = stats["telemetry"]["metrics"]["counters"]
        gauges = stats["telemetry"]["metrics"]["gauges"]
        assert counters['serving_compile_total{bucket="8"}'] == 1
        assert gauges['serving_compile_seconds_total{bucket="8"}'] > 0
        # the compile sits inside the execute span on the trace
        assert any(s["name"] == "serving.execute" for s in tracer.spans())

    def test_poison_split_retry_does_not_double_count_batch_spans(
            self, tiny_params):
        """The per-request poison-isolation retry re-enters the batch path
        from inside the parent serving.batch span; it must not add a
        second queue_wait record per request or nested batch spans."""
        from alphafold2_tpu.serving import PredictionError

        calls = {"n": 0}

        class PoisonFirstBatch(FakeModelEngine):
            def _call_executable(self, bucket, tokens, mask, msa=None,
                                 msa_mask=None):
                calls["n"] += 1
                if calls["n"] == 1:
                    raise RuntimeError("poisoned multi-request batch")
                return super()._call_executable(bucket, tokens, mask, msa,
                                                msa_mask)

        tracer = Tracer()
        eng = PoisonFirstBatch(
            tiny_params, TINY,
            ServingConfig(buckets=(8,), max_batch=2, max_wait_s=5.0),
            tracer=tracer,
        )
        with eng:
            r1 = eng.submit(_seq(5))
            r2 = eng.submit(_seq(6))
            done = []
            for r in (r1, r2):
                try:
                    done.append(r.result(timeout=10))
                except PredictionError:
                    pass
        assert calls["n"] == 3  # 1 poisoned batch + 2 single retries
        names = [s["name"] for s in tracer.spans()]
        assert names.count("serving.batch") == 1
        assert names.count("serving.queue_wait") == 2
        assert names.count("serving.execute") == 3  # real device calls

    def test_untraced_engine_stats_still_carry_empty_telemetry(
            self, tiny_params):
        eng = FakeModelEngine(tiny_params, TINY,
                              ServingConfig(buckets=(8,), max_batch=1))
        with eng:
            eng.predict(_seq(5))
            stats = eng.stats()
        assert stats["telemetry"]["spans"] == {}
        # registry metrics still populated — they are always on
        assert stats["telemetry"]["metrics"]["counters"][
            'serving_requests_total{outcome="completed"}'] == 1


# ---------------------------------------------------------------------------
# training integration: data -> step -> metrics fetch -> checkpoint
# ---------------------------------------------------------------------------


class TestTrainingTraceIntegration:
    def _fake_step(self, fail_at=None):
        fired = {"crashed": False}

        def step_fn(state, batch, rng):  # noqa: ARG001
            step = int(np.asarray(state["step"]))
            if fail_at is not None and step == fail_at and not fired["crashed"]:
                fired["crashed"] = True  # crash exactly once
                raise RuntimeError("injected crash")
            new_state = {**state,
                         "step": np.asarray(step + 1, np.int32)}
            return new_state, {"loss": 0.1, "grad_norm": 0.5}

        return step_fn

    def test_resilient_loop_emits_phase_spans(self, tmp_path):
        from alphafold2_tpu.training.checkpoint import (
            VerifiedCheckpointManager,
        )
        from alphafold2_tpu.training.resilience import run_resilient

        tracer = Tracer()
        state = {"step": np.asarray(0, np.int32),
                 "params": {"w": np.zeros(2, np.float32)}}
        mgr = VerifiedCheckpointManager(str(tmp_path / "ckpt"))
        fetches = {}

        def fetch(step):
            fetches[step] = fetches.get(step, 0) + 1
            return {"x": np.zeros(1)}

        run_resilient(self._fake_step(), state, fetch, steps=3,
                      make_rng=lambda i: None, mgr=mgr, tracer=tracer)
        names = [s["name"] for s in tracer.spans()]
        # the acceptance criterion: data -> step -> checkpoint per step
        assert names.count("train.fetch") == 3
        assert names.count("train.step") == 3
        assert names.count("train.metrics_fetch") == 3
        assert names.count("train.checkpoint") == 3
        doc = tracer.chrome_trace()
        _schema_check_chrome(doc)

    def test_recovery_episode_becomes_restore_span(self):
        from alphafold2_tpu.training.resilience import run_resilient

        tracer = Tracer()
        state = {"step": np.asarray(0, np.int32),
                 "params": {"w": np.zeros(2, np.float32)}}
        batch = {"x": np.zeros(1)}
        run_resilient(self._fake_step(fail_at=1), state,
                      lambda step: dict(batch), steps=3,
                      make_rng=lambda i: None, max_restarts=2,
                      tracer=tracer)
        restores = [s for s in tracer.spans()
                    if s["name"] == "train.restore"]
        assert len(restores) == 1
        assert restores[0]["attrs"]["cause"] == "RuntimeError"
        assert "in-memory" in restores[0]["attrs"]["restored_from"]


class TestSweepArtifactGate:
    """PERF_SWEEP.jsonl auto-detection (PR 7): sweep legs gate like any
    other snapshot, so a future on-chip run of the new legs
    (branch_parallel_on/off, fused_gate_on/off, ...) is regression-gated
    with zero extra wiring."""

    def _sweep(self, tmp_path, name, rows):
        p = tmp_path / name
        p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
        return str(p)

    def test_jsonl_rows_flatten_and_gate(self, tmp_path):
        baseline = self._sweep(tmp_path, "base.jsonl", [
            {"bench": "branch_parallel_on", "spec": {"trunk_schedule":
             "branch_parallel"}, "result": {"sec_per_step": 20.0},
             "error": None},
            {"bench": "fused_gate_on", "result": {"sec_per_step": 24.0}},
            # structured skip and error rows contribute nothing
            {"bench": "overlap_on", "result": {"skipped": "single-device"}},
            {"bench": "e2e_auto", "result": None, "error": "timeout"},
        ])
        current = self._sweep(tmp_path, "cur.jsonl", [
            {"bench": "branch_parallel_on", "result": {"sec_per_step": 19.0}},
            {"bench": "fused_gate_on", "result": {"sec_per_step": 30.0}},
        ])
        passed, rows = check(current, baseline)
        by_metric = {r["metric"]: r for r in rows}
        assert not passed  # fused_gate_on regressed 25% > 15% tol
        assert by_metric["branch_parallel_on.sec_per_step"]["status"] == "ok"
        assert by_metric["fused_gate_on.sec_per_step"]["status"] == "regressed"
        # the skip/error legs never became comparable metrics
        assert not any(m.startswith(("overlap_on", "e2e_auto"))
                       for m in by_metric)

    def test_rerun_rows_supersede(self, tmp_path):
        path = self._sweep(tmp_path, "re.jsonl", [
            {"bench": "e2e_auto", "result": {"sec_per_step": 99.0}},
            {"bench": "e2e_auto", "result": {"sec_per_step": 24.4}},
        ])
        from alphafold2_tpu.telemetry.check import load_metrics

        assert load_metrics(path) == {"e2e_auto.sec_per_step": 24.4}

    def test_single_sweep_row_dict(self):
        from alphafold2_tpu.telemetry.check import load_metrics

        got = load_metrics({"bench": "fused_gate_off",
                            "result": {"sec_per_step": 25.0, "loss": 3.1}})
        assert got == {"fused_gate_off.sec_per_step": 25.0,
                       "fused_gate_off.loss": 3.1}

    def test_list_results_gate_too(self, tmp_path):
        # multi-line workers (the micro kernel grid) record LIST results:
        # each element must still become a gateable metric — qualified by
        # its string fields so grid points don't collide — instead of
        # being silently dropped from the gate
        row = {"bench": "micro_kernel", "result": [
            {"path": "kernel", "dir": "fwd", "shape": "B32_n1152",
             "sec_per_iter": 0.5, "platform": "tpu"},
            {"path": "kernel", "dir": "grad", "shape": "B32_n1152",
             "sec_per_iter": 1.2, "platform": "tpu"},
            {"skipped": "kernel path requires TPU"},  # contributes nothing
        ]}
        from alphafold2_tpu.telemetry.check import load_metrics

        got = load_metrics(row)
        assert got == {
            "micro_kernel.fwd.kernel.tpu.B32_n1152.sec_per_iter": 0.5,
            "micro_kernel.grad.kernel.tpu.B32_n1152.sec_per_iter": 1.2,
        }
        # and a regression in one grid point fails the gate
        base = self._sweep(tmp_path, "b.jsonl", [row])
        bad = {"bench": "micro_kernel", "result": [
            {**row["result"][0], "sec_per_iter": 0.9}, row["result"][1],
        ]}
        cur = self._sweep(tmp_path, "c.jsonl", [bad])
        passed, rows = check(cur, base)
        assert not passed
