"""Training observability plane (telemetry/goodput.py).

Fast tier-1 coverage: ledger exclusive-time accounting and the
sums-to-wall invariant (clock-injected), the chaos matrix landing every
fault in its badput bucket (restart -> restore, preemption -> preempt,
checkpoint corruption -> checkpoint+restore, slow data -> data_fetch +
a `train_data_stall` incident), the straggler detector in a
clock-injected 2-process-shaped harness, the trainer `/healthz`
progress watchdog (503 on stall), federation with an injected gather,
and the loss-curve gate against the committed fixture pair.

Slow (`-m slow`): the PR 12 acceptance bar — a REAL 2-process CPU pod
training run where process 0's `/metrics` scrape carries per-process
step-time and fetch-time families for BOTH processes, the ledger
buckets sum to wall within 1%, and an injected slow-data fault on
process 1 books as data-stall badput and pages `train_data_stall`.
"""

import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from alphafold2_tpu.reliability import Fault, FaultPlan, Preempted, PreemptionHandler
from alphafold2_tpu.telemetry import MetricRegistry
from alphafold2_tpu.telemetry.goodput import (
    BUCKETS,
    NULL_TRAIN_TELEMETRY,
    FederatedRegistryView,
    GoodputLedger,
    MetricFederation,
    StragglerDetector,
    TrainTelemetry,
    relabeled_exposition,
)
from alphafold2_tpu.telemetry.ops_plane import FlightRecorder, OpsServer
from alphafold2_tpu.telemetry.registry import parse_prometheus_text
from alphafold2_tpu.training import (
    resilient_batches,
    run_resilient,
    with_fault_injection,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "tests", "data")


class Clock:
    """Injectable monotonic clock."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# --- the ledger ---------------------------------------------------------------


def test_ledger_buckets_sum_to_wall_exclusive_nesting():
    clk = Clock()
    reg = MetricRegistry()
    led = GoodputLedger(reg, clock=clk)
    with led.account("data_fetch"):
        clk.advance(1.0)
    with led.account("compile"):
        clk.advance(2.0)
        with led.account("assembly"):  # nested must not double-count
            clk.advance(0.5)
    times = led.step_complete(0)
    clk.advance(0.25)  # uncategorized time -> idle
    totals = led.totals()
    assert totals["data_fetch"] == pytest.approx(1.0)
    assert totals["compile"] == pytest.approx(2.0)
    assert totals["assembly"] == pytest.approx(0.5)
    assert totals["idle"] == pytest.approx(0.25)
    assert sum(totals.values()) == pytest.approx(led.wall())
    assert set(totals) == set(BUCKETS)
    # step_complete folds compile into the step time (exclusive of the
    # nested assembly), fetch separately
    assert times == {"step_s": pytest.approx(2.0), "fetch_s": pytest.approx(1.0)}
    snap = led.snapshot()
    assert sum(snap["buckets"].values()) == pytest.approx(snap["wall_s"])


def test_ledger_step_bucket_flips_after_first_step():
    led = GoodputLedger(clock=Clock())
    assert led.step_bucket() == "compile"
    led.step_complete(0)
    assert led.step_bucket() == "step"


def test_ledger_rejects_unknown_and_idle_buckets():
    led = GoodputLedger(clock=Clock())
    with pytest.raises(ValueError, match="unknown ledger bucket"):
        with led.account("nonsense"):
            pass
    with pytest.raises(ValueError, match="unknown ledger bucket"):
        with led.account("idle"):  # idle is derived, never accounted
            pass


def test_ledger_goodput_badput_and_mfu():
    clk = Clock()
    reg = MetricRegistry()
    led = GoodputLedger(reg, clock=clk)
    led.set_workload(step_flops=1e9, peak_flops=1e10)
    with led.account("step"):
        clk.advance(3.0)
    led.step_complete(0)
    clk.advance(1.0)
    assert led.goodput_ratio() == pytest.approx(0.75)
    bad = led.badput()
    assert "step" not in bad and bad["idle"] == pytest.approx(1.0)
    # 1 step x 1e9 flops over 4 s wall = 0.25 GFLOP/s; peak 10 -> 2.5% MFU
    assert led.flops_per_sec() == pytest.approx(0.25e9)
    assert led.mfu() == pytest.approx(0.025)
    led.publish()
    assert reg.gauge("train_goodput_ratio").value == pytest.approx(0.75)
    assert reg.gauge("train_mfu").value == pytest.approx(0.025)
    assert reg.gauge("train_bucket_seconds", bucket="step").value \
        == pytest.approx(3.0)
    assert reg.gauge("train_badput_seconds", cause="idle").value \
        == pytest.approx(1.0)


def test_ledger_progress_watchdog():
    clk = Clock()
    led = GoodputLedger(clock=clk)
    # before the first step the grace window runs from ledger start
    assert led.health(10.0)["status"] == "ok"
    clk.advance(11.0)
    assert led.health(10.0)["status"] == "down"
    led.step_complete(0)
    h = led.health(10.0)
    assert h["status"] == "ok" and h["steps"] == 1
    clk.advance(10.5)
    assert led.health(10.0)["status"] == "down"


# --- chaos matrix: every fault lands in the right badput bucket ---------------


def _host_step(state, batch, rng=None):
    """Host-side stand-in for the jitted step: the supervisor only needs
    (state, metrics) with finite scalars — zero XLA compiles, so the
    matrix runs in milliseconds (the stubbed-seam stance of
    tests/test_chaos.py's serving scenarios)."""
    return (
        {"step": np.int32(int(state["step"]) + 1),
         "w": state["w"] + np.float32(0.5)},
        {"loss": np.float32(0.1), "grad_norm": np.float32(0.2)},
    )


def _fresh_state():
    return {"step": np.int32(0), "w": np.float32(1.0)}


def _telemetry(tmp_path, **detector_kwargs):
    reg = MetricRegistry()
    led = GoodputLedger(reg)
    rec = FlightRecorder(str(tmp_path / "flight"), registry=reg,
                         stats_fn=led.snapshot, min_interval_s=0)
    det = StragglerDetector(recorder=rec, registry=reg,
                            min_seconds=0.001, **detector_kwargs)
    return TrainTelemetry(ledger=led, detector=det, recorder=rec), reg


def _assert_invariant(ledger):
    """The REAL sums-to-wall check: the bucket sum against a live wall
    reading (snapshot's wall_s IS the bucket sum, so comparing those two
    would be tautological — a double-accounting bug inflates the sum
    past the true wall, which only this comparison catches)."""
    before = ledger.wall()
    snap = ledger.snapshot()
    after = ledger.wall()
    assert before > 0
    # the sum was taken between the two readings: held to BOTH, so a
    # process that is off the CPU between them cannot fail it
    assert 0.99 * before <= sum(snap["buckets"].values()) <= 1.01 * after
    return snap


def test_chaos_restart_books_restore_badput(tmp_path):
    tel, reg = _telemetry(tmp_path)
    injector = FaultPlan(
        faults=(Fault("step_exception", at=2),)).injector()
    state = run_resilient(
        with_fault_injection(_host_step, injector), _fresh_state(),
        lambda step: {"x": np.float32(step)}, steps=5,
        make_rng=lambda i: None, telemetry=tel, max_restarts=2,
    )
    assert int(state["step"]) == 5
    assert injector.exhausted()
    snap = _assert_invariant(tel.ledger)
    assert snap["buckets"]["restore"] > 0.0
    assert "restore" in tel.ledger.badput()
    assert reg.counter("train_steps_total").value == 5


def test_chaos_preemption_books_preempt_drain(tmp_path):
    from alphafold2_tpu.training import VerifiedCheckpointManager

    tel, _ = _telemetry(tmp_path)
    mgr = VerifiedCheckpointManager(str(tmp_path / "ckpt"),
                                    save_interval_steps=1)
    injector = FaultPlan(faults=(Fault("preempt", at=2),)).injector()
    handler = PreemptionHandler().install()
    injector.bind_preemption(handler)
    try:
        with pytest.raises(Preempted):
            run_resilient(
                with_fault_injection(_host_step, injector), _fresh_state(),
                lambda step: {"x": np.float32(step)}, steps=5,
                make_rng=lambda i: None, telemetry=tel, mgr=mgr,
                preemption=handler,
            )
    finally:
        handler.uninstall()
    snap = _assert_invariant(tel.ledger)
    assert snap["buckets"]["preempt"] > 0.0     # the final drain save
    assert snap["buckets"]["checkpoint"] > 0.0  # the per-step cadence saves


def test_chaos_ckpt_corruption_books_checkpoint_and_restore(tmp_path):
    from alphafold2_tpu.training import VerifiedCheckpointManager

    tel, _ = _telemetry(tmp_path)
    plan = FaultPlan(faults=(
        Fault("ckpt_corrupt", at=1, mode="truncate"),
        Fault("step_exception", at=3),
    ))
    injector = plan.injector()
    mgr = VerifiedCheckpointManager(str(tmp_path / "ckpt"),
                                    save_interval_steps=1,
                                    fault_hook=injector.checkpoint_hook())
    state = run_resilient(
        with_fault_injection(_host_step, injector), _fresh_state(),
        lambda step: {"x": np.float32(step)}, steps=5,
        make_rng=lambda i: None, telemetry=tel, mgr=mgr, max_restarts=2,
    )
    assert int(state["step"]) == 5
    assert injector.exhausted()
    snap = _assert_invariant(tel.ledger)
    # saves (and the sha256 verify) book as checkpoint badput; the
    # recovery from the corrupted step's fallback books as restore
    assert snap["buckets"]["checkpoint"] > 0.0
    assert snap["buckets"]["restore"] > 0.0


def test_chaos_slow_data_books_data_stall_and_pages(tmp_path):
    tel, reg = _telemetry(tmp_path, patience=2, stall_fraction=0.5)
    plan = FaultPlan(faults=(
        Fault("slow_data", at=1, count=4, delay_s=0.05),))
    injector = plan.injector()
    fetch = resilient_batches(lambda step: {"x": np.float32(step)},
                              injector=injector)
    run_resilient(
        with_fault_injection(_host_step, injector), _fresh_state(),
        fetch, steps=6, make_rng=lambda i: None, telemetry=tel,
    )
    assert injector.exhausted()
    snap = _assert_invariant(tel.ledger)
    assert snap["buckets"]["data_fetch"] >= 0.15  # 4 x 0.05 s sleeps
    bundles = tel.recorder.snapshot()["bundles"]
    assert any("train_data_stall" in b for b in bundles), bundles
    assert reg.counter(
        "train_incidents_total", kind="train_data_stall").value >= 1


# --- straggler detection ------------------------------------------------------


def _pod_rows(step_s, fetch_s):
    return [{"process": i, "step_s": s, "fetch_s": f}
            for i, (s, f) in enumerate(zip(step_s, fetch_s))]


def test_straggler_detector_two_process_shaped(tmp_path):
    rec = FlightRecorder(str(tmp_path), min_interval_s=0)
    reg = MetricRegistry()
    det = StragglerDetector(recorder=rec, registry=reg,
                            skew_threshold=2.0, patience=3,
                            min_seconds=0.001)
    # two healthy steps, then process 1 goes 5x slow for patience steps
    for step in range(2):
        det.observe_pod(step, _pod_rows([0.1, 0.11], [0.01, 0.01]))
    assert rec.snapshot()["bundles"] == []
    for step in range(2, 5):
        det.observe_pod(step, _pod_rows([0.1, 0.5], [0.01, 0.01]))
    bundles = rec.snapshot()["bundles"]
    assert len([b for b in bundles if "train_straggler" in b]) == 1
    assert reg.gauge("train_step_time_skew").value == pytest.approx(5.0)
    # fires ONCE per streak: further bad steps do not re-bundle
    det.observe_pod(5, _pod_rows([0.1, 0.5], [0.01, 0.01]))
    assert len(rec.snapshot()["bundles"]) == len(bundles)
    # recovery re-arms: a new streak fires a new incident
    for step in range(6, 8):
        det.observe_pod(step, _pod_rows([0.1, 0.1], [0.01, 0.01]))
    for step in range(8, 11):
        det.observe_pod(step, _pod_rows([0.1, 0.5], [0.01, 0.01]))
    assert len([b for b in rec.snapshot()["bundles"]
                if "train_straggler" in b]) == 2


def test_straggler_detector_fetch_skew_pages_data_stall(tmp_path):
    rec = FlightRecorder(str(tmp_path), min_interval_s=0)
    det = StragglerDetector(recorder=rec, registry=MetricRegistry(),
                            skew_threshold=2.0, patience=2,
                            min_seconds=0.001)
    for step in range(3):
        det.observe_pod(step, _pod_rows([0.1, 0.1], [0.01, 0.2]))
    assert any("train_data_stall" in b
               for b in rec.snapshot()["bundles"])


def test_straggler_detector_ignores_sub_noise_medians(tmp_path):
    rec = FlightRecorder(str(tmp_path), min_interval_s=0)
    det = StragglerDetector(recorder=rec, registry=MetricRegistry(),
                            patience=1, min_seconds=0.01)
    # huge relative skew but microsecond absolute times: not a straggler
    for step in range(3):
        det.observe_pod(step, _pod_rows([1e-5, 1e-3], [1e-6, 1e-6]))
    assert rec.snapshot()["bundles"] == []


def test_detector_rejects_bad_thresholds():
    with pytest.raises(ValueError, match="skew_threshold"):
        StragglerDetector(skew_threshold=0.5)
    with pytest.raises(ValueError, match="stall_fraction"):
        StragglerDetector(stall_fraction=1.5)
    with pytest.raises(ValueError, match="patience"):
        StragglerDetector(patience=0)


# --- federation ---------------------------------------------------------------


def _paired_federations(reg0, reg1, led0=None, led1=None, every=1):
    """Two MetricFederations wired through an in-memory 2-process gather
    (each side's tick stores its payload; the gather returns both)."""
    store = {}

    def gather_for(i):
        def gather(payload):
            store[i] = payload
            return [store.get(0, payload), store.get(1, payload)]

        return gather

    f0 = MetricFederation(reg0, ledger=led0, process_index=0, every=every,
                          gather_fn=gather_for(0))
    f1 = MetricFederation(reg1, ledger=led1, process_index=1, every=every,
                          gather_fn=gather_for(1))
    return f0, f1


def test_federated_view_serves_both_process_labels():
    reg0, reg1 = MetricRegistry(), MetricRegistry()
    reg0.gauge("train_goodput_ratio").set(0.8)
    reg0.histogram("train_step_seconds").observe(0.1)
    reg1.gauge("train_goodput_ratio").set(0.4)
    reg1.histogram("train_step_seconds").observe(0.3)
    f0, f1 = _paired_federations(reg0, reg1)
    f1.tick(0)
    rows = f0.tick(0)
    assert [r["process"] for r in rows] == [0, 1]
    text = FederatedRegistryView(reg0, f0).to_prometheus()
    parsed = parse_prometheus_text(text)
    for family in ("train_goodput_ratio", "train_step_seconds_count"):
        procs = {dict(labels).get("process")
                 for name, labels in parsed if name == family}
        assert procs == {"0", "1"}, (family, procs)
    # the local side is served LIVE, not from the gathered copy
    reg0.gauge("train_goodput_ratio").set(0.9)
    parsed = parse_prometheus_text(
        FederatedRegistryView(reg0, f0).to_prometheus())
    assert parsed[("train_goodput_ratio", (("process", "0"),))] == 0.9


def test_federation_carries_ledger_step_times():
    clk = Clock()
    reg0, reg1 = MetricRegistry(), MetricRegistry()
    led0 = GoodputLedger(reg0, clock=clk, process_index=0)
    led1 = GoodputLedger(reg1, clock=clk, process_index=1)
    with led1.account("data_fetch"):
        clk.advance(0.4)
    with led1.account("step"):
        clk.advance(0.1)
    led1.step_complete(0)
    f0, f1 = _paired_federations(reg0, reg1, led0, led1)
    f1.tick(0)
    rows = f0.tick(0)
    assert rows[1]["fetch_s"] == pytest.approx(0.4)
    assert rows[1]["step_s"] == pytest.approx(0.1)
    assert f0.snapshot()["processes"] == [0, 1]


def test_federation_cadence_and_validation():
    fed = MetricFederation(MetricRegistry(), process_index=0, every=5,
                           gather_fn=lambda b: [b])
    assert fed.due(0) and fed.due(10) and not fed.due(3)
    with pytest.raises(ValueError, match="every"):
        MetricFederation(MetricRegistry(), process_index=0, every=0,
                         gather_fn=lambda b: [b])


def test_relabeled_exposition_roundtrip():
    reg = MetricRegistry()
    reg.counter("x_total", reason="a b").inc(3)
    reg.histogram("y_seconds").observe(1.0)
    out = parse_prometheus_text(
        relabeled_exposition(reg.to_prometheus(), process=2))
    assert out[("x_total", (("process", "2"), ("reason", "a b")))] == 3.0
    assert ("y_seconds_count", (("process", "2"),)) in out
    assert not any(line.startswith("#") for line in
                   relabeled_exposition(reg.to_prometheus(),
                                        process=2).splitlines())


# --- trainer ops plane --------------------------------------------------------


def test_trainer_healthz_503_on_stalled_step(tmp_path):
    clk = Clock()
    reg = MetricRegistry()
    led = GoodputLedger(reg, clock=clk)
    tel = TrainTelemetry(ledger=led)
    ops = OpsServer(registry=reg,
                    health_fn=lambda: tel.health(horizon_s=30.0),
                    stats_fn=tel.statusz)
    with ops:
        with led.account("step"):
            clk.advance(0.5)
        led.step_complete(0)
        with urllib.request.urlopen(ops.url + "/healthz") as r:
            assert r.status == 200
            assert json.loads(r.read())["status"] == "ok"
        clk.advance(31.0)  # no step within the horizon -> 503
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(ops.url + "/healthz")
        assert err.value.code == 503
        assert json.loads(err.value.read())["status"] == "down"
        statusz = json.loads(
            urllib.request.urlopen(ops.url + "/statusz").read())
        assert statusz["stats"]["goodput"]["steps"] == 1


def test_build_train_telemetry_null_when_disabled():
    import argparse

    from alphafold2_tpu.telemetry import (
        add_observability_args,
        build_train_telemetry,
    )

    ap = argparse.ArgumentParser()
    add_observability_args(ap)
    args = ap.parse_args([])
    tel = build_train_telemetry(
        args, registry=MetricRegistry(enabled=False),
        process_index=0, process_count=1)
    assert tel is NULL_TRAIN_TELEMETRY
    # the null bundle's hooks are no-ops end to end
    with tel.account("data_fetch"):
        pass
    tel.step_complete(0)
    tel.close()


def test_build_train_telemetry_full_plane(tmp_path):
    import argparse

    from alphafold2_tpu.telemetry import (
        add_observability_args,
        build_train_telemetry,
    )

    ap = argparse.ArgumentParser()
    add_observability_args(ap)
    port_file = str(tmp_path / "port")
    args = ap.parse_args([
        "--ops-port", "0", "--ops-port-file", port_file,
        "--flight-dir", str(tmp_path / "flight"),
        "--progress-horizon-s", "60", "--peak-tflops", "100",
    ])
    reg = MetricRegistry(enabled=True)
    tel = build_train_telemetry(args, registry=reg, step_flops=2e9,
                                process_index=0, process_count=1)
    try:
        assert tel.ops is not None and tel.recorder is not None
        assert tel.federation is None  # single-process: nothing to gather
        with open(port_file) as fh:
            assert int(fh.read()) == tel.ops.port
        with tel.account(tel.step_bucket()):
            time.sleep(0.01)
        tel.step_complete(0)
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{tel.ops.port}/metrics").read().decode()
        parsed = parse_prometheus_text(text)
        assert parsed[("train_steps_total", ())] == 1.0
        assert ("train_mfu", ()) in parsed  # peak declared -> MFU gauge
    finally:
        tel.close()
    tel.close()  # idempotent


def test_build_train_telemetry_pod_paths_do_not_collide(tmp_path):
    """On a pod every process arms its own recorder/plane: flight
    bundles land in per-process subdirectories (same-named bundles on
    shared storage must not overwrite each other) and only process 0 —
    the federated view — writes the ops-port file."""
    import argparse

    from alphafold2_tpu.telemetry import (
        add_observability_args,
        build_train_telemetry,
    )

    ap = argparse.ArgumentParser()
    add_observability_args(ap)
    port_file = str(tmp_path / "port")
    argv = ["--ops-port", "0", "--ops-port-file", port_file,
            "--flight-dir", str(tmp_path / "flight")]
    tels = [
        build_train_telemetry(
            ap.parse_args(argv), registry=MetricRegistry(enabled=True),
            process_index=pid, process_count=2)
        for pid in range(2)
    ]
    try:
        dirs = {t.recorder.out_dir for t in tels}
        assert len(dirs) == 2
        assert all(d.endswith(("p0", "p1")) for d in dirs), dirs
        assert tels[0].federation is not None
        with open(port_file) as fh:  # process 0's port, not a race
            assert int(fh.read()) == tels[0].ops.port
        assert tels[1].ops is not None  # rank 1 still has a local plane
    finally:
        for t in tels:
            t.close()


# --- loss-curve gate ----------------------------------------------------------

CONV = os.path.join(DATA, "losscurve_converging.jsonl")
DIV = os.path.join(DATA, "losscurve_diverging.jsonl")


def test_loss_curve_fixture_pass_and_fail():
    from alphafold2_tpu.telemetry.check import main

    assert main(["--loss-curve", "--current", CONV,
                 "--baseline", CONV]) == 0
    assert main(["--loss-curve", "--current", DIV,
                 "--baseline", CONV]) == 1


def test_load_loss_curve_metrics():
    from alphafold2_tpu.telemetry.check import load_loss_curve

    conv = load_loss_curve(CONV)
    div = load_loss_curve(DIV)
    assert conv["points_count"] == 120  # event records skipped
    assert conv["loss_slope"] < 0      # still improving at the end
    assert div["loss_slope"] > 0       # diverging
    assert conv["loss_trend"] < 1.0    # the GATED slope signal
    assert div["loss_trend"] > 1.1
    assert div["loss_final"] > conv["loss_final"] * 1.5
    assert conv["loss_best"] <= conv["loss_final"]


def test_load_loss_curve_rejects_empty(tmp_path):
    from alphafold2_tpu.telemetry.check import load_loss_curve

    p = tmp_path / "empty.jsonl"
    p.write_text('{"step": 0, "event": "restart"}\n')
    with pytest.raises(ValueError, match="at least 3"):
        load_loss_curve(str(p))


def test_loss_curve_rejects_bad_window():
    from alphafold2_tpu.telemetry.check import load_loss_curve, main

    with pytest.raises(ValueError, match="window"):
        load_loss_curve(CONV, window=0)
    with pytest.raises(ValueError, match="window"):
        load_loss_curve(CONV, window=-2)
    # the CLI maps it to the documented usage-error exit code, no traceback
    assert main(["--loss-curve", "--loss-window", "0",
                 "--current", CONV, "--baseline", CONV]) == 2


def test_loss_curve_custom_key_and_window(tmp_path):
    from alphafold2_tpu.telemetry.check import load_loss_curve

    p = tmp_path / "m.jsonl"
    with open(p, "w") as fh:
        for i in range(20):
            fh.write(json.dumps({"step": i, "eval_loss": 2.0 - 0.05 * i})
                     + "\n")
    out = load_loss_curve(str(p), key="eval_loss", window=5, smooth=0.0)
    assert out["loss_slope"] == pytest.approx(-0.05)
    assert out["loss_final"] == pytest.approx(2.0 - 0.05 * 17)
    # trend = window end / window start: (2 - .05*19) / (2 - .05*15)
    assert out["loss_trend"] == pytest.approx(1.05 / 1.25)
    # the raw slope is reported but deliberately ungated
    from alphafold2_tpu.telemetry.check import rule_for

    assert rule_for("loss_slope") == ("ignore", 0.0)
    assert rule_for("loss_trend") == ("lower", 0.10)
    # incident VOLUME counters stay informational even though their
    # labels contain "stall" — run length, not speed
    assert rule_for(
        'counters.train_incidents_total{kind="train_data_stall"}'
    ) == ("ignore", 0.0)
    assert rule_for("train_goodput.data_stall_badput_s") == ("lower", 0.25)


# --- per-process metrics sidecars --------------------------------------------


def test_per_process_metrics_path():
    from alphafold2_tpu.telemetry import per_process_metrics_path

    assert per_process_metrics_path("m.jsonl", 0) == "m.jsonl"
    assert per_process_metrics_path("m.jsonl", 2) == "m.p2.jsonl"
    assert per_process_metrics_path("/a/b/run.jsonl", 1) == "/a/b/run.p1.jsonl"


def test_metrics_logger_process_index_and_tail(tmp_path):
    from alphafold2_tpu.telemetry import MetricsLogger

    path = str(tmp_path / "m.jsonl")
    logger = MetricsLogger(path, process_index=1, tail_window=3)
    for step in range(5):
        logger.log(step, {"loss": 1.0 - 0.1 * step})
    logger.event(5, "restart", error="X")
    logger.close()
    records = [json.loads(line) for line in open(path)]
    assert all(r["process_index"] == 1 for r in records)
    tail = logger.tail()
    assert [r["step"] for r in tail] == [2, 3, 4]  # bounded ring
    assert logger.tail(1)[0]["step"] == 4
    assert all("event" not in r for r in tail)  # scalar records only


def test_metrics_logger_no_process_index_by_default(tmp_path):
    from alphafold2_tpu.telemetry import MetricsLogger

    path = str(tmp_path / "m.jsonl")
    logger = MetricsLogger(path)
    logger.log(0, {"loss": 1.0})
    logger.close()
    assert "process_index" not in json.loads(open(path).read())


# --- run_resilient integration ------------------------------------------------


def test_run_resilient_counts_steps_and_compile_bucket(tmp_path):
    tel, reg = _telemetry(tmp_path)
    run_resilient(
        _host_step, _fresh_state(), lambda step: {"x": np.float32(step)},
        steps=3, make_rng=lambda i: None, telemetry=tel,
    )
    assert reg.counter("train_steps_total").value == 3
    hist = reg.histogram("train_step_seconds")
    assert hist.snapshot()["count"] == 3
    totals = tel.ledger.totals()
    # the first step books as compile, the rest as step
    assert totals["compile"] > 0.0
    assert tel.ledger.step_bucket() == "step"


# --- the 2-process acceptance run (slow) --------------------------------------

POD_WORKER = r"""
import json
import os
import urllib.request

import numpy as np

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

from alphafold2_tpu.parallel.distributed import initialize_from_env

assert initialize_from_env(), "coordinator env not picked up"
assert jax.process_count() == 2

from alphafold2_tpu.models import Alphafold2Config
from alphafold2_tpu.parallel import make_multihost_train_step
from alphafold2_tpu.parallel.sharding import host_to_global
from alphafold2_tpu.reliability import Fault, FaultPlan
from alphafold2_tpu.telemetry import MetricRegistry
from alphafold2_tpu.telemetry.goodput import (
    FederatedRegistryView,
    GoodputLedger,
    MetricFederation,
    StragglerDetector,
    TrainTelemetry,
)
from alphafold2_tpu.telemetry.ops_plane import FlightRecorder, OpsServer
from alphafold2_tpu.telemetry.registry import parse_prometheus_text
from alphafold2_tpu.training import (
    DataConfig,
    TrainConfig,
    per_process_microbatch_fn,
    resilient_batches,
    run_resilient,
)
from alphafold2_tpu.training.harness import train_state_init

pid = jax.process_index()
cfg = Alphafold2Config(dim=16, depth=1, heads=2, dim_head=8, max_seq_len=16)
tcfg = TrainConfig(learning_rate=1e-3, grad_accum=1)
dcfg = DataConfig(batch_size=8, max_len=8, seed=0)  # GLOBAL batch

registry = MetricRegistry()
ledger = GoodputLedger(registry, process_index=pid)
recorder = FlightRecorder(os.environ["AF2_TEST_FLIGHT"] + f"/p{pid}",
                          registry=registry, stats_fn=ledger.snapshot,
                          min_interval_s=0)
detector = StragglerDetector(recorder=recorder, registry=registry,
                             skew_threshold=2.0, patience=2,
                             min_seconds=0.01)
federation = MetricFederation(registry, ledger=ledger,
                              process_index=pid, every=1)
telemetry = TrainTelemetry(ledger=ledger, federation=federation,
                           detector=detector, recorder=recorder)

# slow-data fault on PROCESS 1 only: its fetch stalls 0.2 s/step while
# process 0 stays fast — the straggler detector on process 0 must see
# the fetch-time skew in the federated rows and page train_data_stall
injector = None
if pid == 1:
    injector = FaultPlan(faults=(
        Fault("slow_data", at=1, count=3, delay_s=0.2),)).injector()
fetch = resilient_batches(per_process_microbatch_fn(dcfg, tcfg.grad_accum),
                          injector=injector)

step_fn, st_shardings, assemble, mesh = make_multihost_train_step(
    cfg, tcfg, fetch(0), tp=False, donate_state=False,
    telemetry=telemetry,
)
state = host_to_global(
    train_state_init(jax.random.PRNGKey(0), cfg, tcfg), st_shardings)


def pod_step(st, batch, rng=None):
    return step_fn(st, assemble(batch), rng)


ops = None
if pid == 0:
    ops = OpsServer(
        registry=FederatedRegistryView(registry, federation),
        health_fn=lambda: telemetry.health(600.0),
        stats_fn=telemetry.statusz)
    ops.start()

state = run_resilient(
    pod_step, state, fetch, steps=4, make_rng=lambda i: None,
    telemetry=telemetry,
)
if injector is not None:
    assert injector.exhausted(), "slow_data plan never delivered"

snap = ledger.snapshot()
live_wall = ledger.wall()  # NOT snap["wall_s"] (that IS the bucket sum):
# only a live reading catches double-accounting inflating the sum
assert abs(sum(snap["buckets"].values()) - live_wall) \
    <= 0.01 * live_wall, (snap, live_wall)

result = {"process": pid, "goodput": snap["goodput_ratio"],
          "data_fetch_s": snap["buckets"]["data_fetch"],
          "steps": snap["steps"]}
if pid == 0:
    text = urllib.request.urlopen(ops.url + "/metrics").read().decode()
    parsed = parse_prometheus_text(text)
    for family in ("train_step_seconds_count", "train_fetch_seconds_count"):
        procs = {dict(labels).get("process")
                 for name, labels in parsed if name == family}
        assert procs == {"0", "1"}, (family, procs)
    result["scrape_ok"] = True
    bundles = recorder.snapshot()["bundles"]
    assert any("train_data_stall" in b for b in bundles), bundles
    result["stall_incident"] = True
    with urllib.request.urlopen(ops.url + "/healthz") as r:
        assert r.status == 200
    ops.stop()
print("RESULT " + json.dumps(result), flush=True)
"""


def _pod_env(extra, **pod_kwargs):
    from alphafold2_tpu.parallel.distributed import cpu_pod_env

    return cpu_pod_env(
        repo_path=REPO,
        extra={"JAX_DISABLE_MOST_OPTIMIZATIONS": "true", **extra},
        **pod_kwargs,
    )


@pytest.mark.slow
def test_two_process_federated_metrics_and_data_stall(tmp_path):
    """THE PR 12 acceptance bar: on a real 2-process CPU pod run,
    process 0's /metrics exposes per-process step-time and fetch-time
    families for BOTH processes, every ledger's buckets sum to wall
    within 1%, and a slow-data fault injected on process 1 books as
    data-stall badput there AND pages a train_data_stall incident on
    process 0 (via the federated fetch-time skew)."""
    from alphafold2_tpu.parallel.distributed import free_local_port

    port = free_local_port()
    flight = str(tmp_path / "flight")
    procs = []
    for pid in range(2):
        env = _pod_env(
            {"AF2_TEST_FLIGHT": flight},
            coordinator=f"127.0.0.1:{port}",
            num_processes=2,
            process_id=pid,
        )
        procs.append(subprocess.Popen(
            [sys.executable, "-c", POD_WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        ))
    outs = [p.communicate(timeout=600)[0] for p in procs]
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out}"
    results = {}
    for out in outs:
        for line in reversed(out.strip().splitlines()):
            if line.startswith("RESULT "):
                rec = json.loads(line[len("RESULT "):])
                results[rec["process"]] = rec
                break
        else:
            raise AssertionError(f"no RESULT line:\n{out}")
    assert results[0]["scrape_ok"] and results[0]["stall_incident"]
    assert results[0]["steps"] == 4 and results[1]["steps"] == 4
    # the stalled process's fetch badput carries the injected 3 x 0.2 s
    assert results[1]["data_fetch_s"] >= 0.5
    assert results[1]["data_fetch_s"] > results[0]["data_fetch_s"]
