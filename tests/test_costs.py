"""Serving cost & profiling plane tests (ISSUE 15, tier-1, CPU).

Unit matrix over `telemetry/costs.py` (cost-ledger algebra incl. int8 +
SP cells, serve-goodput accounting, the exemplar flight book), the ops
plane's `/explainz` + `/profilez` endpoints, the headroom-driven
autoscaler up-trigger (clock-injected, no sleeps), and the chaos
acceptance: a REAL two-replica fleet under a kill_replica plan whose
requeued request's whole flight path reconstructs by trace_id over live
HTTP, with every replica's goodput buckets summing to its wall clock
within 1%.
"""

import json
import glob
import os
import time
import urllib.request

import jax
import numpy as np
import pytest

from alphafold2_tpu.constants import AA_ORDER
from alphafold2_tpu.models import Alphafold2Config, alphafold2_init
from alphafold2_tpu.reliability import Fault, FaultPlan
from alphafold2_tpu.serving import (
    FleetConfig,
    ReplicaAutoscaler,
    ScalePolicy,
    ServingConfig,
    ServingEngine,
    ServingFleet,
)
from alphafold2_tpu.telemetry import (
    MetricRegistry,
    OpsServer,
    ProfileBusyError,
    ProfileCapturer,
    ProfileRateLimitedError,
    Tracer,
)
from alphafold2_tpu.telemetry.costs import (
    ExecutableCostLedger,
    FlightBook,
    ServeGoodputLedger,
)

TINY = Alphafold2Config(dim=16, depth=1, heads=2, dim_head=8, max_seq_len=16)


@pytest.fixture(scope="module")
def tiny_params():
    return alphafold2_init(jax.random.PRNGKey(0), TINY)


def seq_of(length, offset=0):
    return "".join(
        AA_ORDER[(offset + i) % len(AA_ORDER)] for i in range(length)
    )


class FakeEngine(ServingEngine):
    """Model call stubbed at the documented seam (test_serving stance)."""

    def _call_executable(self, bucket, tokens, mask, msa=None, msa_mask=None):
        B, Lb = tokens.shape
        return {
            "coords": np.zeros((B, Lb, 3), np.float32),
            "confidence": np.full((B, Lb), 0.5, np.float32),
            "stress": np.zeros((B,), np.float32),
        }


# ----------------------------------------------------- cost-ledger algebra


def test_cost_cell_join_int8_and_sp_cells():
    """The analytic x measured join: chip-seconds-per-request and MFU
    derive exactly from (EMA device-seconds, EMA requests, chips,
    forward FLOPs) — on a dense int8 cell and an 8-chip SP cell."""
    led = ExecutableCostLedger(MetricRegistry())
    led.set_peak(1e12)
    k_int8 = led.register_cell(
        pool="short", bucket=256, schedule="dense", backend_arm="xla_ref",
        weight_dtype="int8", forward_flops=2e9,
        residency_bytes=1 << 28, max_batch=4)
    k_sp = led.register_cell(
        pool="long", bucket=1024, schedule="sp_seq",
        backend_arm="pallas_tpu", weight_dtype="f32", forward_flops=8e10,
        residency_bytes=1 << 30, chips=8, max_batch=2)
    led.observe_batch(k_int8, device_seconds=0.1, requests=4)
    led.observe_batch(k_sp, device_seconds=1.0, requests=2)
    rows = {(c["pool"], c["bucket"]): c for c in led.cells()}
    short = rows[("short", 256)]
    assert short["weight_dtype"] == "int8"
    assert short["chip_seconds_per_request"] == pytest.approx(0.1 / 4)
    # achieved FLOP/s per chip = 4 req x 2e9 / 0.1s; MFU against 1e12
    assert short["mfu"] == pytest.approx((4 * 2e9 / 0.1) / 1e12)
    long_ = rows[("long", 1024)]
    # the SP executable bills ALL 8 chips: 1.0s x 8 / 2 requests
    assert long_["chip_seconds_per_request"] == pytest.approx(4.0)
    assert long_["flops_per_sec_per_chip"] == pytest.approx(
        2 * 8e10 / (1.0 * 8))
    # unmeasured cells carry the analytic columns but no derived price
    k_cold = led.register_cell(
        pool="short", bucket=512, schedule="dense", backend_arm="xla_ref",
        weight_dtype="int8", forward_flops=1e10, residency_bytes=1)
    cold = {(c["pool"], c["bucket"]): c for c in led.cells()}[
        ("short", 512)]
    assert cold["chip_seconds_per_request"] is None
    assert cold["forward_flops"] == 1e10
    assert k_cold != k_int8


def test_cost_ledger_ema_and_registration_idempotent():
    led = ExecutableCostLedger()
    k = led.register_cell(
        pool="p", bucket=8, schedule="dense", backend_arm="xla_ref",
        weight_dtype="f32", forward_flops=1e6, residency_bytes=10)
    led.observe_batch(k, device_seconds=1.0, requests=2)
    led.observe_batch(k, device_seconds=3.0, requests=4)
    cell = led.cells()[0]
    # EMA alpha 0.25: 0.25*3 + 0.75*1 = 1.5; 0.25*4 + 0.75*2 = 2.5
    assert cell["ema_batch_seconds"] == pytest.approx(1.5)
    assert cell["ema_batch_requests"] == pytest.approx(2.5)
    assert cell["batches"] == 2 and cell["requests"] == 6
    # re-registration refreshes analytics, keeps the measured columns
    k2 = led.register_cell(
        pool="p", bucket=8, schedule="dense", backend_arm="xla_ref",
        weight_dtype="f32", forward_flops=2e6, residency_bytes=20)
    assert k2 == k
    cell = led.cells()[0]
    assert cell["forward_flops"] == 2e6 and cell["batches"] == 2
    # an unknown key auto-registers (custom engine_factory path)
    led.observe_batch(("q", 16, "dense", "xla_ref", "f32"),
                      device_seconds=0.5, requests=1)
    assert led.pool_rate_rps("q") == pytest.approx(2.0)


def test_cost_ledger_publish_counter_grows_monotonically():
    reg = MetricRegistry()
    led = ExecutableCostLedger(reg)
    k = led.register_cell(
        pool="p", bucket=8, schedule="dense", backend_arm="xla_ref",
        weight_dtype="f32", forward_flops=1.0, residency_bytes=1)
    led.observe_batch(k, device_seconds=0.1, requests=3)
    led.publish()
    led.publish()  # re-publish must not double the volume counter
    led.observe_batch(k, device_seconds=0.1, requests=2)
    led.publish()
    counters = reg.snapshot()["counters"]
    (name,) = [n for n in counters if n.startswith("serve_cell_requests")]
    assert counters[name] == 5


def test_pool_rate_none_until_measured():
    led = ExecutableCostLedger()
    led.register_cell(
        pool="p", bucket=8, schedule="dense", backend_arm="xla_ref",
        weight_dtype="f32", forward_flops=1.0, residency_bytes=1)
    assert led.pool_rate_rps("p") is None  # registered but unmeasured
    assert led.pool_rate_rps("ghost") is None


# --------------------------------------------------- serve-goodput ledger


def test_goodput_totals_sum_to_wall_with_idle_remainder():
    clk = [0.0]
    led = ServeGoodputLedger(clock=lambda: clk[0])
    led.register("r0", "short")
    led.add("r0", "execute", 2.0)
    led.add("r0", "compile", 1.0)
    clk[0] = 10.0
    totals = led.totals("r0")
    assert totals["idle"] == pytest.approx(7.0)
    assert sum(totals.values()) == pytest.approx(led.wall("r0"))
    snap = led.snapshot()["replicas"]["r0"]
    assert snap["goodput_ratio"] == pytest.approx(0.2)
    assert snap["badput_s"]["compile"] == pytest.approx(1.0)
    with pytest.raises(ValueError, match="unknown serve-goodput cause"):
        led.add("r0", "idle", 1.0)
    with pytest.raises(ValueError, match="unknown serve-goodput cause"):
        led.add("r0", "nonsense", 1.0)


def test_goodput_probe_span_subtracts_inner_accounting():
    """A probe round trip that triggered engine-side accounting (its own
    execute — and on a reinstatement probe, a multi-second compile) must
    bill probe only the DIFFERENCE, or sums-to-wall breaks on the first
    reprobe."""
    clk = [0.0]
    led = ServeGoodputLedger(clock=lambda: clk[0])
    led.register("r0", "p")
    with led.probe_span("r0"):
        clk[0] += 5.0
        led.add("r0", "compile", 3.0)   # what the engine accounted inside
        led.add("r0", "execute", 1.0)
    totals = led.totals("r0")
    assert totals["probe"] == pytest.approx(1.0)  # 5 - (3 + 1)
    assert sum(totals.values()) == pytest.approx(led.wall("r0"))


def test_goodput_register_idempotent_and_pool_aggregate():
    clk = [0.0]
    reg = MetricRegistry()
    led = ServeGoodputLedger(reg, clock=lambda: clk[0])
    led.register("r0", "p")
    clk[0] = 4.0
    led.register("r0", "p")  # restart behind the same name: clock kept
    led.add("r0", "execute", 1.0)
    led.register("r1", "p")
    led.add("r1", "execute", 2.0)
    clk[0] = 10.0
    snap = led.snapshot()
    assert snap["replicas"]["r0"]["wall_s"] == pytest.approx(10.0)
    # pool aggregate: (1 + 2) execute over (10 + 6) wall
    assert snap["pools"]["p"]["goodput_ratio"] == pytest.approx(3.0 / 16.0)
    led.publish()
    gauges = reg.snapshot()["gauges"]
    assert gauges['serve_pool_goodput_ratio{pool="p"}'] == pytest.approx(
        3.0 / 16.0)
    assert gauges['serve_badput_seconds{cause="idle",pool="p",'
                  'replica="r0"}'] == pytest.approx(9.0)


# ----------------------------------------------------------- flight book


def test_flight_book_lifecycle_and_eviction():
    clk = [100.0]
    book = FlightBook(capacity=3, clock=lambda: clk[0])
    book.begin("t1", pool="short", length=12)
    book.note("t1", "dispatch", replica="r0")
    book.finish("t1", "completed", replica="r0", latency_s=0.5)
    rec = book.get("t1")
    assert rec["outcome"] == "completed" and rec["pool"] == "short"
    assert [e["event"] for e in rec["events"]] == [
        "submitted", "dispatch", "terminal"]
    # a reader's copy must not alias the live events list
    rec["events"].append({"event": "tamper"})
    assert [e["event"] for e in book.get("t1")["events"]][-1] == "terminal"
    for i in range(2, 6):
        book.begin(f"t{i}")
    assert book.get("t1") is None           # evicted wholesale
    assert book.snapshot() == {"records": 3, "capacity": 3, "evicted": 2}
    assert book.recent() == ["t3", "t4", "t5"]
    # late events for evicted/unknown ids are dropped, never an error
    book.note("t1", "ghost")
    book.finish("ghost", "completed")
    # a resubmitted id keeps ONE record and notes the re-entry
    book.begin("t5", length=9)
    assert [e["event"] for e in book.get("t5")["events"]] == [
        "submitted", "resubmitted"]
    with pytest.raises(ValueError):
        FlightBook(capacity=0)


# ------------------------------------------------ engine-level integration


def test_fake_engine_registers_cells_and_feeds_measured_columns():
    eng = FakeEngine({}, TINY, ServingConfig(
        buckets=(8, 16), max_batch=2, max_wait_s=0.0, cache_capacity=0))
    try:
        eng.predict(seq_of(6))
        cells = {(c["pool"], c["bucket"]): c
                 for c in eng.stats()["costs"]["cells"]}
        assert set(cells) == {("default", 8), ("default", 16)}
        served = cells[("default", 8)]
        assert served["schedule"] == "dense"
        assert served["weight_dtype"] == "f32"
        assert served["requests"] == 1
        assert served["chip_seconds_per_request"] is not None
        assert served["forward_flops"] > 0
        assert served["residency_bytes"] > 0  # streams priced even w/o params
        assert cells[("default", 16)]["requests"] == 0
        assert eng.cell_for(8)["bucket"] == 8
        assert eng.cell_for(999) == {}
        gp = eng.stats()["serve_goodput"]["replicas"]["engine"]
        assert gp["buckets"]["execute"] > 0
    finally:
        eng.shutdown(timeout=10)


def test_real_engine_excludes_compile_from_execute_ema(tiny_params):
    """The first batch of a bucket carries its AOT compile; the cost
    EMA must price EXECUTION. The compile and the execution are disjoint
    stretches of the one predict() call, so an EMA that leaves the
    compile out fits beside it inside the call's wall time whatever the
    machine's load (with a warm compile cache the "compile" is a 0.2 s
    cache load, only four times the forward: a ratio between the two
    raced the clock under xdist). One that included it would count the
    compile twice and overrun the call by the compile's length."""
    eng = ServingEngine(tiny_params, TINY, ServingConfig(
        buckets=(8,), max_batch=1, max_wait_s=0.0, mds_iters=2,
        cache_capacity=0))
    try:
        t0 = time.monotonic()
        eng.predict(seq_of(5))
        wall = time.monotonic() - t0
        compile_s = eng.metrics.compile_seconds_total()
        assert compile_s > 0
        cell = eng.stats()["costs"]["cells"][0]
        assert cell["requests"] == 1
        assert cell["ema_batch_seconds"] + compile_s <= wall
        gp = eng.stats()["serve_goodput"]["replicas"]["engine"]["buckets"]
        # the ledger's compile stretch encloses the tracker's
        assert gp["compile"] >= 0.9 * compile_s
        assert gp["compile"] + gp["execute"] <= wall
    finally:
        eng.shutdown(timeout=30)


def test_engine_failed_dispatch_bills_requeue_not_execute():
    calls = {"n": 0}

    class Poison(FakeEngine):
        def _call_executable(self, bucket, tokens, mask, msa=None,
                             msa_mask=None):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("boom")
            return super()._call_executable(bucket, tokens, mask, msa,
                                            msa_mask)

    eng = Poison({}, TINY, ServingConfig(
        buckets=(8,), max_batch=1, max_wait_s=0.0, cache_capacity=0))
    try:
        with pytest.raises(Exception):
            eng.predict(seq_of(5))
        eng.predict(seq_of(6))
        gp = eng.stats()["serve_goodput"]["replicas"]["engine"]["buckets"]
        assert gp["requeue"] > 0     # the burned failed-dispatch time
        assert gp["execute"] > 0     # the successful one
        cell = eng.stats()["costs"]["cells"][0]
        assert cell["requests"] == 1  # only the SUCCESS fed the cost EMA
    finally:
        eng.shutdown(timeout=10)


# --------------------------------------------------- /explainz + /profilez


def test_explainz_endpoint_roundtrip_and_errors(tmp_path):
    book = FlightBook()
    book.begin("abc123", pool="short", length=8)
    book.finish("abc123", "completed", replica="r0")
    ops = OpsServer(registry=MetricRegistry(), flights=book)
    with ops:
        base = ops.url

        def get(path):
            try:
                with urllib.request.urlopen(base + path, timeout=10) as r:
                    return r.status, json.loads(r.read().decode())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read().decode())

        code, payload = get("/explainz?trace_id=abc123")
        assert code == 200
        assert payload["outcome"] == "completed"
        assert payload["replica"] == "r0"
        code, payload = get("/explainz")
        assert code == 400 and payload["recent_trace_ids"] == ["abc123"]
        code, payload = get("/explainz?trace_id=nope")
        assert code == 404 and "recent_trace_ids" in payload
        # the root index advertises the new endpoints
        code, payload = get("/")
        assert "/explainz" in payload["endpoints"]
        assert "/profilez" in payload["endpoints"]
        # no profiler wired: 404, with the arming hint
        code, payload = get("/profilez")
        assert code == 404


def test_explainz_without_flight_book_is_404():
    ops = OpsServer(registry=MetricRegistry())
    code, payload = ops.explainz("whatever")
    assert code == 404


def test_profilez_capture_rate_limit_and_artifact(tmp_path):
    """One real capture on CPU (artifact existence), then the busy and
    rate-limit rejections — the 409/429 mapping through the HTTP layer."""
    prof = ProfileCapturer(str(tmp_path / "profiles"),
                           registry=MetricRegistry(),
                           min_interval_s=60.0)
    ops = OpsServer(registry=MetricRegistry(), profiler=prof)
    code, payload = ops.profilez("0.4")
    assert code == 200 and payload["status"] == "capturing"
    # a second start while running: busy (409)
    code, busy = ops.profilez("0.2")
    assert code == 409
    with pytest.raises(ProfileBusyError):
        prof.start(0.1)
    # generate some device work for the trace, then wait out the capture
    import jax.numpy as jnp

    jnp.ones((32, 32)).sum().block_until_ready()
    deadline = time.monotonic() + 30
    while prof.snapshot()["running"] is not None:
        assert time.monotonic() < deadline, "capture never stopped"
        time.sleep(0.05)
    files = [p for p in glob.glob(payload["dir"] + "/**/*", recursive=True)
             if os.path.isfile(p)]
    assert files, f"no profiler artifact under {payload['dir']}"
    # inside the rate-limit window: 429
    code, payload = ops.profilez("0.2")
    assert code == 429
    with pytest.raises(ProfileRateLimitedError):
        prof.start(0.1)
    # bad duration: 400
    assert ops.profilez("zero")[0] == 400
    assert ops.profilez("-1")[0] == 400
    snap = prof.snapshot()
    assert len(snap["captures"]) == 1
    ops.stop()


def test_tracer_dropped_spans_become_scrapeable_counter():
    """ISSUE 15 satellite: retention overflow was visible only in
    summary()/Chrome otherData — the ops ticker now publishes it as
    `trace_spans_dropped_total`."""
    tracer = Tracer(enabled=True, max_spans=2)
    reg = MetricRegistry()
    ops = OpsServer(registry=reg, tracer=tracer)
    # registered eagerly at 0: alertable before anything drops
    assert reg.snapshot()["counters"]["trace_spans_dropped_total"] == 0
    for i in range(5):
        with tracer.span("s", cat="t"):
            pass
    ops.tick()
    assert reg.snapshot()["counters"]["trace_spans_dropped_total"] == 3
    ops.tick()  # delta-published: a second tick must not double-count
    assert reg.snapshot()["counters"]["trace_spans_dropped_total"] == 3
    ops.stop()


# ------------------------------------------- headroom-driven autoscaling


class StubFleet:
    _closed = False

    def __init__(self, registry, n=1):
        self.registry = registry
        self.n = n

    def sample_gauges(self):
        pass

    def replica_count(self, pool=None):
        return self.n

    def add_replica(self, pool=None):
        self.n += 1
        return f"r{self.n - 1}"

    def remove_replica(self, name=None, pool=None):
        self.n -= 1
        return f"r{self.n}"


def mk_scaler(registry=None, pool="", **policy):
    registry = registry if registry is not None else MetricRegistry()
    fleet = StubFleet(registry)
    base = dict(min_replicas=1, max_replicas=3, up_sustain=2,
                down_sustain=2, up_cooldown_s=1.0, down_cooldown_s=5.0)
    base.update(policy)
    t = [0.0]
    scaler = ReplicaAutoscaler(fleet, ScalePolicy(**base),
                               registry=registry, pool=pool,
                               clock=lambda: t[0])
    return scaler, fleet, registry, t


def test_headroom_trigger_scales_up_before_queue_wait_would():
    """The acceptance pin: identical signals — queue EMPTY, queue-wait
    p95 well under its threshold, occupancy moderate — scale up via the
    headroom MODEL alone; with the headroom trigger disabled the same
    signals never fire (the symptom triggers would have waited for the
    queue to actually hurt)."""
    def arm(registry):
        hist = registry.histogram("fleet_queue_wait_seconds")
        for _ in range(8):
            hist.observe(0.3)          # p95 far BELOW the 2.0s threshold
        registry.gauge("fleet_queue_depth").set(0)   # queue not yet hurting
        registry.gauge("fleet_occupancy").set(0.5)
        registry.gauge("fleet_pool_headroom_ratio",
                       pool="default").set(0.05)     # the model: 5% left

    scaler, fleet, registry, t = mk_scaler(up_headroom=0.2)
    arm(registry)
    scaler.tick()                      # sustain 1/2
    assert fleet.n == 1
    t[0] += 1.0
    scaler.tick()                      # sustain 2/2: the MODEL fires
    assert fleet.n == 2
    ev = scaler.scale_events()[0]
    assert ev["signals"]["headroom"] == pytest.approx(0.05)
    assert ev["signals"]["queue_wait_p95"] < 2.0  # symptom never crossed

    # control arm: headroom trigger off, same signals -> no action ever
    scaler2, fleet2, registry2, t2 = mk_scaler(up_headroom=0.0)
    arm(registry2)
    for _ in range(6):
        t2[0] += 1.0
        scaler2.tick()
    assert fleet2.n == 1


def test_headroom_absent_gauge_keeps_trigger_inert():
    """No measured batches -> no headroom gauge -> the trigger must not
    read absence as zero headroom and scale a cold fleet to max."""
    scaler, fleet, registry, t = mk_scaler(up_headroom=0.5)
    registry.gauge("fleet_queue_depth").set(0)
    for _ in range(6):
        t[0] += 1.0
        scaler.tick()
    assert fleet.n == 1
    assert scaler.events() == [] or all(
        e["signals"]["headroom"] is None for e in scaler.events())


def test_headroom_pool_scoped_reads_its_own_pool():
    registry = MetricRegistry()
    registry.gauge("fleet_pool_headroom_ratio", pool="long").set(0.01)
    registry.gauge("fleet_pool_headroom_ratio", pool="short").set(0.9)
    registry.gauge("fleet_pool_queue_depth", pool="short").set(0)
    scaler, fleet, _, t = mk_scaler(registry=registry, pool="short",
                                    up_headroom=0.2, up_sustain=1)
    scaler.tick()
    assert fleet.n == 1                # its own pool has headroom
    # the fleet-wide scaler keys on the TIGHTEST pool
    scaler2, fleet2, _, _ = mk_scaler(registry=registry, up_headroom=0.2,
                                      up_sustain=1)
    registry.gauge("fleet_queue_depth").set(0)
    scaler2.tick()
    assert fleet2.n == 2


def test_headroom_zero_capacity_publishes_worst_case_not_stale():
    """A measured pool whose every replica went unhealthy must publish
    headroom = -1 (worst case), not freeze the last pre-outage value —
    the up-trigger exists for exactly that outage."""
    fleet = ServingFleet(
        {}, TINY, ServingConfig(buckets=(8,), max_batch=1, max_wait_s=0.0,
                                cache_capacity=0),
        FleetConfig(replicas=1, probe_interval_s=0),
        engine_factory=lambda n, c, h: FakeEngine({}, TINY, c,
                                                  fault_hook=h))
    try:
        # arm the capacity model: one measured batch in the pool's cell
        fleet.costs.observe_batch(
            ("default", 8, "dense", "xla_ref", "f32"),
            device_seconds=0.1, requests=1)
        fleet._sample_headroom(time.monotonic(), {"default": 1})
        g = fleet.registry.snapshot()["gauges"]
        assert g['fleet_pool_headroom_ratio{pool="default"}'] == 1.0
        # every replica down -> worst case, immediately
        fleet._sample_headroom(time.monotonic() + 1.0, {"default": 0})
        g = fleet.registry.snapshot()["gauges"]
        assert g['fleet_pool_headroom_ratio{pool="default"}'] == -1.0
        assert g['fleet_pool_capacity_per_sec{pool="default"}'] == 0.0
    finally:
        fleet.shutdown(timeout=10)


def test_engine_flight_sealed_on_coalesce_and_queue_full():
    """Single-engine /explainz must not show rejected/coalesced
    submissions as forever in flight."""
    book = FlightBook()
    release = __import__("threading").Event()

    class Slow(FakeEngine):
        def _call_executable(self, bucket, tokens, mask, msa=None,
                             msa_mask=None):
            release.wait(10)
            return super()._call_executable(bucket, tokens, mask, msa,
                                            msa_mask)

    eng = Slow({}, TINY, ServingConfig(
        buckets=(8,), max_batch=1, max_queue=1, max_wait_s=0.0,
        cache_capacity=64, request_timeout_s=None), flights=book)
    try:
        first = eng.submit(seq_of(5), trace_id="first000000000aa")
        # identical query coalesces onto `first`: its own record seals
        co = eng.submit(seq_of(5), trace_id="coalesced0000000")
        assert co is first
        rec = book.get("coalesced0000000")
        assert rec["outcome"] == "coalesced"
        assert rec["onto"] == "first000000000aa"
        # wait for the worker to pull `first` into its (blocked)
        # dispatch so the queue is empty again, then fill it
        deadline = time.monotonic() + 10
        while eng._queue.qsize() > 0:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        eng.submit(seq_of(6), trace_id="queued0000000000")
        from alphafold2_tpu.serving import QueueFullError

        with pytest.raises(QueueFullError):
            eng.submit(seq_of(7), trace_id="rejected00000000")
        assert book.get("rejected00000000")["outcome"] == "rejected"
        release.set()
        first.result(timeout=10)
        assert book.get("first000000000aa")["outcome"] == "completed"
    finally:
        release.set()
        eng.shutdown(timeout=10)


def test_scale_policy_up_headroom_validation():
    with pytest.raises(ValueError, match="up_headroom"):
        ScalePolicy(up_headroom=1.5)
    with pytest.raises(ValueError, match="up_headroom"):
        ScalePolicy(up_headroom=-0.1)
    pol = ScalePolicy.from_dict({"up_headroom": 0.3})
    assert pol.up_headroom == 0.3


# --------------------------------------------------- chaos acceptance run


def test_fleet_chaos_explainz_goodput_and_cost_rows(tiny_params):
    """The ISSUE 15 acceptance, chip-free: a real 2-replica fleet under
    a kill_replica plan serves a requeued request; then (1) /explainz
    over live HTTP reconstructs the request's whole flight path by
    trace_id (dispatch r0 -> requeue -> dispatch r1 -> completed), (2)
    every replica's goodput buckets sum to its wall within 1%, (3) the
    cost ledger has a measured row for the served (pool, bucket), and
    (4) headroom gauges publish once the model arms."""
    from alphafold2_tpu.telemetry import ops_server_for_fleet

    inj = FaultPlan(
        faults=(Fault("kill_replica", replica="r0", at=0),)).injector()
    scfg = ServingConfig(buckets=(8,), max_batch=1, max_wait_s=0.0,
                         mds_iters=2, request_timeout_s=300.0,
                         cache_capacity=0)
    fleet = ServingFleet(
        tiny_params, TINY, scfg,
        FleetConfig(replicas=2, probe_interval_s=0,
                    reprobe_interval_s=30.0, fail_threshold=1,
                    requeue_limit=2, default_timeout_s=300.0),
        injector=inj)
    try:
        got = fleet.predict(seq_of(5))
        assert got.requeues == 1 and got.replica == "r1"
        # a couple more so the measured columns settle
        for i in range(2):
            fleet.predict(seq_of(4 + i, offset=i))

        # (1) explain the requeued request end to end, over live HTTP
        with ops_server_for_fleet(fleet) as ops:
            with urllib.request.urlopen(
                    f"{ops.url}/explainz?trace_id={got.trace_id}",
                    timeout=10) as r:
                assert r.status == 200
                flight = json.loads(r.read().decode())
        assert flight["outcome"] == "completed"
        assert flight["requeues"] == 1
        events = [(e["event"], e.get("replica"), e.get("failed_on"))
                  for e in flight["events"]]
        assert ("dispatch", "r0", None) in events
        assert any(ev == "requeue" and failed == "r0"
                   for ev, _, failed in events)
        assert ("dispatch", "r1", None) in events
        assert events[-1][0] == "terminal"
        # the dispatch hop carries the cost-cell identity
        hop = next(e for e in flight["events"]
                   if e["event"] == "dispatch" and e.get("replica") == "r1")
        assert hop["schedule"] == "dense"
        assert hop["bucket"] == 8

        st = fleet.stats()
        # (2) sums-to-wall within 1% per replica, against the ledger's
        # LIVE clock wall — the snapshot's wall_s is the bucket sum by
        # construction (comparing against it would be a tautology);
        # accounted exceeds the clock wall only via cross-thread
        # accounting overlap (the chaos run exercised execute, compile,
        # requeue, probe, and drain accounting concurrently)
        for name in st["serve_goodput"]["replicas"]:
            total = sum(fleet.goodput.totals(name).values())
            wall_now = fleet.goodput.wall(name)
            assert total <= wall_now * 1.01 + 1e-6, (
                name, total, wall_now)
        # r0's burned attempt + drain are badput, r1 did the execute
        assert st["serve_goodput"]["replicas"]["r0"]["buckets"][
            "requeue"] > 0
        assert st["serve_goodput"]["replicas"]["r1"]["buckets"][
            "execute"] > 0

        # (3) a measured cost row for the served (pool, bucket)
        cells = {(c["pool"], c["bucket"]): c for c in st["costs"]["cells"]}
        served = cells[("default", 8)]
        assert served["requests"] >= 3
        assert served["chip_seconds_per_request"] is not None
        assert served["forward_flops"] > 0

        # (4) two spaced samples arm the arrival EMA -> headroom publishes
        fleet.sample_gauges()
        time.sleep(0.06)
        fleet.sample_gauges()
        gauges = fleet.registry.snapshot()["gauges"]
        assert gauges['fleet_pool_headroom_ratio{pool="default"}'] >= -1.0
        assert gauges['fleet_pool_capacity_per_sec{pool="default"}'] > 0
        assert st["flights"]["records"] >= 3
    finally:
        fleet.shutdown(timeout=30)
