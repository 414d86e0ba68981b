"""Fleet artifact store + front-door coalescing (ISSUE 17, tier-1, CPU).

Unit layer: content-addressed framing (checksum round-trip, corrupt
variants), the two-level store (hot ring over disk), budget sweep and
tag GC. Integration layer (fake engines, zero XLA): store hits serve
with zero dispatches, N identical requests across two capability pools
collapse onto exactly ONE engine dispatch, feature bundles replay from
the store on re-submission, coalition failure/shutdown propagation,
rolling-update invalidation, and the chip-seconds A/B gate that
`telemetry.check` enforces over the bench artifacts.
"""

import os
import threading
import time

import numpy as np
import pytest

from alphafold2_tpu.constants import AA_ORDER
from alphafold2_tpu.models import Alphafold2Config
from alphafold2_tpu.serving import (
    ArtifactStore,
    ArtifactStoreConfig,
    EngineClosedError,
    FleetConfig,
    PoolSpec,
    PredictionResult,
    ServingConfig,
    ServingEngine,
    ServingFleet,
    featurize_request,
    request_key,
)
from alphafold2_tpu.serving.artifact_store import (
    _MAGIC,
    _pack,
    _unpack,
    ArtifactCorruptError,
    tag_digest,
)
from alphafold2_tpu.serving.bucketing import BucketLadder

TINY = Alphafold2Config(dim=16, depth=1, heads=2, dim_head=8, max_seq_len=16)
AA = AA_ORDER.replace("W", "")


def seq_of(length, offset=0):
    return "".join(AA[(offset + i) % len(AA)] for i in range(length))


def result_of(seq, fill=1.0):
    L = len(seq)
    return PredictionResult(
        seq=seq, coords=np.full((L, 3), fill, np.float32),
        confidence=np.full((L,), 0.5, np.float32), stress=0.25,
        bucket=8, from_cache=False, latency_s=0.1)


class FakeEngine(ServingEngine):
    """Device call stubbed at the documented seam; counts dispatches."""

    def __init__(self, *args, call_hook=None, **kwargs):
        self.calls = 0
        self._hook = call_hook
        super().__init__(*args, **kwargs)

    def _call_executable(self, bucket, tokens, mask, msa=None, msa_mask=None):
        self.calls += 1
        if self._hook is not None:
            self._hook(bucket, tokens, mask)
        B, Lb = tokens.shape
        return {
            "coords": np.zeros((B, Lb, 3), np.float32),
            "confidence": np.full((B, Lb), 0.5, np.float32),
            "stress": np.zeros((B,), np.float32),
        }


def fleet_scfg(**overrides):
    base = dict(buckets=(8, 16), max_batch=2, max_queue=8, max_wait_s=0.0,
                request_timeout_s=30.0, cache_capacity=0)
    base.update(overrides)
    return ServingConfig(**base)


def fake_fleet(store=None, call_hook=None, scfg=None, **overrides):
    base = dict(replicas=2, probe_interval_s=0, reprobe_interval_s=0.05,
                fail_threshold=1, requeue_limit=2)
    base.update(overrides)
    engines = []

    def factory(name, cfg, fault_hook):
        e = FakeEngine({}, TINY, cfg, call_hook=call_hook,
                       fault_hook=fault_hook)
        engines.append(e)
        return e

    fleet = ServingFleet({}, TINY, scfg or fleet_scfg(), FleetConfig(**base),
                         engine_factory=factory, artifact_store=store)
    fleet._test_engines = engines
    return fleet


def total_calls(fleet):
    return sum(e.calls for e in fleet._test_engines)


# ------------------------------------------------------------- framing


def test_pack_unpack_roundtrip_and_checksum():
    arrays = {"coords": np.arange(12, dtype=np.float32).reshape(4, 3)}
    meta = {"kind": "result", "seq": "ACDE", "stress": 0.5, "bucket": 8}
    blob = _pack(arrays, meta)
    assert blob.startswith(_MAGIC)
    out_arrays, out_meta = _unpack(blob)
    assert out_meta == meta
    np.testing.assert_array_equal(out_arrays["coords"], arrays["coords"])
    # every corruption class raises the SAME error (one degradation
    # path: recompute)
    for bad in (
        blob[:-5],                              # torn tail
        blob[:len(_MAGIC) + 10],                # truncated header
        b"GARBAGE!" + blob[len(_MAGIC):],       # bad magic
        blob[:40] + bytes([blob[40] ^ 0xFF]) + blob[41:],  # poisoned byte
        b"",
    ):
        with pytest.raises(ArtifactCorruptError):
            _unpack(bad)


def test_store_roundtrip_memory_and_disk(tmp_path):
    store = ArtifactStore(ArtifactStoreConfig(root=str(tmp_path)))
    seq = seq_of(6)
    key = request_key(seq, None, "tag-a")
    assert store.lookup_result("tag-a", key) is None
    store.put_result("tag-a", key, result_of(seq))
    obj, level = store.lookup_result("tag-a", key)
    assert level == "memory" and obj.seq == seq and obj.from_cache
    # a second store over the same disk root reads what the first wrote
    # (the fleet-not-replica unit of memoization): disk level provenance
    store2 = ArtifactStore(ArtifactStoreConfig(root=str(tmp_path)))
    obj2, level2 = store2.lookup_result("tag-a", key)
    assert level2 == "disk"
    np.testing.assert_array_equal(obj2.coords, obj.coords)
    # ... and the disk hit promoted it into store2's hot ring
    assert store2.lookup_result("tag-a", key)[1] == "memory"
    # keys embed the tag: another tag cannot reach the entry
    assert store2.lookup_result("tag-b", key) is None


def test_store_features_roundtrip(tmp_path):
    store = ArtifactStore(ArtifactStoreConfig(root=str(tmp_path)))
    seq = seq_of(7)
    msa = np.zeros((2, 7), np.int32)
    mask = np.ones((2, 7), bool)
    bundle = featurize_request(seq, msa=msa, msa_mask=mask,
                               ladder=BucketLadder((8, 16)), msa_rows=4)
    key = request_key(seq, msa, "feat-tag", msa_mask=mask)
    store.put_features("feat-tag", key, bundle)
    fresh = ArtifactStore(ArtifactStoreConfig(root=str(tmp_path)))
    out, level = fresh.lookup_features("feat-tag", key)
    assert level == "disk" and out.seq == bundle.seq
    assert out.bucket == bundle.bucket
    np.testing.assert_array_equal(out.tokens, bundle.tokens)
    np.testing.assert_array_equal(out.msa, bundle.msa)
    np.testing.assert_array_equal(out.msa_mask, bundle.msa_mask)


def test_hot_ring_bounded_by_entries_and_bytes():
    store = ArtifactStore(ArtifactStoreConfig(memory_entries=3))
    for i in range(5):
        seq = seq_of(6, offset=i)
        store.put_result("t", request_key(seq, None, "t"), result_of(seq))
    snap = store.snapshot()
    assert snap["memory"]["entries"] == 3
    assert snap["evictions_memory"] == 2
    # oldest evicted, newest present
    assert store.lookup_result(
        "t", request_key(seq_of(6, offset=0), None, "t")) is None
    assert store.lookup_result(
        "t", request_key(seq_of(6, offset=4), None, "t")) is not None
    # byte budget evicts independently of the entry cap
    tiny = ArtifactStore(ArtifactStoreConfig(memory_entries=100,
                                             memory_bytes=600))
    for i in range(4):
        seq = seq_of(8, offset=i)
        tiny.put_result("t", request_key(seq, None, "t"), result_of(seq))
    assert tiny.snapshot()["memory"]["bytes"] <= 600


def test_corrupt_disk_entry_degrades_to_miss(tmp_path):
    store = ArtifactStore(ArtifactStoreConfig(root=str(tmp_path),
                                              memory_entries=0))
    seq = seq_of(6)
    key = request_key(seq, None, "t")
    store.put_result("t", key, result_of(seq))
    path = store._path("result", "t", key)
    with open(path, "r+b") as fh:
        fh.seek(-10, os.SEEK_END)
        fh.write(b"\xff" * 10)
    assert store.lookup_result("t", key) is None     # poisoned -> miss
    assert not os.path.exists(path)                  # and quarantined
    assert store.snapshot()["corrupt"] == 1


def test_sweep_gc_stale_tags_and_byte_budget(tmp_path):
    store = ArtifactStore(ArtifactStoreConfig(root=str(tmp_path),
                                              disk_bytes=10_000_000))
    for tag in ("old-tag", "new-tag"):
        for i in range(3):
            seq = seq_of(6, offset=i)
            store.put_result(tag, request_key(seq, None, tag),
                             result_of(seq))
    store.set_current_tags(["new-tag"])
    out = store.sweep()
    assert out["gc_files"] == 3
    old_dir = os.path.join(str(tmp_path), "result", tag_digest("old-tag"))
    assert not os.path.exists(old_dir)
    # stale-tag hot-ring entries purged too: unreachable != resident
    assert store.snapshot()["memory"]["entries"] == 3
    key0 = request_key(seq_of(6), None, "new-tag")
    assert store.lookup_result("new-tag", key0) is not None
    # byte budget: shrink it and the sweep evicts oldest-mtime-first
    small = ArtifactStore(ArtifactStoreConfig(root=str(tmp_path),
                                              disk_bytes=1))
    small.set_current_tags(["new-tag"])
    out = small.sweep()
    assert out["budget_files"] >= 2 and out["disk_bytes"] <= 1


def test_store_metrics_rebind_into_fleet_registry(tmp_path):
    """serve.py builds the store BEFORE the fleet exists: attaching must
    re-home the artifact_store_* families into the fleet registry (one
    /metrics scrape carries both) and carry pre-warm counts over."""
    store = ArtifactStore(ArtifactStoreConfig(root=str(tmp_path)))
    seq = seq_of(6)
    key = request_key(seq, None, "warm-tag")
    store.put_result("warm-tag", key, result_of(seq))
    assert store.lookup_result("warm-tag", key)[1] == "memory"  # 1 hit
    fleet = fake_fleet(store=store)
    try:
        def total(name, **labels):
            snap = fleet.registry.snapshot()
            out = 0.0
            for series, v in {**snap["counters"], **snap["gauges"]}.items():
                base = series.split("{", 1)[0]
                if base != name:
                    continue
                if all(f'{k}="{val}"' in series
                       for k, val in labels.items()):
                    out += v
            return out
        snap = fleet.registry.snapshot()
        fams = {s.split("{", 1)[0]
                for s in (*snap["counters"], *snap["gauges"])}
        assert {"artifact_store_hits_total", "artifact_store_misses_total",
                "cache_corrupt_total", "artifact_store_disk_writes_total",
                "artifact_store_memory_bytes"} <= fams
        # the pre-attach memory hit and disk write were seeded across
        assert total("artifact_store_hits_total", level="memory") == 1
        assert total("artifact_store_disk_writes_total") == 1
        # post-attach traffic lands in the SAME registry
        fleet.predict(seq_of(9))
        fleet.predict(seq_of(9))
        assert total("artifact_store_hits_total", level="memory") >= 2
        # idempotent: rebinding to the same registry is a no-op
        before = total("artifact_store_hits_total")
        store.bind_registry(fleet.registry)
        assert total("artifact_store_hits_total") == before
    finally:
        fleet.shutdown()


# ------------------------------------------------- fleet: store hits


def test_fleet_store_hit_serves_with_zero_dispatches(tmp_path):
    store = ArtifactStore(ArtifactStoreConfig(root=str(tmp_path)))
    fleet = fake_fleet(store=store)
    try:
        seq = seq_of(6)
        r1 = fleet.predict(seq)
        assert total_calls(fleet) == 1 and not r1.from_cache
        r2 = fleet.predict(seq)
        assert total_calls(fleet) == 1          # zero new dispatches
        assert r2.from_cache
        np.testing.assert_array_equal(r1.coords, r2.coords)
        snap = fleet.stats()["artifact_store"]
        assert snap["hits_memory"] >= 1
        # flight provenance: the hit's terminal event says WHERE it came
        # from (/explainz contract)
        rec = fleet.flights.get(r2.trace_id)
        assert rec["outcome"] == "completed"
        assert rec.get("cache_tier") == "artifact_store"
        assert rec.get("cache_level") == "memory"
    finally:
        fleet.shutdown()


def test_fleet_store_survives_restart_via_disk(tmp_path):
    store = ArtifactStore(ArtifactStoreConfig(root=str(tmp_path)))
    fleet = fake_fleet(store=store)
    try:
        seq = seq_of(9)
        fleet.predict(seq)
    finally:
        fleet.shutdown()
    # a NEW fleet process over the same disk tier: the request is free
    fleet2 = fake_fleet(
        store=ArtifactStore(ArtifactStoreConfig(root=str(tmp_path))))
    try:
        r = fleet2.predict(seq)
        assert r.from_cache and total_calls(fleet2) == 0
    finally:
        fleet2.shutdown()


def test_degraded_tier_results_never_enter_the_store(tmp_path):
    """A degraded-tier answer is reduced-fidelity by contract — caching
    it would serve degraded numerics as full ones forever after."""
    store = ArtifactStore(ArtifactStoreConfig(root=str(tmp_path)))
    fleet = fake_fleet(store=store, replicas=1, degraded_mds_iters=1,
                       fail_threshold=1, requeue_limit=0,
                       reprobe_interval_s=30.0)
    try:
        # force the lone replica down; traffic spills to the degraded tier
        fleet._health.force_down("r0", "test")
        r = fleet.predict(seq_of(6))
        assert r.degraded
        # the FEATURES write is fine (featurization is params-independent
        # and identical on the degraded tier); the RESULT keyspace must
        # stay empty — on disk and in the hot ring
        result_dir = os.path.join(str(tmp_path), "result")
        assert (not os.path.exists(result_dir)
                or not any(os.scandir(result_dir)))
        r2 = fleet.predict(seq_of(6))
        assert r2.degraded and not r2.from_cache   # recomputed, not cached
    finally:
        fleet.shutdown()


# ------------------------------------------- fleet: front-door coalescing


def test_identical_requests_across_two_pools_one_dispatch():
    """THE ISSUE 17 coalescing acceptance pin: a fleet with TWO
    capability pools, N identical in-flight submissions -> exactly one
    engine dispatch fleet-wide; every waiter gets the leader's answer."""
    gate = threading.Event()
    big = Alphafold2Config(dim=16, depth=1, heads=2, dim_head=8,
                           max_seq_len=32)
    engines = []

    def factory(name, cfg, fault_hook):
        e = FakeEngine({}, big, cfg,
                       call_hook=lambda *a: gate.wait(10),
                       fault_hook=fault_hook)
        engines.append(e)
        return e

    store = ArtifactStore(ArtifactStoreConfig())   # memory-only
    fleet = ServingFleet(
        {}, big, fleet_scfg(), FleetConfig(
            replicas=1, probe_interval_s=0, reprobe_interval_s=30.0,
            pools=(PoolSpec("short", replicas=2, buckets=(8, 16)),
                   PoolSpec("long", replicas=2, buckets=(8, 16, 32)))),
        engine_factory=factory, artifact_store=store)
    try:
        seq = seq_of(6)
        handles = [fleet.submit(seq) for _ in range(5)]
        # all five are in flight together: one leader dispatched (or
        # queued), four followers parked at the front door
        deadline = time.monotonic() + 5
        while (fleet.stats()["frontdoor"]["waiting_followers"] < 4
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert fleet.stats()["frontdoor"]["waiting_followers"] == 4
        gate.set()
        results = [h.result(timeout=10) for h in handles]
        assert sum(e.calls for e in engines) == 1
        assert sum(1 for r in results if not r.from_cache) == 1  # the leader
        for r in results:
            np.testing.assert_array_equal(r.coords, results[0].coords)
        assert fleet.stats()["frontdoor"]["coalesced_total"] == 4
        reg = fleet.registry.snapshot()
        assert reg["counters"]["fleet_coalesced_total"] == 4
    finally:
        gate.set()
        fleet.shutdown()


def test_follower_carries_leader_failure():
    """A coalition fails together: the leader's terminal error reaches
    every follower (never a hang, never a silent drop)."""
    gate = threading.Event()

    def hook(bucket, tokens, mask):
        gate.wait(10)
        raise RuntimeError("injected device fault")

    store = ArtifactStore(ArtifactStoreConfig())
    fleet = fake_fleet(store=store, call_hook=hook, replicas=2,
                       requeue_limit=0)
    try:
        seq = seq_of(6)
        leader = fleet.submit(seq)
        follower = fleet.submit(seq)
        assert fleet.stats()["frontdoor"]["waiting_followers"] == 1
        gate.set()
        with pytest.raises(Exception) as e1:
            leader.result(timeout=10)
        with pytest.raises(Exception) as e2:
            follower.result(timeout=10)
        assert type(e1.value) is type(e2.value)
        # nothing cached from a failure: the result keyspace is empty
        # (the one memory hit the stats DO show is the follower's
        # feature-bundle replay, which is failure-independent)
        tag = fleet._store_tag(next(iter(fleet._pools)))
        key = request_key(seq, None, tag)
        assert store.lookup_result(tag, key) is None
        counts = fleet.stats()["requests"]
        assert counts["in_flight"] == 0
    finally:
        gate.set()
        fleet.shutdown()


def test_shutdown_resolves_parked_followers():
    gate = threading.Event()
    store = ArtifactStore(ArtifactStoreConfig())
    fleet = fake_fleet(store=store, call_hook=lambda *a: gate.wait(10))
    seq = seq_of(6)
    leader = fleet.submit(seq)
    followers = [fleet.submit(seq) for _ in range(3)]
    assert fleet.stats()["frontdoor"]["waiting_followers"] == 3
    gate.set()
    fleet.shutdown(drain=True)
    # drain served the leader; its settle path completed every follower
    assert leader.result(timeout=1).seq == seq
    for f in followers:
        r = f.result(timeout=1)
        assert r.from_cache and r.seq == seq
    assert fleet.stats()["requests"]["in_flight"] == 0


def test_shutdown_without_drain_fails_followers_terminally():
    gate = threading.Event()
    store = ArtifactStore(ArtifactStoreConfig())
    fleet = fake_fleet(store=store, call_hook=lambda *a: gate.wait(10))
    seq = seq_of(6)
    leader = fleet.submit(seq)
    followers = [fleet.submit(seq) for _ in range(2)]
    assert fleet.stats()["frontdoor"]["waiting_followers"] == 2
    fleet.shutdown(drain=False)
    gate.set()
    # the leader was already dispatched when shutdown hit, so it may
    # legitimately complete; the PARKED followers must resolve
    # terminally (EngineClosedError), never hang
    try:
        leader.result(timeout=5)
    except Exception:
        pass
    for h in followers:
        with pytest.raises(EngineClosedError):
            h.result(timeout=5)
    assert fleet.stats()["requests"]["in_flight"] == 0
    assert fleet.stats()["frontdoor"]["waiting_followers"] == 0


# ---------------------------------------------- fleet: feature replay


def test_feature_bundles_replay_from_store_on_resubmission(tmp_path):
    store = ArtifactStore(ArtifactStoreConfig(root=str(tmp_path)))
    fleet = fake_fleet(store=store)
    try:
        seq = seq_of(10)
        fleet.predict(seq)
        # resubmit: the RESULT hit wins outright, but drop the result
        # entry to force the featurize path and prove the bundle replays
        ftag = fleet._feature_tag()
        fkey = request_key(seq, None, ftag)
        assert store.lookup_features(ftag, fkey) is not None
        rtag = fleet._store_tag(next(iter(fleet._pools)))
        bundle = store.lookup_features(ftag, fkey)[0]
        rkey = request_key(bundle.seq, bundle.msa, rtag,
                           msa_mask=bundle.msa_mask)
        # predict() returns when the leader's future resolves, which is
        # BEFORE the settle path persists the result (persistence rides
        # the dispatch callback thread) — wait for the file, not the clock
        result_path = store._path("result", rtag, rkey)
        deadline = time.monotonic() + 10
        while not os.path.exists(result_path) and time.monotonic() < deadline:
            time.sleep(0.01)
        assert os.path.exists(result_path)
        writes = fleet.stats()["artifact_store"]["disk"]["writes"]
        assert writes >= 2  # result + features
        # evict the result from ring+disk, keep the features
        store._ring.pop(("result", rtag, rkey), None)
        os.unlink(result_path)
        h = fleet.submit(seq)
        r = h.result(timeout=10)
        assert not r.from_cache
        rec = fleet.flights.get(r.trace_id)
        assert any(e.get("event") == "features_from_store"
                   for e in rec["events"])
    finally:
        fleet.shutdown()


# ------------------------------------------ rolling-update invalidation


def test_rolling_update_invalidates_old_tag_entries(tmp_path):
    """Satellite: after rolling_update(params_tag=...), old-tag entries
    are unreachable AND GC'd from disk, while in-flight old-tag waiters
    (a coalesced follower mid-update) still complete."""
    store = ArtifactStore(ArtifactStoreConfig(root=str(tmp_path)))

    def slow_hook(bucket, tokens, mask):
        time.sleep(0.2)

    fleet = fake_fleet(store=store, call_hook=slow_hook, replicas=2)
    try:
        warm = seq_of(6)
        fleet.predict(warm)                      # cached under the old tag
        old_tag = fleet._store_tag(next(iter(fleet._pools)))
        old_dir = os.path.join(str(tmp_path), "result",
                               tag_digest(old_tag))
        # the leader's future resolves BEFORE the settle path persists
        # (persistence rides the dispatch callback thread) — wait for it
        deadline = time.monotonic() + 5
        while not os.path.isdir(old_dir) and time.monotonic() < deadline:
            time.sleep(0.01)
        assert os.listdir(old_dir)
        calls_before = total_calls(fleet)
        # leader + follower in flight across the update
        inflight = seq_of(9)
        leader = fleet.submit(inflight)
        follower = fleet.submit(inflight)
        fleet.rolling_update(params_tag="pins-v2", timeout_s=30.0)
        # the in-flight old-tag coalition still completed
        assert leader.result(timeout=10).seq == inflight
        r2 = follower.result(timeout=10)
        assert r2.seq == inflight
        # old-tag keyspace: unreachable (tag changed) and GC'd from disk
        new_tag = fleet._store_tag(next(iter(fleet._pools)))
        assert new_tag != old_tag
        assert not os.path.exists(old_dir)
        # the warm entry is gone for real: same sequence recomputes
        r3 = fleet.predict(warm)
        assert not r3.from_cache
        assert total_calls(fleet) > calls_before
    finally:
        fleet.shutdown()


# ---------------------------------------------- the chip-seconds gate


def run_duplicate_trace(store, n_unique=3, repeats=3, service_s=0.01):
    """One A/B arm: a duplicate-heavy trace (each unique sequence
    submitted `repeats` times, sequentially so the store arm exercises
    HITS, not just coalescing) against a fake fleet whose per-dispatch
    device-seconds are deterministic. Returns the bench-artifact metric
    dict for telemetry.check."""
    fleet = fake_fleet(store=store,
                       call_hook=lambda *a: time.sleep(service_s))
    try:
        seqs = [seq_of(6 + i % 8, offset=i) for i in range(n_unique)]
        n = 0
        for _ in range(repeats):
            for seq in seqs:
                fleet.predict(seq)
                n += 1
        completed = fleet.stats()["requests"]["completed"]
        assert completed == n
        # the test factory builds engines with PRIVATE cost ledgers (only
        # the default factory threads the shared fleet ledger through),
        # so sum device-seconds across the engines' own ledgers
        chip_s = sum(e.costs.fleet_chip_seconds_total()
                     for e in fleet._test_engines)
        dispatches = total_calls(fleet)
        return {
            "metric": "serve_chip_seconds_per_request",
            "value": chip_s / completed,
            "requests": float(completed),
            "dispatches": float(dispatches),
        }
    finally:
        fleet.shutdown()


def test_chip_seconds_per_request_gate_30_percent():
    """Satellite: the telemetry.check gate. Under a >=3x-repetition
    trace the store-enabled fleet must cut amortized chip-seconds per
    request by >=30% vs the store-disabled baseline — enforced with the
    same rule string CI uses over the committed bench artifacts."""
    from alphafold2_tpu.telemetry.check import check

    # the CI rule: negative tolerance turns the regression gate into an
    # IMPROVEMENT floor — status regresses unless current improved >=30%
    gate = [("*chip_seconds_per_request*", "lower", -0.30)]
    baseline = run_duplicate_trace(store=None)
    current = run_duplicate_trace(store=ArtifactStore(ArtifactStoreConfig()))
    assert baseline["dispatches"] >= 3 * current["dispatches"] - 1e-9
    passed, rows = check(current, baseline, rules=gate)
    assert passed, rows
    row = next(r for r in rows
               if r["metric"] == "serve_chip_seconds_per_request")
    assert row["change"] <= -0.30
    # the gate has teeth: identical artifacts FAIL an improvement floor
    # (a -30% tolerance is not a pass-by-default)
    passed_same, _ = check(baseline, baseline, rules=gate)
    assert not passed_same
