"""Fleet tier: stat merging, pin-guarded GC, routing, admission control,
adaptive per-bucket waits, and canary-then-promote rollouts. All timing
is driven by fake clocks — no sleeps, no flakes."""
import jax
import numpy as np
import pytest

from repro.api import KernelKMeans
from repro.data import blob_ring
from repro.fleet import (AdaptiveWaitController, AdmissionController, Fleet,
                         FleetWorker, RolloutManager, Router, ShedError)
from repro.serve import AsyncBatcher, LatencyStats, VersionStore, assign
from repro.serve.latency import Histogram

N, P, R, K, BLOCK = 250, 2, 2, 2, 64


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance_ms(self, ms):
        self.t += ms / 1e3


@pytest.fixture(scope="module")
def model():
    X, _ = blob_ring(jax.random.PRNGKey(0), n=N)
    return KernelKMeans(k=K, r=R, kernel="polynomial",
                        kernel_params={"gamma": 0.0, "degree": 2},
                        backend_params={"oversampling": 10},
                        block=BLOCK).fit(X, key=jax.random.PRNGKey(1)).model_


@pytest.fixture(scope="module")
def model_b(model):
    # Permuted centroid rows: same geometry, permuted labels — which
    # version served a request is readable from its labels.
    return model._replace(centroids=model.centroids[::-1])


@pytest.fixture()
def store(tmp_path, model):
    s = VersionStore(str(tmp_path / "versions"))
    s.publish(model)
    return s


def _requests(widths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randn(P, w).astype(np.float32) for w in widths]


# ---------------------------------------------------------------------------
# LatencyStats.merge: tier aggregation must equal a single stream
# ---------------------------------------------------------------------------

def _record(stats, t0, wait_ms, extra_ms, bucket):
    stats.record(t0, t0 + wait_ms / 1e3, t0 + (wait_ms + extra_ms) / 1e3,
                 queries=3, bucket=bucket)


def test_merge_equals_single_stream_on_interleaved_samples():
    rng = np.random.RandomState(7)
    workers = [LatencyStats(slo_ms=50.0) for _ in range(3)]
    single = LatencyStats(slo_ms=50.0)
    # Interleave 300 samples round-robin across three workers; the same
    # stream lands in `single` in arrival order.
    for i in range(300):
        wait, extra = rng.exponential(5.0), rng.exponential(30.0)
        bucket = int(2 ** rng.randint(3, 7))
        _record(workers[i % 3], float(i), wait, extra, bucket)
        _record(single, float(i), wait, extra, bucket)
    merged = LatencyStats.merged(workers)
    got, want = merged.summary(), single.summary()
    # Histogram counts share fixed edges, so percentiles/counters are
    # EXACTLY the single-stream values; the means fold float sums in a
    # different order and may differ in the last ulp.
    for d in (got, want):
        d["latency_ms"]["mean"] = round(d["latency_ms"]["mean"], 9)
        for row in d["per_bucket"].values():
            row["mean"] = round(row["mean"], 9)
    assert got == want
    assert merged.requests == 300 and merged.queries == 900
    # Non-mutating: the per-worker stats were not folded into each other.
    assert workers[0].requests == 100


def test_merge_is_exact_at_every_percentile():
    a, b = LatencyStats(), LatencyStats()
    single = LatencyStats()
    for i, ms in enumerate([0.1, 1.0, 5.0, 42.0, 999.0, 0.5, 7.0, 80.0]):
        target = a if i % 2 == 0 else b
        _record(target, 0.0, ms / 2, ms / 2, None)
        _record(single, 0.0, ms / 2, ms / 2, None)
    m = LatencyStats.merged([a, b])
    for q in (1.0, 25.0, 50.0, 90.0, 95.0, 99.0, 100.0):
        assert m.total.percentile(q) == single.total.percentile(q)
        assert m.queue_wait.percentile(q) == single.queue_wait.percentile(q)


def test_merge_rejects_mismatched_slo():
    a, b = LatencyStats(slo_ms=50.0), LatencyStats(slo_ms=100.0)
    with pytest.raises(ValueError, match="different SLO"):
        a.merge(b)
    # An EMPTY slo-less aggregate adopts the first real threshold...
    empty = LatencyStats()
    empty.merge(b)
    assert empty.slo_ms == 100.0
    # ...but one that already recorded against None must refuse.
    dirty = LatencyStats()
    _record(dirty, 0.0, 1.0, 1.0, None)
    with pytest.raises(ValueError, match="different SLO"):
        dirty.merge(b)


def test_histogram_merge_folds_counts_min_max():
    a, b = Histogram(), Histogram()
    for ms in (1.0, 2.0, 3.0):
        a.record(ms)
    for ms in (0.5, 10.0):
        b.record(ms)
    out = a.merge(b)
    assert out is a
    assert a.n == 5
    assert a.min == 0.5 and a.max == 10.0
    assert abs(a.total - 16.5) < 1e-9


# ---------------------------------------------------------------------------
# VersionStore pins: GC must never delete a version a worker holds
# ---------------------------------------------------------------------------

def test_gc_spares_pinned_versions(tmp_path, model):
    s = VersionStore(str(tmp_path / "v"))
    v1 = s.publish(model)
    v2 = s.publish(model)
    v3 = s.publish(model)
    s.pin(v1, "w0")
    s.pin(v1, "w1")
    assert s.pins(v1) == ["w0", "w1"]
    removed = s.gc(keep=1)
    # v2 is neither recent nor pinned -> gone; pinned v1 survives.
    assert removed == [v2]
    assert s.versions() == [v1, v3]
    s.load(v1)                            # still fully loadable
    # Dropping ONE of two pins is not enough...
    s.unpin(v1, "w0")
    assert s.gc(keep=1) == []
    assert v1 in s.versions()
    # ...dropping the last pin is.
    s.unpin(v1, "w1")
    assert s.gc(keep=1) == [v1]
    assert s.versions() == [v3]
    assert s.pins(v1) == []               # pin dir swept with the version


def test_pin_unpin_edge_cases(tmp_path, model):
    s = VersionStore(str(tmp_path / "v"))
    v1 = s.publish(model)
    with pytest.raises(FileNotFoundError):
        s.pin(v1 + 7, "w0")               # pinning a ghost raises loudly
    s.pin(v1, "w0")
    s.unpin(v1, "w0")
    s.unpin(v1, "w0")                     # idempotent
    s.unpin(v1 + 7, "w0")                 # unpinning a ghost is a no-op
    assert s.pins(v1) == []


def test_worker_pin_lifecycle_guards_gc(store, model_b):
    w = FleetWorker("w0", store, clock=FakeClock())
    v1 = w.version
    assert store.pins(v1) == ["w0"]
    v2 = store.publish(model_b)
    # The serving version is pinned: aggressive GC cannot take it.
    store.gc(keep=1)
    assert v1 in store.versions()
    w.swap_to(v2)                         # re-pin: new BEFORE old released
    assert store.pins(v2) == ["w0"] and store.pins(v1) == []
    assert store.gc(keep=1) == [v1]       # now v1 is fair game
    w.stop()
    assert store.pins(v2) == []           # retirement releases the pin


# ---------------------------------------------------------------------------
# Router
# ---------------------------------------------------------------------------

class StubWorker:
    def __init__(self, worker_id, depth=0):
        self.worker_id = worker_id
        self._depth = depth

    def depth(self):
        return self._depth


def test_least_loaded_routes_to_smallest_queue():
    ws = [StubWorker("a", 5), StubWorker("b", 2), StubWorker("c", 9)]
    r = Router(ws)
    assert r.route().worker_id == "b"
    ws[1]._depth = 100
    assert r.route().worker_id == "a"     # load signal is live, not cached
    ws[0]._depth = ws[2]._depth = 100
    assert r.route().worker_id == "a"     # ties break by id: deterministic


def test_hash_routing_is_sticky_and_covers_the_fleet():
    r = Router([StubWorker(f"w{i}") for i in range(4)], policy="hash")
    keys = [f"session-{i}" for i in range(400)]
    first = {k: r.route(k).worker_id for k in keys}
    assert {r.route(k).worker_id for k in keys} == set(first.values())
    assert first == {k: r.route(k).worker_id for k in keys}  # sticky
    # 64 vnodes keep every worker in rotation for 400 keys.
    assert len(set(first.values())) == 4


def test_hash_routing_remaps_only_the_removed_workers_keys():
    r = Router([StubWorker(f"w{i}") for i in range(4)], policy="hash")
    keys = [f"k{i}" for i in range(500)]
    before = {k: r.route(k).worker_id for k in keys}
    r.remove("w2")
    after = {k: r.route(k).worker_id for k in keys}
    moved = [k for k in keys if before[k] != after[k]]
    # The consistency property: ONLY keys that lived on w2 moved.
    assert all(before[k] == "w2" for k in moved)
    assert not any(after[k] == "w2" for k in keys)


def test_router_membership_errors():
    r = Router([StubWorker("a")])
    with pytest.raises(ValueError, match="duplicate"):
        r.add(StubWorker("a"))
    with pytest.raises(KeyError):
        r.remove("ghost")
    with pytest.raises(ValueError, match="routing key"):
        Router([StubWorker("a")], policy="hash").route()
    with pytest.raises(ValueError, match="policy"):
        Router([], policy="round-robin")
    with pytest.raises(RuntimeError, match="no workers"):
        Router([]).route()


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------

def test_static_cap_sheds_queue_full():
    ac = AdmissionController(max_queue_depth=10)
    assert ac.admit(StubWorker("a", depth=6), 4).worker_id == "a"
    with pytest.raises(ShedError) as ei:
        ac.admit(StubWorker("a", depth=7), 4)
    assert ei.value.reason == "queue-full"
    assert ei.value.depth == 7 and ei.value.limit == 10
    assert ac.admitted == 1 and ac.shed == 1 and ac.shed_rate == 0.5


def test_breaker_tightens_cap_until_p99_recovers():
    ac = AdmissionController(max_queue_depth=100, slo_ms=50.0,
                             shed_factor=0.5)
    assert ac.effective_depth() == 100
    assert ac.update(80.0) is True        # p99 over SLO: breaker opens
    assert ac.effective_depth() == 50
    with pytest.raises(ShedError) as ei:
        ac.admit(StubWorker("a", depth=60), 1)
    assert ei.value.reason == "slo-breach"
    assert ac.update(10.0) is False       # tail recovered: breaker closes
    assert ac.effective_depth() == 100
    ac.admit(StubWorker("a", depth=60), 1)
    assert ac.summary()["shed_by_reason"] == {"slo-breach": 1}


def test_admission_validates_construction():
    with pytest.raises(ValueError):
        AdmissionController(max_queue_depth=0)
    with pytest.raises(ValueError):
        AdmissionController(shed_factor=0.0)


# ---------------------------------------------------------------------------
# Per-bucket deadlines + the AIMD wait controller
# ---------------------------------------------------------------------------

def test_per_bucket_wait_overrides_the_flush_deadline(model):
    clock = FakeClock()
    ab = AsyncBatcher(model, max_wait_ms=5.0, clock=clock, max_bucket=128)
    ab.set_bucket_wait(8, 1.0)            # tiny requests flush fast
    ab.submit(_requests([3])[0])          # window coalesces to bucket 8
    clock.advance_ms(1.0)
    assert ab.due()                       # 1 ms: the OVERRIDE applies
    assert ab.poll() == 1
    # A window that grows into an un-overridden bucket keeps the default.
    ab.submit(_requests([3])[0])
    ab.submit(_requests([30])[0])         # now coalesces to bucket 64
    clock.advance_ms(2.0)
    assert not ab.due()                   # default 5 ms still governs
    clock.advance_ms(3.0)
    assert ab.poll() == 2
    assert ab.bucket_wait(8) == 1.0 and ab.bucket_wait(64) == 5.0
    with pytest.raises(ValueError):
        ab.set_bucket_wait(8, 0.0)


def test_controller_decreases_wait_on_breached_bucket(store):
    clock = FakeClock()
    w = FleetWorker("w0", store, max_wait_ms=8.0, slo_ms=200.0, clock=clock)
    ctl = AdaptiveWaitController(200.0, min_samples=1, min_wait_ms=0.25)
    # One slow request: 150 ms total >> budget (200 * 0.5 = 100 ms).
    w.submit(_requests([3])[0])
    clock.advance_ms(150.0)
    w.flush()
    (adj,) = ctl.step(w)
    assert adj["action"] == "decrease"
    assert adj["wait_after_ms"] == 4.0    # multiplicative: 8 -> 4
    assert w.scheduler().bucket_wait(adj["bucket"]) == 4.0
    # No fresh traffic since the decision: the controller holds.
    assert ctl.step(w) == []
    # Repeated breaches floor at min_wait_ms, never zero.
    for _ in range(12):
        w.submit(_requests([3])[0])
        clock.advance_ms(150.0)
        w.flush()
        ctl.step(w)
    assert w.scheduler().bucket_wait(adj["bucket"]) == 0.25
    w.stop()


def test_controller_increases_wait_on_comfortable_bucket(store):
    clock = FakeClock()
    w = FleetWorker("w0", store, max_wait_ms=2.0, slo_ms=200.0, clock=clock)
    ctl = AdaptiveWaitController(200.0, min_samples=8, increase_ms=0.5,
                                 max_wait_ms=3.0)
    for _ in range(8):                    # fast traffic: ~1 ms latencies
        w.submit(_requests([3])[0])
        clock.advance_ms(1.0)
        w.flush()
    (adj,) = ctl.step(w)
    assert adj["action"] == "increase"
    assert adj["wait_after_ms"] == 2.5    # additive: one step per period
    # Creep is capped at the controller's max.
    for _ in range(4):
        for _ in range(8):
            w.submit(_requests([3])[0])
            clock.advance_ms(1.0)
            w.flush()
        ctl.step(w)
    assert w.scheduler().bucket_wait(adj["bucket"]) == 3.0
    w.stop()


def test_controller_needs_min_samples_before_acting(store):
    clock = FakeClock()
    w = FleetWorker("w0", store, max_wait_ms=2.0, slo_ms=200.0, clock=clock)
    ctl = AdaptiveWaitController(200.0, min_samples=8)
    for _ in range(7):                    # one short of the window
        w.submit(_requests([3])[0])
        clock.advance_ms(1.0)
        w.flush()
    assert ctl.step(w) == []
    with pytest.raises(ValueError):
        AdaptiveWaitController(0.0)
    with pytest.raises(ValueError):
        AdaptiveWaitController(100.0, decrease_factor=1.0)
    w.stop()


# ---------------------------------------------------------------------------
# Rollouts: canary-then-promote, rollback on breach
# ---------------------------------------------------------------------------

def test_rollout_promotes_canary_first_then_fleet(store, model_b):
    clock = FakeClock()
    workers = [FleetWorker(f"w{i}", store, clock=clock) for i in range(3)]
    v1 = workers[0].version
    v2 = store.publish(model_b)
    seen = []
    mgr = RolloutManager(workers, store, budget_ms=100.0,
                         probe=lambda w: seen.append(
                             [x.version for x in workers]) or 0.0)
    rep = mgr.rollout()
    assert rep.promoted and rep.state == "done"
    assert [s for s, _ in rep.timeline] == \
        ["canary", "probing", "promoting", "done"]
    # At probe time ONLY the canary had swapped — the blast radius.
    assert seen == [[v2, v1, v1]]
    assert all(w.version == v2 for w in workers)
    assert rep.old_versions == {"w0": v1, "w1": v1, "w2": v1}
    assert set(rep.swaps) == {f"w{i}->v{v2}" for i in range(3)}
    # Idempotent: a second rollout to the same target is a no-op.
    assert mgr.rollout() is None
    for w in workers:
        w.stop()


def test_breached_probe_rolls_back_and_restores_version(store, model_b):
    clock = FakeClock()
    workers = [FleetWorker(f"w{i}", store, clock=clock) for i in range(2)]
    v1 = workers[0].version
    v2 = store.publish(model_b)
    mgr = RolloutManager(workers, store, budget_ms=100.0,
                         probe=lambda w: 350.0)   # injected breach
    # Pending traffic on BOTH workers across the failed rollout.
    pend = [w.submit(r) for w in workers for r in _requests([4])]
    rep = mgr.rollout(v2)
    for w in workers:
        w.flush()
    assert not rep.promoted and rep.state == "rolled-back"
    assert [s for s, _ in rep.timeline] == ["canary", "probing",
                                            "rolled-back"]
    assert all(w.version == v1 for w in workers)   # prior version restored
    assert rep.canary_p95_ms == 350.0
    # The canary swapped out AND back; the follower never moved.
    assert set(rep.swaps) == {f"w0->v{v2}", f"w0->v{v1}"}
    assert sum(not f.done() for f in pend) == 0    # zero stranded futures
    assert v2 in store.versions()                  # target intact for retry
    assert store.pins(v1) == ["w0", "w1"]          # guard pin released
    for w in workers:
        w.stop()


def test_single_worker_rollback_survives_concurrent_gc(store, model_b):
    # The canary's own swap releases its pin on the outgoing version; on
    # a 1-worker fleet the manager's guard pin is all that stops a GC
    # during probing from deleting the rollback target.
    clock = FakeClock()
    w = FleetWorker("w0", store, clock=clock)
    v1 = w.version
    v2 = store.publish(model_b)

    def probe_with_gc(worker):
        store.gc(keep=1)                  # hostile GC mid-decision
        return 999.0                      # then the probe breaches

    rep = RolloutManager([w], store, budget_ms=10.0).rollout(
        v2, probe=probe_with_gc)
    assert rep.state == "rolled-back" and w.version == v1
    np.testing.assert_array_equal(
        np.asarray(store.load(v1).centroids),
        np.asarray(store.load(w.version).centroids))
    w.stop()


# ---------------------------------------------------------------------------
# Fleet front door, end to end
# ---------------------------------------------------------------------------

def test_fleet_routed_labels_match_direct_assignment(store, model):
    clock = FakeClock()
    with Fleet(store, n_workers=3, clock=clock, max_wait_ms=2.0) as fleet:
        reqs = _requests([5, 17, 2, 31, 9, 24], seed=3)
        futs = [fleet.submit(r) for r in reqs]
        assert fleet.depth() == sum(r.shape[1] for r in reqs)
        fleet.flush()
        got = np.concatenate([f.result()[0] for f in futs])
        want, _ = assign(model, np.concatenate(reqs, axis=1))
        np.testing.assert_array_equal(got, np.asarray(want))
        assert fleet.latency().requests == len(reqs)


def test_fleet_overload_sheds_but_keeps_admitted_p99_in_slo(store):
    clock = FakeClock()
    fleet = Fleet(store, n_workers=2, max_queue_depth=8, slo_ms=250.0,
                  clock=clock, max_wait_ms=2.0)
    futs, shed = [], 0
    for r in _requests([4] * 32, seed=5):
        clock.advance_ms(1.0)             # queue wait accrues, bounded
        try:
            futs.append(fleet.submit(r))
        except ShedError as e:
            assert e.reason == "queue-full"
            shed += 1
    fleet.flush()
    assert shed > 0                              # the flood DID shed
    assert len(futs) == 4                        # 2 workers x depth 8 / 4
    assert sum(not f.done() for f in futs) == 0  # admitted all resolved
    stats = fleet.latency()
    assert stats.total.percentile(99.0) <= 250.0
    assert stats.slo_violations == 0
    assert fleet.admission.shed_rate == shed / 32
    assert fleet.stats()["admission"]["shed_by_reason"] == \
        {"queue-full": shed}
    fleet.stop()


def test_fleet_control_loop_closes_both_feedbacks(store):
    clock = FakeClock()
    fleet = Fleet(store, n_workers=2, slo_ms=100.0, max_queue_depth=100,
                  clock=clock, max_wait_ms=2.0)
    for r in _requests([3] * 4):
        fleet.submit(r)
        clock.advance_ms(3.0)             # past every deadline
    ctl = fleet.control()
    assert ctl["completed"] == 4          # poll flushed the due windows
    assert ctl["breaker_open"] is False   # fake-clock latencies are tiny
    assert ctl["p99_ms"] <= 100.0
    # Force a breach through the same path the tier p99 feeds.
    fleet.admission.update(500.0)
    with pytest.raises(ShedError) as ei:
        # Effective cap is 50; a 60-wide request cannot be admitted even
        # onto an empty worker.
        fleet.submit(_requests([60])[0])
    assert ei.value.reason == "slo-breach"
    fleet.stop()


def test_fleet_rollout_and_sync_follow_the_store(store, model, model_b):
    clock = FakeClock()
    fleet = Fleet(store, n_workers=2, clock=clock, rollout_budget_ms=100.0)
    assert fleet.sync() is None           # already at latest
    r = _requests([16], seed=9)[0]
    pending = fleet.submit(r)             # still queued when the rollout runs
    v2 = store.publish(model_b)
    rep = fleet.sync()                    # follower mode picks it up
    assert rep is not None and rep.promoted
    assert fleet.stats()["versions"] == {"w0": v2, "w1": v2}
    # The swap drained the queued request into the version it was sent to.
    assert pending.done()
    np.testing.assert_array_equal(pending.result()[0],
                                  np.asarray(assign(model, r)[0]))
    # Labels prove the new version serves: permuted centroids flip them.
    fut = fleet.submit(r)
    fleet.flush()
    want_new, _ = assign(model_b, r)
    want_old, _ = assign(model, r)
    np.testing.assert_array_equal(fut.result()[0], np.asarray(want_new))
    assert not np.array_equal(np.asarray(want_new), np.asarray(want_old))
    fleet.stop()
    assert all(store.pins(v) == [] for v in store.versions())
