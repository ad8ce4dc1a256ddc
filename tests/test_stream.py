"""Streaming subsystem (repro.stream): partial_fit/fit bit-parity at the
re-eig boundary, artifact resume, drift detection, minibatch K-means, the
int8 artifact codec, and the end-to-end drift -> refit -> publish -> swap
loop under async traffic."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KernelKMeans
from repro.core.kmeans import kmeans
from repro.core.metrics import clustering_accuracy
from repro.core.sketch import make_srht, srht_apply_t, srht_rows_at
from repro.data import blob_ring
from repro.distributed.compression import (dequantize_state, int8_decode,
                                           int8_encode, quantize_state)
from repro.serve import (MicroBatcher, ModelRegistry, VersionStore,
                         load_model, save_model)
from repro.stream import (DriftMonitor, RetrainWorker, SketchAccumulator,
                          minibatch_kmeans)

N, P, R, K, BLOCK = 250, 2, 2, 2, 64

_POLY = dict(k=K, r=R, kernel="polynomial",
             kernel_params={"gamma": 0.0, "degree": 2}, block=BLOCK)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance_ms(self, ms):
        self.t += ms / 1e3


def _assert_models_equal(a, b):
    """Every FittedModel leaf bit-identical (spec by equality)."""
    assert a.spec == b.spec
    for name, va in a._asdict().items():
        if name == "spec":
            continue
        vb = getattr(b, name)
        if va is None or vb is None:
            assert va is None and vb is None, name
        else:
            np.testing.assert_array_equal(np.asarray(va), np.asarray(vb),
                                          err_msg=name)


def _blobs_1d(rng, xs, n_per, sigma=0.25):
    """1-d-separable 2-row blobs at the given x centers -> (X, labels)."""
    cols, labels = [], []
    for i, x0 in enumerate(xs):
        c = np.zeros((2, n_per), np.float32)
        c[0] = x0 + sigma * rng.standard_normal(n_per)
        c[1] = sigma * rng.standard_normal(n_per)
        cols.append(c)
        labels.append(np.full(n_per, i))
    return np.concatenate(cols, axis=1), np.concatenate(labels)


# ---------------------------------------------------------------------------
# partial_fit parity with one-shot fit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["onepass-srht", "onepass-gaussian"])
def test_partial_fit_bit_identical_to_fit(backend):
    """Chunked partial_fit over a full pass == fit at the re-eig boundary
    — bit-for-bit, including a ragged final chunk (N=250 is not a
    multiple of BLOCK=64, and the chunk edges are not block-aligned)."""
    X, _ = blob_ring(jax.random.PRNGKey(0), n=N)
    ref = KernelKMeans(backend=backend, **_POLY).fit(X, key=7)
    est = KernelKMeans(backend=backend, **_POLY)
    for lo, hi in [(0, 100), (100, 164), (164, N)]:
        est.partial_fit(X[:, lo:hi], key=7, capacity=N, reeig=(hi == N))
    _assert_models_equal(est.model_, ref.model_)
    np.testing.assert_array_equal(np.asarray(est.labels_),
                                  np.asarray(ref.labels_))
    assert est.inertia_ == ref.inertia_
    # The one-shot fit carries the same streaming slab (resumable too):
    # full blocks applied, the ragged tail staged, capacity recorded.
    assert ref.model_.stream_counts is not None
    np.testing.assert_array_equal(np.asarray(ref.model_.stream_counts),
                                  [(N // BLOCK) * BLOCK, N])


def test_partial_fit_chunking_invariant():
    """Two different chunkings of the same pass agree bit-for-bit."""
    X, _ = blob_ring(jax.random.PRNGKey(2), n=N)
    a = KernelKMeans(**_POLY)
    for lo, hi in [(0, 3), (3, 131), (131, N)]:
        a.partial_fit(X[:, lo:hi], key=11, capacity=N, reeig=(hi == N))
    b = KernelKMeans(**_POLY)
    b.partial_fit(X, key=11, capacity=N)
    _assert_models_equal(a.model_, b.model_)


def test_partial_fit_first_call_contract():
    X, _ = blob_ring(jax.random.PRNGKey(0), n=64)
    with pytest.raises(ValueError, match="capacity"):
        KernelKMeans(**_POLY).partial_fit(X, key=0)
    with pytest.raises(ValueError, match="one-pass"):
        KernelKMeans(k=K, r=R, backend="nystrom",
                     backend_params={"m": 16}).partial_fit(
                         X, key=0, capacity=64)


def test_partial_fit_accumulates_without_reeig():
    X, _ = blob_ring(jax.random.PRNGKey(1), n=N)
    est = KernelKMeans(**_POLY)
    est.partial_fit(X[:, :100], key=4, capacity=N, reeig=False)
    assert est.model_ is None                      # cheap steady state
    prog = est.stream_progress
    assert prog["n_added"] == 100 and prog["capacity"] == N
    assert prog["n_applied"] == 64 and prog["n_pending"] == 36
    assert prog["reeigs"] == 0
    est.partial_fit(X[:, 100:], reeig=True)
    prog = est.stream_progress
    assert prog["n_added"] == N and prog["reeigs"] == 1
    assert 0.0 <= prog["approx_err_estimate"] <= 1.0
    assert est.model_ is not None and est.labels_.shape == (N,)


def test_accumulator_capacity_guard():
    X, _ = blob_ring(jax.random.PRNGKey(0), n=64)
    est = KernelKMeans(**_POLY)
    est.partial_fit(X, key=0, capacity=64)
    with pytest.raises(ValueError, match="capacity"):
        est.partial_fit(X[:, :1])


# ---------------------------------------------------------------------------
# artifact round-trip and resume
# ---------------------------------------------------------------------------

def test_stream_state_roundtrips_and_resumes(tmp_path):
    """save -> load -> partial_fit continues bit-identically to the live
    estimator that never went through the artifact."""
    X, _ = blob_ring(jax.random.PRNGKey(3), n=N)
    live = KernelKMeans(**_POLY)
    live.partial_fit(X[:, :150], key=5, capacity=N)
    path = str(tmp_path / "ckpt")
    save_model(live.model_, path)
    meta = json.loads((pathlib.Path(path) / "leaves.json").read_text())
    for leaf in ("stream_w", "stream_row_norms2", "stream_counts"):
        assert leaf in meta["names"]

    resumed = KernelKMeans.load(path)
    live.partial_fit(X[:, 150:])
    resumed.partial_fit(X[:, 150:], key=5)
    _assert_models_equal(resumed.model_, live.model_)
    # And both equal the one-shot fit over all N columns.
    ref = KernelKMeans(**_POLY).fit(X, key=5)
    _assert_models_equal(resumed.model_, ref.model_)


def test_accumulator_from_model_requires_stream_state():
    X, _ = blob_ring(jax.random.PRNGKey(0), n=64)
    est = KernelKMeans(**_POLY).fit(X, key=0)
    stripped = est.model_._replace(stream_w=None, stream_row_norms2=None,
                                   stream_counts=None)
    with pytest.raises(ValueError, match="stream"):
        SketchAccumulator.from_model(stripped)


def test_srht_rows_matches_dense_apply():
    """Materialized Omega rows == the historical transform applied to the
    identity — the cross-term path reuses the exact same operator."""
    n = 37
    srht = make_srht(jax.random.PRNGKey(9), n, 16)
    dense = srht_apply_t(srht, jnp.eye(n, dtype=jnp.float32)).T  # (n, r')
    def rows(start, stop):
        return srht_rows_at(jnp.arange(start, stop, dtype=jnp.int32),
                            srht.signs[start:stop], srht.rows, srht.n_pad)
    np.testing.assert_array_equal(np.asarray(rows(0, n)), np.asarray(dense))
    np.testing.assert_array_equal(np.asarray(rows(5, 21)),
                                  np.asarray(dense[5:21]))


# ---------------------------------------------------------------------------
# drift monitor
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lin_est():
    rng = np.random.default_rng(0)
    X0, y0 = _blobs_1d(rng, (-2.0, 2.0), 100)
    est = KernelKMeans(k=2, r=2, kernel="linear", backend="onepass-srht",
                      block=BLOCK)
    est.partial_fit(X0, key=3, capacity=400)
    return est, X0, y0


def test_drift_monitor_quiet_on_reference_traffic(lin_est):
    est, X0, _ = lin_est
    mon = DriftMonitor(est.model_, ref_labels=est.labels_, min_queries=50)
    for lo in range(0, 200, 40):
        mon.observe(X0[:, lo:lo + 40])
    rep = mon.report()
    assert rep.queries == 200 and rep.samples == 200
    assert not rep.fired and rep.reason == "no drift"
    assert rep.chi2 < 10.0 and rep.max_frac_delta < 0.1


def test_drift_monitor_fires_on_assignment_shift(lin_est):
    est, X0, _ = lin_est
    mon = DriftMonitor(est.model_, ref_labels=est.labels_, min_queries=50)
    # All traffic served the same label: a total population collapse.
    for lo in range(0, 200, 40):
        mon.observe(X0[:, lo:lo + 40], labels=np.zeros(40, np.int32))
    rep = mon.report()
    assert rep.assign_fired and rep.fired
    assert "assignment shift" in rep.reason
    assert rep.chi2 > mon.chi2_threshold
    assert rep.live_fracs == [1.0, 0.0]
    # Below min_queries the same skew stays quiet.
    mon.reset_window()
    mon.observe(X0[:, :40], labels=np.zeros(40, np.int32))
    assert not mon.report().fired
    d = rep.to_dict()
    assert d["fired"] and isinstance(d["live_fracs"], list)


def test_drift_monitor_derives_ref_labels_and_samples_every(lin_est):
    est, X0, _ = lin_est
    mon = DriftMonitor(est.model_, min_queries=50, sample_every=2)
    assert abs(sum(mon.ref_fracs) - 1.0) < 1e-9
    np.testing.assert_allclose(mon.ref_fracs, [0.5, 0.5], atol=0.05)
    for lo in range(0, 160, 40):                  # 4 calls, 2 sampled
        mon.observe(X0[:, lo:lo + 40])
    rep = mon.report()
    assert rep.queries == 160 and rep.samples == 80


def test_drift_monitor_approx_error_trigger():
    """RBF model: on-support queries keep the kernel-column residual
    small; off-support queries land outside the rank-r eigenbasis and
    push p95 over the threshold."""
    rng = np.random.default_rng(1)
    X0, _ = _blobs_1d(rng, (-2.0, 2.0), 100, sigma=0.3)
    est = KernelKMeans(k=2, r=4, kernel="rbf", kernel_params={"gamma": 0.5},
                      backend="onepass-srht", block=BLOCK)
    est.fit(X0, key=2)
    mon = DriftMonitor(est.model_, ref_labels=est.labels_,
                       approx_err_threshold=0.5, min_queries=10 ** 9)
    Xq, _ = _blobs_1d(rng, (-2.0, 2.0), 64, sigma=0.3)
    mon.observe(Xq)
    quiet = mon.report()
    assert not quiet.fired and quiet.approx_err_p95 < 0.5
    mon.reset_window()
    Xfar = np.stack([rng.normal(0.0, 0.3, 64),
                     rng.normal(6.0, 0.3, 64)]).astype(np.float32)
    mon.observe(Xfar)
    rep = mon.report()
    assert rep.approx_fired and rep.fired and "approx-err" in rep.reason
    assert rep.approx_err_p95 > quiet.approx_err_p95


def test_sample_serving_stats_preserves_buckets(lin_est):
    est, X0, _ = lin_est
    mb = MicroBatcher(est.model_, min_bucket=8)
    mb.assign_batch(X0[:, :10])
    mon = DriftMonitor(est.model_, ref_labels=est.labels_)
    snap = mon.sample_serving_stats(mb)
    assert snap["queries"] == 10 and snap["bucket_hits"] == {16: 1}
    # Counters reset, but the executables view (what a warm hot-swap
    # replays) survives the sample.
    assert mb.stats["queries"] == 0 and mb.stats["bucket_hits"] == {16: 0}
    assert mb.executables == [16]
    mb.reset_stats()                              # full reset drops them
    assert mb.executables == []


# ---------------------------------------------------------------------------
# minibatch K-means
# ---------------------------------------------------------------------------

def test_minibatch_kmeans_tracks_full_quality():
    key = jax.random.PRNGKey(4)
    centers = jnp.array([[0.0, 0.0], [6.0, 6.0], [-6.0, 5.0]])
    idx = jax.random.randint(key, (600,), 0, 3)
    pts = centers[idx] + 0.4 * jax.random.normal(
        jax.random.PRNGKey(5), (600, 2))
    full = kmeans(jax.random.PRNGKey(6), pts, 3, n_restarts=5, max_iter=30)
    mb = minibatch_kmeans(jax.random.PRNGKey(6), pts, 3, 128, 80)
    assert mb.labels.shape == (600,) and mb.centroids.shape == (3, 2)
    assert int(mb.n_steps) == 80
    assert float(mb.objective) <= 1.5 * float(full.objective)
    # jit + explicit key: bit-deterministic across calls.
    mb2 = minibatch_kmeans(jax.random.PRNGKey(6), pts, 3, 128, 80)
    np.testing.assert_array_equal(np.asarray(mb.labels),
                                  np.asarray(mb2.labels))


def test_partial_fit_minibatch_mode():
    X, _ = blob_ring(jax.random.PRNGKey(7), n=N)
    est = KernelKMeans(**_POLY)
    est.partial_fit(X, key=8, capacity=N, kmeans_mode="minibatch",
                    minibatch_size=64, minibatch_steps=40)
    assert est.labels_.shape == (N,) and np.isfinite(est.inertia_)
    assert est.model_ is not None
    assert est.predict(X[:, :16]).shape == (16,)
    with pytest.raises(ValueError, match="kmeans_mode"):
        est.reeig_now(kmeans_mode="nope")


# ---------------------------------------------------------------------------
# int8 artifact codec
# ---------------------------------------------------------------------------

def test_int8_codec_roundtrip():
    x = jnp.asarray(np.linspace(-3.0, 5.0, 97, dtype=np.float32))
    q, scale = int8_encode(x)
    assert q.dtype == jnp.int8 and scale == pytest.approx(5.0 / 127.0)
    rt = int8_decode(q, scale)
    assert float(jnp.max(jnp.abs(rt - x))) <= scale / 2 + 1e-7
    qz, sz = int8_encode(jnp.zeros(5))            # all-zero leaf
    assert sz == 1.0 and not np.any(np.asarray(qz))

    state = {"w": x, "idx": jnp.arange(4, dtype=jnp.int32)}
    enc, quantized = quantize_state(state, dtype="int8")
    assert quantized["w"]["codec"] == "int8" and "idx" not in quantized
    assert enc["idx"].dtype == jnp.int32          # ints pass through
    dec = dequantize_state(enc, quantized)
    assert float(jnp.max(jnp.abs(dec["w"] - x))) <= scale / 2 + 1e-7
    # Legacy bare-string bf16 entries still decode.
    enc16, q16 = quantize_state({"w": x}, dtype="bf16")
    assert q16 == {"w": "bf16"}
    assert np.allclose(dequantize_state(enc16, q16)["w"], x, atol=0.05)
    with pytest.raises(ValueError, match="unknown quantized dtype"):
        quantize_state(state, dtype="fp4")


def test_int8_artifact_serves(tmp_path, lin_est):
    est, X0, y0 = lin_est
    path = save_model(est.model_, str(tmp_path / "int8"), dtype="int8")
    meta = json.loads((pathlib.Path(path) / "leaves.json").read_text())
    assert meta["quantized"]["U"]["codec"] == "int8"
    assert "stream_counts" not in meta["quantized"]
    m2 = load_model(path)
    assert m2.stream_counts.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(m2.stream_counts),
                                  np.asarray(est.model_.stream_counts))
    ref = est.predict(X0)
    got = KernelKMeans.from_model(m2).predict(X0)
    assert float(np.mean(ref == got)) >= 0.95


# ---------------------------------------------------------------------------
# end-to-end: drift -> refit -> publish -> swap under async traffic
# ---------------------------------------------------------------------------

def test_e2e_stream_drift_refit_swap(tmp_path):
    rng = np.random.default_rng(42)
    X0, _ = _blobs_1d(rng, (-2.0, 2.0), 100)      # initial distribution
    Xd, yd = _blobs_1d(rng, (3.0, 8.0), 100)      # drifted distribution

    est = KernelKMeans(k=2, r=2, kernel="linear", backend="onepass-srht",
                      block=BLOCK)
    est.partial_fit(X0, key=3, capacity=400)
    # The stale model collapses the drifted blobs onto one centroid.
    stale_acc = clustering_accuracy(yd, est.predict(Xd), 2)
    assert stale_acc <= 0.75

    store = VersionStore(str(tmp_path / "store"), keep=4)
    reg = ModelRegistry()
    reg.register("stream-demo", est.model_, version=store.publish(est.model_))
    clock = FakeClock()
    sched_kwargs = dict(max_wait_ms=5.0, clock=clock)
    sched = reg.scheduler("stream-demo", **sched_kwargs)
    mon = DriftMonitor(est.model_, ref_labels=est.labels_,
                       min_queries=50, chi2_threshold=30.0)

    def refit(report):
        assert report.fired
        est.partial_fit(Xd)                       # fold the drifted window
        return est.model_

    worker = RetrainWorker("stream-demo", reg, store, mon, refit)

    # Healthy traffic (shuffled, so each batch mixes both clusters): the
    # monitor observes the served labels, nothing fires.
    Xh = X0[:, rng.permutation(X0.shape[1])]
    healthy = [Xh[:, lo:lo + 20] for lo in range(0, 100, 20)]
    futs = [sched.submit(ch) for ch in healthy]
    sched.flush()
    for ch, f in zip(healthy, futs):
        mon.observe(ch, f.result(timeout=5)[0])
    assert worker.step() is None and worker.checks == 1

    # Drifted traffic through the same async front door.
    drifted = [Xd[:, lo:lo + 20] for lo in range(0, 200, 20)]
    futs = [sched.submit(ch) for ch in drifted]
    sched.flush()
    for ch, f in zip(drifted, futs):
        mon.observe(ch, f.result(timeout=5)[0])
    # One request still pending when the rollout begins: the swap must
    # drain it against the OLD model, never strand it.
    pending = sched.submit(Xd[:, :8])

    out = worker.step()
    assert out is not None and worker.retrains == 1
    assert out.version == 2 and out.drift.assign_fired
    assert out.swap.old_version == 1 and out.swap.new_version == 2
    assert out.swap.drained_requests == 1
    assert out.detect_to_swap_s >= 0.0
    assert pending.done() and pending.result()[0].shape == (8,)
    stranded = [f for f in futs + [pending] if not f.done()]
    assert stranded == []
    assert sched.stopped                          # old handle retired
    with pytest.raises(RuntimeError, match="stopped"):
        sched.submit(Xd[:, :4])

    # Window was rebound to the new model: no immediate re-fire.
    assert worker.step() is None

    # The registry now serves the refit version, warm.
    assert reg.version("stream-demo") == 2 and store.latest() == 2
    new_sched = reg.scheduler("stream-demo", **sched_kwargs)
    assert new_sched is not sched
    f = new_sched.submit(Xd[:, :16])
    new_sched.flush()
    assert f.result(timeout=5)[0].shape == (16,)
    new_acc = clustering_accuracy(yd, KernelKMeans.from_model(
        reg.get("stream-demo")).predict(Xd), 2)
    assert new_acc >= 0.95 and new_acc > stale_acc + 0.2
    d = out.to_dict()
    assert d["swap"]["drained_requests"] == 1 and d["drift"]["fired"]
