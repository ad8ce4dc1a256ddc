"""Mesh-sharded one-pass fit (repro.distributed.fit) vs single-host.

The engine's contract is BIT-identity on a 1-device mesh: fit and
partial_fit under `ComputePolicy(mesh=...)` must reproduce the canonical
SketchAccumulator path exactly — same W, same row norms, same eig, same
labels — for both one-pass backends, under ragged chunk schedules, and
when resuming from a published artifact. The multi-device variant of the
same checks runs via subprocess under XLA_FLAGS in test_distributed.py
(tests/fit_dist_checks.py).

Also here: the ComputePolicy legacy-kwarg shims (DeprecationWarning +
bit-identical behavior) and partial_fit's fail-fast chunk validation.
"""
import warnings

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.api import KernelKMeans
from repro.data import blob_ring
from repro.serve import ComputePolicy
from repro.serve.extend import Extender

N, BLOCK = 96, 32

_POLY = dict(k=2, r=2, kernel="polynomial",
             kernel_params={"gamma": 0.0, "degree": 2}, block=BLOCK)
BACKENDS = ["onepass-srht", "onepass-gaussian"]


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


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


@pytest.fixture(scope="module")
def blobs():
    X, labels = blob_ring(jax.random.PRNGKey(0), n=N)
    return np.asarray(X, np.float32), labels


# ---------------------------------------------------------------------------
# bit-identity: sharded fit == single-host fit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_sharded_fit_bit_identical(blobs, backend):
    X, _ = blobs
    ref = KernelKMeans(backend=backend, **_POLY).fit(X, key=7)
    sh = KernelKMeans(backend=backend, **_POLY,
                      policy=ComputePolicy(mesh=_mesh1())).fit(X, key=7)
    _assert_models_equal(ref.model_, sh.model_)
    np.testing.assert_array_equal(np.asarray(ref.labels_),
                                  np.asarray(sh.labels_))
    np.testing.assert_array_equal(np.asarray(ref.embedding_),
                                  np.asarray(sh.embedding_))


@pytest.mark.parametrize("backend", BACKENDS)
def test_sharded_partial_fit_ragged_chunks(blobs, backend):
    """Chunked sharded ingest == one-shot single-host fit at the re-eig
    boundary, with chunk edges NOT aligned to the block size (the engine
    stages partial blocks exactly like the canonical accumulator)."""
    X, _ = blobs
    ref = KernelKMeans(backend=backend, **_POLY).fit(X, key=7)
    est = KernelKMeans(backend=backend, **_POLY,
                       policy=ComputePolicy(mesh=_mesh1()))
    edges = [0, 40, 73, N]           # ragged: 40, 33, 23 columns
    for lo, hi in zip(edges[:-1], edges[1:]):
        est.partial_fit(X[:, lo:hi], key=7, capacity=N,
                        reeig=(hi == N))
    _assert_models_equal(ref.model_, est.model_)
    np.testing.assert_array_equal(np.asarray(ref.labels_),
                                  np.asarray(est.labels_))


@pytest.mark.parametrize("backend", BACKENDS)
def test_sharded_resume_from_artifact(tmp_path, blobs, backend):
    """Publish mid-stream, resume under a mesh: identical to resuming
    single-host (the engine re-ingests the persisted columns)."""
    X, _ = blobs
    first, rest = X[:, :64], X[:, 64:]

    def start():
        est = KernelKMeans(backend=backend, **_POLY)
        est.partial_fit(first, key=7, capacity=N)
        return est

    path = str(tmp_path / f"art-{backend}")
    start().save(path)

    single = KernelKMeans.load(path)
    single.partial_fit(rest, key=7)
    sharded = KernelKMeans.load(path)
    sharded.policy = ComputePolicy(mesh=_mesh1())
    sharded.partial_fit(rest, key=7)
    _assert_models_equal(single.model_, sharded.model_)


# ---------------------------------------------------------------------------
# partial_fit fail-fast validation
# ---------------------------------------------------------------------------

def test_partial_fit_rejects_wrong_feature_dim(blobs):
    X, _ = blobs
    est = KernelKMeans(**_POLY)
    est.partial_fit(X[:, :BLOCK], key=0, capacity=N, reeig=False)
    with pytest.raises(ValueError, match="feature"):
        est.partial_fit(X[:1, BLOCK:2 * BLOCK], reeig=False)
    with pytest.raises(ValueError, match="2-D"):
        est.partial_fit(X[:, 0], reeig=False)


def test_partial_fit_rejects_policy_swap_mid_stream(blobs):
    X, _ = blobs
    est = KernelKMeans(**_POLY)
    est.partial_fit(X[:, :BLOCK], key=0, capacity=N, reeig=False)
    est.policy = ComputePolicy(mesh=_mesh1())
    with pytest.raises(ValueError, match="ComputePolicy"):
        est.partial_fit(X[:, BLOCK:2 * BLOCK], reeig=False)


def test_partial_fit_rejects_wrong_dim_against_loaded_model(tmp_path,
                                                           blobs):
    X, _ = blobs
    est = KernelKMeans(**_POLY)
    est.partial_fit(X[:, :64], key=0, capacity=N)
    path = str(tmp_path / "art")
    est.save(path)
    resumed = KernelKMeans.load(path)
    with pytest.raises(ValueError, match="feature"):
        resumed.partial_fit(X[:1, 64:], reeig=False)


# ---------------------------------------------------------------------------
# ComputePolicy legacy-kwarg shims
# ---------------------------------------------------------------------------

def test_legacy_kwargs_warn_and_match_policy(blobs):
    X, _ = blobs
    est = KernelKMeans(**_POLY).fit(X, key=7)
    Xq = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (2, 17)),
                    np.float32)
    with pytest.warns(DeprecationWarning, match="ComputePolicy"):
        legacy = Extender(est.model_, fused=True, interpret=True)
    policy = Extender(est.model_, policy=ComputePolicy(embed_fused=True,
                                                       interpret=True))
    np.testing.assert_array_equal(np.asarray(legacy.embed(Xq)),
                                  np.asarray(policy.embed(Xq)))


def test_legacy_kwargs_plus_policy_is_ambiguous(blobs):
    X, _ = blobs
    est = KernelKMeans(**_POLY).fit(X, key=7)
    with pytest.raises(ValueError, match="policy"):
        Extender(est.model_, fused=True, interpret=True,
                 policy=ComputePolicy())


def test_no_legacy_kwargs_no_warning(blobs):
    X, _ = blobs
    est = KernelKMeans(**_POLY).fit(X, key=7)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        Extender(est.model_)
        Extender(est.model_, policy=ComputePolicy())


def test_policy_rejects_explicit_mesh_axis():
    """jax.make_mesh defaults to Explicit axes; the sharded paths are
    written for Auto sharding, so the policy says so up front."""
    from repro.serve import data_mesh
    explicit = jax.make_mesh((1,), ("data",),
                             axis_types=(jax.sharding.AxisType.Explicit,))
    with pytest.raises(ValueError, match="Explicit"):
        ComputePolicy(mesh=explicit)
    assert ComputePolicy(mesh=data_mesh(jax.devices()[:1])).shards == 1


# ---------------------------------------------------------------------------
# fused fit path (fp tolerance, interpret mode)
# ---------------------------------------------------------------------------

def test_fit_fused_policy_close_to_canonical(blobs):
    X, _ = blobs
    ref = KernelKMeans(**_POLY).fit(X, key=7)
    fused = KernelKMeans(**_POLY, policy=ComputePolicy(
        fit_fused=True, interpret=True)).fit(X, key=7)
    np.testing.assert_allclose(np.asarray(ref.model_.stream_w),
                               np.asarray(fused.model_.stream_w),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(ref.eigvals_),
                               np.asarray(fused.eigvals_),
                               rtol=2e-3, atol=2e-3)


def test_fit_fused_requires_statics_through_accumulator():
    from repro.core.kernels_fn import make_kernel
    from repro.stream.accumulate import SketchAccumulator
    with pytest.raises(ValueError, match="kernel_statics"):
        SketchAccumulator(jax.random.PRNGKey(0),
                          make_kernel("polynomial", gamma=0.0, degree=2),
                          64, 2, policy=ComputePolicy(fit_fused=True,
                                                      interpret=True))
