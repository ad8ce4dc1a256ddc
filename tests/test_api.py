"""repro.api: backend parity, the estimator front door, serve round-trip.

The ISSUE-5 acceptance surface:
  - all four registered backends within tolerance of each other (and of
    the exact ceiling) on clustering accuracy + kernel approx error,
    through the ONE KernelKMeans front door;
  - a Nystrom-fitted model flows through the ENTIRE serving stack
    (artifact -> VersionStore publish -> registry -> async traffic ->
    warm hot-swap) and assigns identically to a direct evaluation of the
    Nystrom extension formula;
  - the legacy entry points (fit_model, one_pass_kernel_kmeans) are
    deprecation shims that reproduce the new API bit-for-bit;
  - make_kernel rejects unknown kernel params loudly.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (KernelKMeans, available_backends, fit_memory_bytes,
                       get_backend)
from repro.api.estimator import spec_to_estimator
from repro.core import (clustering_accuracy, kernel_approx_error,
                        make_kernel)
from repro.core.kernels_fn import gram_matrix
from repro.data import gaussian_blobs
from repro.serve import (ClusteringSpec, MicroBatcher, ModelRegistry,
                         ModelSpec, VersionStore, assign, load_model)
from repro.serve.extend import _projection

BACKENDS = ("exact", "nystrom", "onepass-gaussian", "onepass-srht")


@pytest.fixture(scope="module")
def blobs():
    # Well-separated synthetic blobs: every backend must nail these.
    X, labels = gaussian_blobs(jax.random.PRNGKey(0), n=240, p=4, k=3)
    return X, labels


@pytest.fixture(scope="module")
def fits(blobs):
    X, _ = blobs
    out = {}
    for name in BACKENDS:
        est = KernelKMeans(k=3, r=4, kernel="rbf",
                           kernel_params={"gamma": 1.0}, backend=name,
                           backend_params=({"m": 120}
                                           if name == "nystrom" else {}),
                           block=64)
        out[name] = est.fit(X, key=2)
    return out


def _direct_labels(model, ref, Xq):
    """Nearest-centroid labels of a direct evaluation of the extension
    y(x) = Sigma^{-1/2} U^T kappa(ref, x), kappa the fits' RBF kernel."""
    Yq = _projection(model) @ make_kernel("rbf", gamma=1.0)(ref, Xq)
    d2 = (jnp.sum(Yq.T ** 2, 1)[:, None]
          + jnp.sum(model.centroids ** 2, 1)[None, :]
          - 2.0 * Yq.T @ model.centroids.T)
    return np.asarray(jnp.argmin(d2, axis=1), np.int32)


def test_registry_lists_all_four_backends():
    assert list(BACKENDS) == available_backends()
    with pytest.raises(ValueError, match="unknown backend"):
        get_backend("onepass-typo")
    with pytest.raises(ValueError, match="unknown backend"):
        KernelKMeans(backend="nope")


def test_backend_parity_accuracy_and_error(blobs, fits):
    """All four backends within tolerance on accuracy + approx error."""
    X, labels = blobs
    K = gram_matrix(make_kernel("rbf", gamma=1.0), X)
    err_exact = kernel_approx_error(K, fits["exact"].embedding_)
    for name, est in fits.items():
        acc = clustering_accuracy(labels, est.labels_, 3)
        err = kernel_approx_error(K, est.embedding_)
        assert acc >= 0.95, f"{name}: accuracy {acc}"
        # The exact eigendecomposition is the rank-r floor; every
        # approximation must land within a small additive margin of it.
        assert err <= err_exact + 0.15, \
            f"{name}: err {err} vs exact {err_exact}"
        assert err >= err_exact - 1e-5, \
            f"{name}: err {err} beats the exact rank-r floor {err_exact}"


@pytest.mark.parametrize("name", BACKENDS)
def test_backend_parity_serving_assignment(blobs, fits, name):
    """Every backend's fit predicts through the same serving path, and
    held-out assignments agree with the exact backend's up to the label
    permutation (centroid order is seed/backend dependent). The bucketed
    serving stack assigns exactly as a direct evaluation of the
    backend's own extension y(x) = Sigma^{-1/2} U^T kappa(ref, x), ref
    being the training set or the Nystrom landmarks."""
    X, _ = blobs
    Xq = X[:, :60]
    est = fits[name]
    got = est.predict(Xq)
    agree = clustering_accuracy(fits["exact"].predict(Xq), got, 3)
    assert agree >= 0.95, f"{name}: only {agree:.2f} label agreement"

    ref = est.model_.landmarks if name == "nystrom" else X
    want = _direct_labels(est.model_, ref, Xq)
    served, _ = MicroBatcher(est.model_, max_bucket=64).assign_batch(Xq)
    assert np.array_equal(served, want), \
        f"{name}: served stack != direct extension assignment"
    assert np.array_equal(np.asarray(got), want), name


def test_memory_model_ordering(blobs):
    """The paper's axis: one-pass O(r'n) < nystrom O(mn) < exact O(n^2)."""
    n, r = 4000, 2
    onepass = fit_memory_bytes("onepass-srht", n, r, oversampling=10)
    ny = fit_memory_bytes("nystrom", n, r)
    exact = fit_memory_bytes("exact", n, r)
    assert onepass == 4 * n * (r + 10)
    assert onepass < ny < exact
    assert exact == 4 * n * n


def test_nystrom_landmark_artifact_smaller_and_exact(blobs, fits):
    """Nystrom extension state: landmarks persisted, U spans them, and
    the training round-trip is exact BY CONSTRUCTION (any kernel)."""
    X, _ = blobs
    est = fits["nystrom"]
    model = est.model_
    assert model.landmarks is not None and model.landmarks.shape == (4, 120)
    assert model.landmark_idx is not None
    assert model.U.shape[0] == model.n_ref == 120
    assert model.n_ref < X.shape[1]     # serves at landmark height, not n
    Y_ext = est.embed(X)
    rel = (float(jnp.linalg.norm(Y_ext - est.embedding_)) /
           float(jnp.linalg.norm(est.embedding_)))
    assert rel <= 1e-5, rel
    # Y is undefined for landmark fits — loud error, not silent garbage.
    with pytest.raises(AttributeError, match="landmark"):
        model.Y


def test_nystrom_rank_deficient_fit_serves_consistently():
    """Fit and serve must make the SAME rank decision: when the landmark
    gram is rank-deficient, the fit zeroes the truncated eigenvalues, so
    the serving projection (absolute epsilon) cannot re-invert a
    direction the fit refused — which would amplify noise ~1/sqrt(eps)
    and break the exact train round-trip."""
    # 3 distinct points tiled: homogeneous quadratic kernel on p=2 data
    # has feature rank <= 3, so r=6 forces truncated directions.
    base = jnp.asarray([[0.3, -1.2, 2.0], [1.1, 0.4, -0.7]], jnp.float32)
    X = jnp.tile(base, (1, 16))                     # (2, 48)
    est = KernelKMeans(k=2, r=6, kernel="polynomial",
                       kernel_params={"gamma": 0.0, "degree": 2},
                       backend="nystrom", backend_params={"m": 24},
                       block=16).fit(X, key=0)
    evs = np.asarray(est.model_.eigvals)
    # Directions the fit truncated are exactly 0 (here the relative
    # threshold is ~1.6e-6, far above the serving epsilon 1e-7, so every
    # kept eigenvalue is served invertibly too): nothing may land in the
    # inconsistent band (0, 1e-7] where serving would zero what the fit
    # inverted — or worse, the fit zero what serving would invert.
    assert ((evs == 0.0) | (evs > 1e-7)).all(), evs
    assert (evs == 0.0).any(), f"expected truncated directions, got {evs}"
    Y_ext = est.embed(X)
    assert np.isfinite(np.asarray(Y_ext)).all()
    rel = (float(jnp.linalg.norm(Y_ext - est.embedding_)) /
           float(jnp.linalg.norm(est.embedding_)))
    assert rel <= 1e-4, rel


def test_nystrom_full_serve_roundtrip(tmp_path, blobs, fits):
    """Acceptance: backend="nystrom" through the FULL stack — fit ->
    VersionStore.publish -> registry -> async traffic across a warm
    swap -> assign parity with the direct Nystrom embedding."""
    X, _ = blobs
    est = fits["nystrom"]
    store = VersionStore(str(tmp_path / "versions"))
    v1 = store.publish(est.model_)
    reg = ModelRegistry()
    served = reg.load_version("ny", str(tmp_path / "versions"))
    assert reg.version("ny") == v1
    assert served.spec.backend == "nystrom"

    Xq = np.asarray(X[:, :40], np.float32)
    parts = np.split(Xq, [15, 16, 30], axis=1)
    sched = reg.scheduler("ny", max_wait_ms=5.0)
    pre = [sched.submit(p) for p in parts]
    sched.flush()
    labels_async = np.concatenate([f.result()[0] for f in pre])

    # Direct Nystrom embedding: y(x) = Lambda_r^{-1/2} U_r^T k(landmarks, x)
    want = _direct_labels(served, served.landmarks, jnp.asarray(Xq))
    assert np.array_equal(labels_async, want), \
        "served stack != direct Nystrom embedding assignment"

    # Warm hot-swap to a permuted-centroid v2 while requests are pending.
    model_b = served._replace(centroids=served.centroids[::-1])
    v2 = store.publish(model_b)
    pending = [sched.submit(p) for p in parts]
    reg.swap("ny", store.load(v2), version=v2)
    assert all(f.done() for f in pending), "swap stranded futures"
    old = np.concatenate([f.result()[0] for f in pending])
    assert np.array_equal(old, labels_async), \
        "pre-swap requests must resolve against the old version"
    sched2 = reg.scheduler("ny")
    post = [sched2.submit(p) for p in parts]
    sched2.flush()
    new = np.concatenate([f.result()[0] for f in post])
    k = served.spec.k
    assert np.array_equal(new, (k - 1) - labels_async), \
        "post-swap labels must come from the permuted v2 centroids"


def test_estimator_save_load_predict(tmp_path, fits, blobs):
    X, _ = blobs
    est = fits["nystrom"]
    path = est.save(str(tmp_path / "art"))
    est2 = KernelKMeans.load(path)
    assert est2.spec_ == est.spec_
    assert np.array_equal(est2.predict(X[:, :30]), est.predict(X[:, :30]))
    np.testing.assert_allclose(np.asarray(est2.embed(X[:, :30])),
                               np.asarray(est.embed(X[:, :30])),
                               rtol=1e-6, atol=1e-7)
    # And the plain serve-side loaders see the same model.
    m = load_model(path)
    lab, _ = assign(m, X[:, :30])
    assert np.array_equal(np.asarray(lab), est.predict(X[:, :30]))


def test_estimator_unfitted_raises_and_score(blobs, fits):
    X, _ = blobs
    with pytest.raises(RuntimeError, match="not fitted"):
        KernelKMeans().predict(X)
    est = fits["onepass-srht"]
    assert est.score() == -est.inertia_ < 0.0
    assert est.score(X) <= 0.0


def test_spec_roundtrip_and_legacy_schema():
    spec = ClusteringSpec(kernel="rbf", kernel_params={"gamma": 2.0},
                          k=3, r=4, backend="nystrom",
                          backend_params={"m": 99}, n=100, p=5)
    assert ClusteringSpec.from_json(spec.to_json()) == spec
    assert ModelSpec is ClusteringSpec           # legacy alias
    # Pre-estimator-API spec.json schema still loads.
    legacy = ('{"kernel": "polynomial", "kernel_params": {"degree": 2, '
              '"gamma": 0.0}, "n": 250, "p": 2, "r": 2, "k": 2, '
              '"oversampling": 7, "block": 64, "sketch_type": "gaussian"}')
    old = ClusteringSpec.from_json(legacy)
    assert old.backend == "onepass-gaussian"
    assert old.backend_params == {"oversampling": 7}
    assert old.sketch_type == "gaussian" and old.oversampling == 7
    assert (old.n, old.p, old.block) == (250, 2, 64)


def test_spec_to_estimator_refit(blobs):
    X, _ = blobs
    est = KernelKMeans(k=3, r=4, kernel="rbf",
                       kernel_params={"gamma": 1.0}).fit(X, key=5)
    est2 = spec_to_estimator(est.spec_).fit(X, key=5)
    assert np.array_equal(np.asarray(est.labels_), np.asarray(est2.labels_))


# ---------------------------------------------------------------------------
# deprecation shims
# ---------------------------------------------------------------------------

def test_fit_model_shim_warns_and_matches(blobs):
    from repro.serve import fit_model
    X, _ = blobs
    with pytest.warns(DeprecationWarning, match="KernelKMeans"):
        old = fit_model(jax.random.PRNGKey(3), X, k=3, r=4, kernel="rbf",
                        kernel_params={"gamma": 1.0}, oversampling=6,
                        block=64)
    new = KernelKMeans(k=3, r=4, kernel="rbf",
                       kernel_params={"gamma": 1.0},
                       backend_params={"oversampling": 6},
                       block=64).fit(X, key=jax.random.PRNGKey(3)).model_
    assert old.spec == new.spec
    for field in ("U", "eigvals", "centroids", "sketch_signs",
                  "sketch_rows"):
        np.testing.assert_array_equal(np.asarray(getattr(old, field)),
                                      np.asarray(getattr(new, field)))


def test_one_pass_shim_warns_and_matches(blobs):
    from repro.core import one_pass_kernel_kmeans
    X, _ = blobs
    kern = make_kernel("rbf", gamma=1.0)
    with pytest.warns(DeprecationWarning, match="KernelKMeans"):
        old = one_pass_kernel_kmeans(jax.random.PRNGKey(4), kern, X,
                                     k=3, r=4, oversampling=6, block=64)
    new = KernelKMeans(k=3, r=4, kernel="rbf",
                       kernel_params={"gamma": 1.0},
                       backend_params={"oversampling": 6},
                       block=64).fit(X, key=jax.random.PRNGKey(4))
    assert np.array_equal(np.asarray(old.labels), np.asarray(new.labels_))
    np.testing.assert_array_equal(np.asarray(old.Y),
                                  np.asarray(new.embedding_))


def test_shims_do_not_warn_on_new_path(blobs):
    """The front door itself must be warning-free."""
    X, _ = blobs
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        KernelKMeans(k=3, r=4, kernel="rbf",
                     kernel_params={"gamma": 1.0}, block=64).fit(X, key=0)


# ---------------------------------------------------------------------------
# make_kernel param validation
# ---------------------------------------------------------------------------

def test_make_kernel_rejects_unknown_params():
    with pytest.raises(ValueError, match=r"gamm.*valid params.*gamma"):
        make_kernel("rbf", gamm=0.5)            # the classic typo
    with pytest.raises(ValueError, match="degree"):
        make_kernel("rbf", degree=2)            # poly-only param
    with pytest.raises(ValueError, match="no params"):
        make_kernel("linear", gamma=1.0)        # used to be swallowed
    with pytest.raises(ValueError, match="unknown kernel"):
        make_kernel("polynomail")
    # Valid calls still construct.
    make_kernel("polynomial", gamma=0.0, degree=3)
    make_kernel("rbf", gamma=0.5)
    make_kernel("linear")


def test_kernel_kmeans_validates_kernel_name_early():
    with pytest.raises(ValueError, match="unknown kernel"):
        KernelKMeans(kernel="polynomail")
