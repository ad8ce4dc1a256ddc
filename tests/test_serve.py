"""repro.serve: out-of-sample consistency, batching exactness, artifacts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KernelKMeans
from repro.core.kernels_fn import polynomial_kernel, stripe_iterator
from repro.data import blob_ring
from repro.serve import (ModelRegistry, MicroBatcher, assign, bucket_size,
                         embed, load_model, save_model)

N, P, R, K, BLOCK = 250, 2, 2, 2, 64   # ragged: 250 = 3*64 + 58


def _fit(backend="onepass-srht"):
    X, _ = blob_ring(jax.random.PRNGKey(0), n=N)
    params = {"oversampling": 10} if backend.startswith("onepass-") else {}
    return KernelKMeans(k=K, r=R, kernel="polynomial",
                        kernel_params={"gamma": 0.0, "degree": 2},
                        backend=backend, backend_params=params,
                        block=BLOCK).fit(X, key=jax.random.PRNGKey(1)).model_


@pytest.fixture(scope="module")
def model():
    return _fit()


@pytest.mark.parametrize("backend",
                         ["onepass-srht", "onepass-gaussian", "exact"])
def test_train_points_reproduce_fitted_Y(model, backend):
    """The extension identity: embed(X_train) == Y to ~1e-4 relative, on
    each backend that serves against the training set. The quadratic
    kernel on p = 2 has rank 3 <= r', so a one-pass sketch holds it
    whole; the exact backend's eigenvectors make it exact for any
    kernel. (Nystrom's identity: test_api.py.)"""
    if backend != model.spec.backend:
        model = _fit(backend)
    Y_ext = embed(model, model.X_train)
    rel = (float(jnp.linalg.norm(Y_ext - model.Y)) /
           float(jnp.linalg.norm(model.Y)))
    assert rel <= 1e-4, rel


def test_embedding_inner_products_match_kernel():
    """y(x)^T y(x') reproduces kappa(x, x') on held-out points when the fit
    rank covers the kernel's feature space (r=3 for homogeneous poly d=2,
    p=2)."""
    X, _ = blob_ring(jax.random.PRNGKey(0), n=N)
    m3 = KernelKMeans(k=K, r=3, kernel="polynomial",
                      kernel_params={"gamma": 0.0, "degree": 2},
                      backend_params={"oversampling": 10},
                      block=BLOCK).fit(X, key=jax.random.PRNGKey(1)).model_
    Xq = jax.random.normal(jax.random.PRNGKey(2), (P, 40)) * 1.5
    Yq = embed(m3, Xq)
    kern = polynomial_kernel(gamma=0.0, degree=2)
    Kq = np.asarray(kern(Xq, Xq))
    rel = (np.linalg.norm(np.asarray(Yq.T @ Yq) - Kq) /
           np.linalg.norm(Kq))
    assert rel < 1e-4, rel


def test_save_load_roundtrip(model, tmp_path):
    path = save_model(model, str(tmp_path / "artifact"))
    loaded = load_model(path)
    assert loaded.spec == model.spec
    for name in ("X_train", "U", "eigvals", "centroids", "sketch_signs",
                 "sketch_rows"):
        np.testing.assert_array_equal(np.asarray(getattr(loaded, name)),
                                      np.asarray(getattr(model, name)))
    assert loaded.sketch_omega is None
    Xq = jax.random.normal(jax.random.PRNGKey(3), (P, 33))
    np.testing.assert_array_equal(np.asarray(embed(loaded, Xq)),
                                  np.asarray(embed(model, Xq)))


def test_save_load_bf16_roundtrip(model, tmp_path):
    """dtype="bf16" halves the float payload (uint16 bit patterns via
    distributed/compression.py) and round-trips to float32 within bf16
    precision; assignments survive the quantization."""
    import pathlib

    f32_dir = save_model(model, str(tmp_path / "f32"))
    bf16_dir = save_model(model, str(tmp_path / "bf16"), dtype="bf16")

    def payload(d):
        return sum(p.stat().st_size
                   for p in (pathlib.Path(d) / "step_0").glob("leaf_*.npy"))

    # X_train/U/eigvals/centroids/sketch_signs halve; int leaves
    # (sketch_rows) don't — so strictly between 50% and 100%.
    assert payload(bf16_dir) < 0.6 * payload(f32_dir)

    loaded = load_model(bf16_dir)
    assert loaded.spec == model.spec
    for name in ("X_train", "U", "eigvals", "centroids"):
        got = np.asarray(getattr(loaded, name))
        want = np.asarray(getattr(model, name))
        assert got.dtype == np.float32
        # bf16 has an 8-bit mantissa: exact to ~3 decimal digits.
        np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-3)
    # Integer sketch rows must survive bit-exact (they index the FWHT).
    np.testing.assert_array_equal(np.asarray(loaded.sketch_rows),
                                  np.asarray(model.sketch_rows))

    Xq = jax.random.normal(jax.random.PRNGKey(7), (P, 128)) * 1.5
    lab_f32, _ = assign(model, Xq)
    lab_bf16, _ = assign(loaded, Xq)
    agree = float(np.mean(np.asarray(lab_f32) == np.asarray(lab_bf16)))
    assert agree >= 0.99, f"bf16 artifact changed {1 - agree:.1%} of labels"
    Y32 = embed(model, Xq)
    Y16 = embed(loaded, Xq)
    rel = (float(jnp.linalg.norm(Y16 - Y32)) /
           max(float(jnp.linalg.norm(Y32)), 1e-30))
    assert rel <= 2e-2, rel


def test_save_model_rejects_unknown_dtype(model, tmp_path):
    with pytest.raises(ValueError, match="unknown quantized dtype"):
        save_model(model, str(tmp_path / "x"), dtype="int3")


def test_save_load_gaussian_sketch(tmp_path):
    X, _ = blob_ring(jax.random.PRNGKey(4), n=128)
    m = KernelKMeans(k=2, r=2, backend="onepass-gaussian",
                     block=64).fit(X, key=jax.random.PRNGKey(5)).model_
    loaded = load_model(save_model(m, str(tmp_path / "g")))
    assert loaded.sketch_signs is None and loaded.sketch_rows is None
    np.testing.assert_array_equal(np.asarray(loaded.sketch_omega),
                                  np.asarray(m.sketch_omega))


def test_bucketed_equals_unbatched_exactly(model):
    for b in (5, 64, 300):   # < bucket, == bucket, ragged multi-stripe
        Xq = jax.random.normal(jax.random.PRNGKey(b), (P, b)) * 1.5
        labels_direct, d2_direct = assign(model, Xq)
        batcher = MicroBatcher(model, max_bucket=128)
        labels_bucket, d2_bucket = batcher.assign_batch(Xq)
        assert np.array_equal(np.asarray(labels_direct), labels_bucket)
        np.testing.assert_allclose(np.asarray(d2_direct), d2_bucket,
                                   rtol=1e-5, atol=1e-6)


def test_queue_drain_matches_unbatched(model):
    Xq = jax.random.normal(jax.random.PRNGKey(9), (P, 101)) * 1.5
    labels_direct, _ = assign(model, Xq)
    batcher = MicroBatcher(model, max_bucket=64)
    parts = np.split(np.asarray(Xq), [7, 40, 41, 90], axis=1)
    tickets = [batcher.submit(p) for p in parts]
    out = batcher.drain()
    assert len(out) == len(parts)
    got = np.concatenate([out[t][0] for t in tickets])
    assert np.array_equal(np.asarray(labels_direct), got)
    assert batcher.drain() == []     # queue empties


def test_bucketing_policy_bounds_executables(model):
    batcher = MicroBatcher(model, min_bucket=8, max_bucket=64)
    for b in (1, 3, 5, 7, 9, 17, 33, 60, 64, 100, 129):
        Xq = jax.random.normal(jax.random.PRNGKey(b), (P, b))
        labels, d2 = batcher.assign_batch(Xq)
        assert labels.shape == (b,) and d2.shape == (b,)
    # pow-2 buckets in [8, 64] only: at most 8,16,32,64 ever compiled.
    assert set(batcher.executables) <= {8, 16, 32, 64}


def test_fused_pallas_assign_matches_jnp(model):
    Xq = jax.random.normal(jax.random.PRNGKey(11), (P, 96)) * 1.5
    lab_jnp, d2_jnp = assign(model, Xq, fused=False)
    lab_pal, d2_pal = assign(model, Xq, fused=True)
    assert np.array_equal(np.asarray(lab_jnp), np.asarray(lab_pal))
    np.testing.assert_allclose(np.asarray(d2_jnp), np.asarray(d2_pal),
                               rtol=1e-4, atol=1e-5)


def test_zero_width_requests_rejected_cleanly(model):
    batcher = MicroBatcher(model)
    with pytest.raises(ValueError):
        batcher.submit(np.zeros((P, 0), np.float32))
    labels, d2 = batcher.assign_batch(np.zeros((P, 0), np.float32))
    assert labels.shape == (0,) and d2.shape == (0,)


def test_bucket_size():
    assert bucket_size(1) == 8
    assert bucket_size(8) == 8
    assert bucket_size(9) == 16
    assert bucket_size(1000) == 1024
    assert bucket_size(5000, max_bucket=1024) == 1024
    with pytest.raises(ValueError):
        bucket_size(0)


def test_registry_multi_model(model, tmp_path):
    reg = ModelRegistry()
    reg.register("a", model)
    path = reg.save("a", str(tmp_path / "a"))
    reg.load("b", path)
    assert reg.names() == ["a", "b"]
    with pytest.raises(ValueError):
        reg.register("a", model)
    reg.register("a", model, overwrite=True)
    Xq = jax.random.normal(jax.random.PRNGKey(13), (P, 17))
    la, _ = reg.batcher("a").assign_batch(Xq)
    lb, _ = reg.batcher("b").assign_batch(Xq)
    assert np.array_equal(la, lb)
    with pytest.raises(KeyError):
        reg.get("missing")


# ---------------------------------------------------------------------------
# stripe_iterator: tail path and out-of-sample (lhs=) stripes
# ---------------------------------------------------------------------------

def test_stripe_iterator_tail_matches_direct():
    kern = polynomial_kernel(gamma=0.0, degree=2)
    X = jax.random.normal(jax.random.PRNGKey(20), (3, 70))
    Kfull = np.asarray(kern(X, X))
    got = np.concatenate([np.asarray(s) for _, s in
                          stripe_iterator(kern, X, block=32)], axis=1)
    np.testing.assert_allclose(got, Kfull, rtol=1e-5, atol=1e-6)


def test_stripe_iterator_rectangular_lhs():
    kern = polynomial_kernel(gamma=0.0, degree=2)
    Xt = jax.random.normal(jax.random.PRNGKey(21), (3, 50))
    Xq = jax.random.normal(jax.random.PRNGKey(22), (3, 23))
    want = np.asarray(kern(Xt, Xq))
    got = np.concatenate([np.asarray(s) for _, s in
                          stripe_iterator(kern, Xq, block=16, lhs=Xt)],
                         axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # pad_tail=True keeps every stripe at full block width.
    widths = [s.shape[1] for _, s in
              stripe_iterator(kern, Xq, block=16, lhs=Xt, pad_tail=True)]
    assert widths == [16, 16]


def test_stripe_iterator_single_compiled_path():
    """The ragged tail must go through the one jitted gram_stripe: the
    kernel callable is traced exactly once across repeated passes."""
    traces = []

    def counting_kernel(X, Y):
        traces.append(1)
        return (X.T @ Y) ** 2

    X = jax.random.normal(jax.random.PRNGKey(23), (3, 70))  # 70 = 2*32 + 6
    for _ in range(3):
        for _start, _s in stripe_iterator(counting_kernel, X, block=32):
            pass
    assert len(traces) == 1, f"kernel traced {len(traces)}x; tail retracing"
