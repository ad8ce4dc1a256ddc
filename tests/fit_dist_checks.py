"""Multi-device sharded-fit checks (2 fake host devices), run in a
subprocess (see test_distributed.py) — jax locks the device count at
first init, so this cannot share the pytest process.

1-device BIT-identity with the canonical accumulator is pinned in
test_sharded_fit.py. Across real shards the engine's local-FWHT +
butterfly exchange and psum reductions re-associate floating point, so
vs single-host the contract is close agreement; what stays BITWISE on a
fixed mesh is chunk-size invariance (ragged partial_fit == one-shot
sharded fit) and artifact resume — both checked here on 2 devices.

Exit code 0 = all assertions passed.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import tempfile

import jax
import numpy as np
from jax.sharding import Mesh

_KW = dict(k=2, r=2, kernel="polynomial",
           kernel_params={"gamma": 0.0, "degree": 2}, block=32)
N = 96


def _models_equal(a, b):
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


def check_two_device_fit_close_to_single_host():
    from repro.api import KernelKMeans
    from repro.core.metrics import clustering_accuracy
    from repro.data import blob_ring
    from repro.serve import ComputePolicy

    assert len(jax.devices()) == 2, jax.devices()
    mesh = Mesh(np.array(jax.devices()), ("data",))
    X, _ = blob_ring(jax.random.PRNGKey(0), n=N)
    X = np.asarray(X, np.float32)
    for backend in ("onepass-srht", "onepass-gaussian"):
        ref = KernelKMeans(backend=backend, **_KW).fit(X, key=7)
        sh = KernelKMeans(backend=backend, **_KW,
                          policy=ComputePolicy(mesh=mesh)).fit(X, key=7)
        np.testing.assert_allclose(np.asarray(sh.model_.stream_w),
                                   np.asarray(ref.model_.stream_w),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(sh.eigvals_),
                                   np.asarray(ref.eigvals_),
                                   rtol=2e-4, atol=2e-4)
        acc = clustering_accuracy(np.asarray(sh.labels_),
                                  np.asarray(ref.labels_), _KW["k"])
        assert acc == 1.0, f"{backend}: label agreement {acc}"
        print(f"2-device fit close to single-host ok ({backend})")


def check_chunk_invariance_bitwise_on_mesh():
    """On a FIXED mesh, ragged chunked ingest replays the identical
    per-block executables as one-shot — bitwise, 2 devices included."""
    from repro.api import KernelKMeans
    from repro.data import blob_ring
    from repro.serve import ComputePolicy

    mesh = Mesh(np.array(jax.devices()), ("data",))
    X, _ = blob_ring(jax.random.PRNGKey(0), n=N)
    X = np.asarray(X, np.float32)
    for backend in ("onepass-srht", "onepass-gaussian"):
        pol = ComputePolicy(mesh=mesh)
        one = KernelKMeans(backend=backend, **_KW, policy=pol).fit(X, key=7)
        est = KernelKMeans(backend=backend, **_KW, policy=pol)
        edges = [0, 40, 73, N]        # ragged: 40, 33, 23 columns
        for lo, hi in zip(edges[:-1], edges[1:]):
            est.partial_fit(X[:, lo:hi], key=7, capacity=N,
                            reeig=(hi == N))
        _models_equal(one.model_, est.model_)
        assert np.array_equal(np.asarray(one.labels_),
                              np.asarray(est.labels_))
        print(f"2-device ragged chunk invariance bitwise ok ({backend})")


def check_resume_from_artifact_bitwise_on_mesh():
    from repro.api import KernelKMeans
    from repro.data import blob_ring
    from repro.serve import ComputePolicy

    mesh = Mesh(np.array(jax.devices()), ("data",))
    X, _ = blob_ring(jax.random.PRNGKey(0), n=N)
    X = np.asarray(X, np.float32)
    pol = ComputePolicy(mesh=mesh)
    straight = KernelKMeans(**_KW, policy=pol)
    straight.partial_fit(X[:, :64], key=7, capacity=N)
    with tempfile.TemporaryDirectory() as tmp:
        path = straight.save(os.path.join(tmp, "art"))
        straight.partial_fit(X[:, 64:], key=7)
        resumed = KernelKMeans.load(path)
        resumed.policy = pol
        resumed.partial_fit(X[:, 64:], key=7)
    _models_equal(straight.model_, resumed.model_)
    print("2-device artifact resume bitwise ok")


def check_chip_smoke_mesh_phase():
    """chip_smoke.py's mesh phase at a tiny size, kernels interpreted."""
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    import chip_smoke as cs
    from repro.data import gaussian_blobs
    from repro.serve import ComputePolicy

    clock, check = cs.CompileClock(), cs.Checks()
    k_data, k_gamma, k_fit = jax.random.split(jax.random.PRNGKey(0), 3)
    X, _ = gaussian_blobs(k_data, n=2048, p=32, k=cs.K)
    cs.mesh_phase(X, cs.rbf_gamma(X, k_gamma), k_fit, 2,
                  ComputePolicy(interpret=True), check, clock)
    assert check.failed == [], check.failed
    print("chip_smoke mesh phase ok on 2 devices")


if __name__ == "__main__":
    check_chip_smoke_mesh_phase()
    check_two_device_fit_close_to_single_host()
    check_chunk_invariance_bitwise_on_mesh()
    check_resume_from_artifact_bitwise_on_mesh()
    print("ALL FIT DIST CHECKS PASSED")
