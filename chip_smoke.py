#!/usr/bin/env python3
"""Chip smoke: fit -> publish -> serve on one TPU chip at MNIST scale.

Drives the one-pass path once through the entry points a user calls, on
seeded data of MNIST's shape (70,000 points x 784 features, k=10, whose
n x n float32 gram would be 19.6 GB):

  fit    KernelKMeans(onepass-srht, RBF, r=10) with the fit_sketch
         kernel compiled; the same fit on the jnp accumulator path, at
         full f32 matmul precision, must agree (eigenvalues, training
         labels), and the accuracy against the seeded labels is printed;
  serve  save -> VersionStore.publish -> ModelRegistry, then 64 requests
         of 1..1024 columns through the registry's AsyncBatcher with
         extend_embed and kmeans_assign compiled; labels and embeddings
         must agree with a float32 jnp reference at full matmul
         precision.

`--chips 4` runs only the mesh phase: the same fit on a 4-device mesh
against a one-chip fit in the same process, and ShardedExtender against
Extender.

Exits nonzero, printing no result, unless JAX's default device is a TPU.
A passing run's last line is one JSON object naming the device.

Usage, from the repository root:
  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # mesh phase, four chips
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import KernelKMeans  # noqa: E402
from repro.core import clustering_accuracy  # noqa: E402
from repro.data import gaussian_blobs  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.serve import (ComputePolicy, Extender, ModelRegistry,  # noqa: E402
                         ShardedExtender, VersionStore, assign, data_mesh,
                         embed, load_model)

# MNIST's shape (ROADMAP Reach 1): the standard large-n set of the
# approximate kernel K-means literature.
N, P, K = 70_000, 784, 10
R, OVERSAMPLING, BLOCK = 10, 10, 512
GAMMA_SAMPLE = 1000            # points whose median squared distance sets gamma
N_REQUESTS, MAX_WIDTH = 64, 1024
MESH_QUERIES = 2048
MAX_WAIT_MS = 5.0
FUTURE_TIMEOUT_S = 600.0

EIG_RTOL = 1e-3                # fit vs fit: max relative eigenvalue gap
FIT_LABEL_AGREE = 0.99         # fit vs fit: labels after label matching
SERVE_LABEL_AGREE = 0.999      # served labels vs the jnp reference
EMBED_RTOL = 1e-3              # embeddings vs the reference, Frobenius


class Checks:
    """Named pass/fail checks, each printed as it is made."""

    def __init__(self):
        self.failed: list = []

    def __call__(self, name: str, value: float, limit: str, ok: bool):
        print(f"check {name}: {value!r} (limit {limit}) "
              f"{'PASS' if ok else 'FAIL'}", flush=True)
        if not ok:
            self.failed.append(name)


class CompileClock:
    """Seconds JAX spent in backend compilation, from its monitoring
    events."""

    def __init__(self):
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs


def rbf_gamma(X, key) -> float:
    """1 / median squared distance over a seeded GAMMA_SAMPLE-point
    sample, in float64 on the host."""
    idx = jax.random.choice(key, X.shape[1], (min(GAMMA_SAMPLE, X.shape[1]),),
                            replace=False)
    S = np.asarray(X[:, idx], np.float64)
    sq = np.sum(S * S, axis=0)
    d2 = sq[:, None] + sq[None, :] - 2.0 * (S.T @ S)
    return 1.0 / float(np.median(d2[np.triu_indices(S.shape[1], 1)]))


def estimator(gamma: float, policy) -> KernelKMeans:
    return KernelKMeans(k=K, r=R, kernel="rbf", kernel_params={"gamma": gamma},
                        backend="onepass-srht",
                        backend_params={"oversampling": OVERSAMPLING},
                        block=BLOCK, policy=policy)


def timed(name: str, clock: CompileClock, fn):
    """Run fn(); print its wall, compile and remaining seconds."""
    c0, t0 = clock.secs, time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    comp = clock.secs - c0
    print(f"{name}: {wall:.3f} s wall, {comp:.3f} s compile, "
          f"{wall - comp:.3f} s run", flush=True)
    return out


def compare_fits(check: Checks, name: str, got: KernelKMeans,
                 want: KernelKMeans) -> None:
    ev_got, ev_want = np.asarray(got.eigvals_), np.asarray(want.eigvals_)
    print(f"{name} eigenvalues: {ev_got.tolist()} vs {ev_want.tolist()}")
    rel = float(np.max(np.abs(ev_got - ev_want) / np.abs(ev_want)))
    check(f"{name} eigenvalue max rel diff", rel, f"<= {EIG_RTOL}",
          rel <= EIG_RTOL)
    agree = clustering_accuracy(np.asarray(want.labels_),
                                np.asarray(got.labels_), K)
    check(f"{name} training-label agreement", float(agree),
          f">= {FIT_LABEL_AGREE}", agree >= FIT_LABEL_AGREE)


def rel_err(got, want) -> float:
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def queries(X, key, total: int):
    """`total` query columns: training points with fresh noise."""
    k_idx, k_noise = jax.random.split(key)
    idx = jax.random.randint(k_idx, (total,), 0, X.shape[1])
    return X[:, idx] + 0.1 * jax.random.normal(k_noise, (X.shape[0], total))


def fit_phase(X, labels, gamma: float, key, policy, check: Checks,
              clock: CompileClock) -> KernelKMeans:
    """Fit with `policy` (fit_sketch on), then on the jnp accumulator at
    full f32 matmul precision, as the kernels contract."""
    est = timed("fit (fit_sketch kernel)", clock,
                lambda: estimator(gamma, policy).fit(X, key=key))
    with jax.default_matmul_precision("highest"):
        ref = timed("fit (jnp accumulator)", clock,
                    lambda: estimator(gamma, ComputePolicy(
                        fit_fused=False)).fit(X, key=key))
    compare_fits(check, "fit_sketch vs jnp fit", est, ref)
    acc = clustering_accuracy(np.asarray(labels), np.asarray(est.labels_), K)
    print(f"accuracy against the seeded labels: {float(acc)!r}")
    return est


def serve_phase(est: KernelKMeans, X, key, policy, check: Checks,
                clock: CompileClock) -> None:
    """save -> publish -> registry -> AsyncBatcher, against a reference."""
    rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 2**31 - 1)))
    widths = np.rint(2.0 ** rng.uniform(0.0, np.log2(MAX_WIDTH),
                                        N_REQUESTS)).astype(int)
    widths[0], widths[-1] = 1, MAX_WIDTH
    offsets = np.concatenate([[0], np.cumsum(widths)])
    Xq = queries(X, key, int(offsets[-1]))
    Xq_host = np.asarray(Xq)
    with tempfile.TemporaryDirectory() as tmp:
        art = est.save(os.path.join(tmp, "artifact"))
        store = VersionStore(os.path.join(tmp, "store"))
        version = store.publish(load_model(art))
        registry = ModelRegistry()
        model = registry.load_version("blobs", str(store.root), version)
    print(f"published version {version}; serving {N_REQUESTS} requests, "
          f"{int(offsets[-1])} columns, widths {int(widths.min())}.."
          f"{int(widths.max())}")
    sched = registry.scheduler("blobs", policy=policy,
                               max_wait_ms=MAX_WAIT_MS)

    def serve():
        with sched:
            futs = [sched.submit(Xq_host[:, offsets[i]:offsets[i + 1]])
                    for i in range(N_REQUESTS)]
            return [f.result(timeout=FUTURE_TIMEOUT_S) for f in futs]

    results = timed("serve (64 requests, compiles included)", clock, serve)
    check("futures resolved", len(results), f"== {N_REQUESTS}",
          len(results) == N_REQUESTS)
    print(f"latency: {json.dumps(registry.latency_summary('blobs'))}")
    lab_srv = np.concatenate([lab for lab, _ in results])
    ref_policy = ComputePolicy(embed_fused=False, assign_fused=False)
    with jax.default_matmul_precision("highest"):
        lab_ref, _ = assign(model, Xq, policy=ref_policy)
        Y_ref = embed(model, Xq, policy=ref_policy)
    agree = float(np.mean(lab_srv == np.asarray(lab_ref)))
    check("served labels vs jnp reference", agree, f">= {SERVE_LABEL_AGREE}",
          agree >= SERVE_LABEL_AGREE)
    rel = rel_err(sched.batcher.extender.embed(Xq), Y_ref)
    check("served embedding rel err", rel, f"<= {EMBED_RTOL}",
          rel <= EMBED_RTOL)


def mesh_phase(X, gamma: float, key, n_chips: int, policy, check: Checks,
               clock: CompileClock) -> None:
    """Mesh-sharded fit and extension against one chip, one process."""
    devices = jax.devices()
    if len(devices) < n_chips:
        raise RuntimeError(f"--chips {n_chips} needs {n_chips} devices, "
                           f"have {len(devices)}")
    mesh_policy = policy.replace(mesh=data_mesh(devices[:n_chips]))
    one = timed("fit (one chip)", clock,
                lambda: estimator(gamma, policy).fit(X, key=key))
    # partial_fit with capacity=n is fit, and keeps the sharded
    # accumulator on the estimator for the placement check.
    shard = timed(f"fit ({n_chips}-device mesh)", clock,
                  lambda: estimator(gamma, mesh_policy).partial_fit(
                      X, key=key, capacity=X.shape[1]))
    compare_fits(check, f"{n_chips}-device vs one-chip fit", shard, one)
    spread = len(shard._acc._engine._Xbuf.sharding.device_set)
    check("sharded data buffer devices", spread, f"== {n_chips}",
          spread == n_chips)
    Xq = queries(X, jax.random.fold_in(key, 1), MESH_QUERIES)
    Y_one = Extender(one.model_, policy=policy).embed(Xq)
    Y_mesh = timed(f"ShardedExtender.embed ({MESH_QUERIES} queries)", clock,
                   lambda: ShardedExtender(one.model_,
                                           policy=mesh_policy).embed(Xq))
    rel = rel_err(Y_mesh, Y_one)
    check(f"{n_chips}-device vs one-chip embedding rel err", rel,
          f"<= {EMBED_RTOL}", rel <= EMBED_RTOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the mesh phase on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: the default device is {dev.platform!r}, not a "
              f"TPU; nothing was run", file=sys.stderr)
        return 1
    print(f"compile cache: {use_compile_cache()}")
    print(f"device: {dev.device_kind} ({dev.platform}), "
          f"{len(jax.devices())} visible")
    clock = CompileClock()
    check = Checks()
    policy = ComputePolicy(interpret=False)
    key = jax.random.PRNGKey(args.seed)
    k_data, k_gamma, k_fit, k_serve = jax.random.split(key, 4)

    X, labels = timed("data", clock,
                      lambda: jax.block_until_ready(
                          gaussian_blobs(k_data, n=N, p=P, k=K)))
    gamma = rbf_gamma(X, k_gamma)
    print(f"data: X {X.shape} f32; rbf gamma {gamma!r} (median heuristic "
          f"over {GAMMA_SAMPLE} points)")
    if args.chips == 1:
        est = fit_phase(X, labels, gamma, k_fit, policy, check, clock)
        serve_phase(est, X, k_serve, policy, check, clock)
    else:
        mesh_phase(X, gamma, k_fit, args.chips, policy, check, clock)
    print(f"total backend compile: {clock.secs:.3f} s")
    if check.failed:
        print(f"chip_smoke: FAILED {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
