"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. The derived column carries
the paper-comparable metric; the wall times are the host's and say nothing
about the chip, which `bench/run.py` (BENCHMARK.json) measures.

  table1    Table 1: accuracy + approx error, ours vs exact vs Nystrom vs
            plain K-means (blob+ring primary geometry, rings secondary)
  fig3      Fig. 3: error/accuracy vs sampled columns m (seg-proxy data)
  theorem1  Thm. 1 bound tightness over random PSD matrices
  memory    memory footprint: ours O(r'n) vs Nystrom O(mn) at matched error

Select sections with --sections (comma list; default: all). The one-pass
fits run through the estimator API (`repro.api.KernelKMeans`).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def _row(name, us, derived):
    print(f"{name},{us:.0f},{derived}", flush=True)


_POLY = {"gamma": 0.0, "degree": 2}


def _onepass_est(k, r, oversampling, block=512):
    from repro.api import KernelKMeans
    return KernelKMeans(k=k, r=r, kernel="polynomial", kernel_params=_POLY,
                        backend="onepass-srht",
                        backend_params={"oversampling": oversampling},
                        block=block)


def table1():
    from repro.core import (polynomial_kernel, gram_matrix, kmeans,
                            exact_eig_from_gram, nystrom,
                            linearized_kmeans_from_Y,
                            clustering_accuracy, kernel_approx_error)
    from repro.data import blob_ring, two_rings

    kern = polynomial_kernel(gamma=0.0, degree=2)
    for geom, maker in [("blobring", blob_ring), ("rings", two_rings)]:
        X, labels = maker(jax.random.PRNGKey(0), 4000)
        K = gram_matrix(kern, X)
        t0 = time.perf_counter()
        ex = exact_eig_from_gram(K, 2)
        t_ex = (time.perf_counter() - t0) * 1e6
        acc = clustering_accuracy(labels, linearized_kmeans_from_Y(
            jax.random.PRNGKey(3), ex.Y, 2).labels, 2)
        _row(f"table1.{geom}.exact", t_ex,
             f"err={kernel_approx_error(K, ex.Y):.2f};acc={acc:.2f}")
        errs, accs, t = [], [], 0.0
        for s in range(5):
            t0 = time.perf_counter()
            res = _onepass_est(2, 2, 10).fit(X, key=jax.random.PRNGKey(10 + s))
            t += (time.perf_counter() - t0) * 1e6
            errs.append(kernel_approx_error(K, res.embedding_))
            accs.append(clustering_accuracy(labels, res.labels_, 2))
        _row(f"table1.{geom}.ours", t / 5,
             f"err={np.mean(errs):.2f};acc={np.mean(accs):.2f}")
        for m in (20, 100):
            errs, accs, t = [], [], 0.0
            for s in range(5):
                t0 = time.perf_counter()
                ny = nystrom(jax.random.PRNGKey(50 + s), kern, X, m=m, r=2)
                km = linearized_kmeans_from_Y(jax.random.PRNGKey(3), ny.Y, 2)
                t += (time.perf_counter() - t0) * 1e6
                errs.append(kernel_approx_error(K, ny.Y))
                accs.append(clustering_accuracy(labels, km.labels, 2))
            _row(f"table1.{geom}.nystrom_m{m}", t / 5,
                 f"err={np.mean(errs):.2f};acc={np.mean(accs):.2f}")
        t0 = time.perf_counter()
        km = kmeans(jax.random.PRNGKey(5), X.T, 2)
        _row(f"table1.{geom}.plain_kmeans",
             (time.perf_counter() - t0) * 1e6,
             f"acc={clustering_accuracy(labels, km.labels, 2):.2f}")


def fig3():
    from repro.core import (polynomial_kernel, gram_matrix, nystrom,
                            linearized_kmeans_from_Y, clustering_accuracy,
                            kernel_approx_error)
    from repro.data import segmentation_proxy

    X, labels = segmentation_proxy(jax.random.PRNGKey(1))
    kern = polynomial_kernel(gamma=0.0, degree=2)
    K = gram_matrix(kern, X)
    errs, accs = [], []
    t0 = time.perf_counter()
    for s in range(5):
        res = _onepass_est(7, 2, 5).fit(X, key=jax.random.PRNGKey(20 + s))
        errs.append(kernel_approx_error(K, res.embedding_))
        accs.append(clustering_accuracy(labels, res.labels_, 7))
    _row("fig3.ours_rp7", (time.perf_counter() - t0) / 5 * 1e6,
         f"err={np.mean(errs):.3f};acc={np.mean(accs):.3f}")
    for m in (10, 20, 50):
        errs, accs = [], []
        t0 = time.perf_counter()
        for s in range(5):
            ny = nystrom(jax.random.PRNGKey(60 + s), kern, X, m=m, r=2)
            km = linearized_kmeans_from_Y(jax.random.PRNGKey(3), ny.Y, 7)
            errs.append(kernel_approx_error(K, ny.Y))
            accs.append(clustering_accuracy(labels, km.labels, 7))
        _row(f"fig3.nystrom_m{m}", (time.perf_counter() - t0) / 5 * 1e6,
             f"err={np.mean(errs):.3f};acc={np.mean(accs):.3f}")


def theorem1():
    from repro.core import theorem1_bounds, best_rank_r

    tight_any, tight_best = [], []
    t0 = time.perf_counter()
    for seed in range(15):
        rng = np.random.RandomState(seed)
        A = rng.randn(6, 4).astype(np.float32)
        K = jnp.asarray(A @ A.T)
        K_hat = best_rank_r(K, 2)
        excess, bound_any, bound_best = theorem1_bounds(K, K_hat, 2)
        tight_any.append(excess / max(bound_any, 1e-9))
        tight_best.append(excess / max(bound_best, 1e-9))
        assert excess <= bound_best + 1e-3
    _row("theorem1.tightness", (time.perf_counter() - t0) / 15 * 1e6,
         f"excess/tr(E)={np.mean(tight_best):.3f};"
         f"excess/2trnorm={np.mean(tight_any):.3f};violations=0")


def memory():
    """Memory to reach (near-)exact rank-2 error: ours vs Nystrom."""
    from repro.core import (polynomial_kernel, gram_matrix, nystrom,
                            exact_eig_from_gram, kernel_approx_error,
                            randomized_eig)
    from repro.data import blob_ring

    X, _ = blob_ring(jax.random.PRNGKey(0), 4000)
    n = 4000
    kern = polynomial_kernel(gamma=0.0, degree=2)
    K = gram_matrix(kern, X)
    eig = randomized_eig(jax.random.PRNGKey(1), kern, X, 2, oversampling=10)
    err_ours = kernel_approx_error(K, eig.Y)
    ours_bytes = n * 12 * 4            # W: n x r'
    m = 12
    while m <= 512:
        errs = [kernel_approx_error(K, nystrom(jax.random.PRNGKey(s), kern,
                                               X, m=m, r=2).Y)
                for s in range(3)]
        if np.mean(errs) <= 1.02 * err_ours:
            break
        m *= 2
    ny_bytes = n * m * 4               # C: n x m
    _row("memory.ours", 0, f"bytes={ours_bytes};err={err_ours:.3f}")
    _row("memory.nystrom_matched", 0,
         f"bytes={ny_bytes};m={m};ratio={ny_bytes/ours_bytes:.1f}x")


_SECTIONS = {"table1": table1, "fig3": fig3, "theorem1": theorem1,
             "memory": memory}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sections", default=",".join(_SECTIONS),
                    help=f"comma list of {sorted(_SECTIONS)}")
    args = ap.parse_args()
    sections = [s.strip() for s in args.sections.split(",") if s.strip()]
    unknown = set(sections) - set(_SECTIONS)
    if unknown:
        ap.error(f"unknown section(s) {sorted(unknown)}; "
                 f"have {sorted(_SECTIONS)}")
    print("name,us_per_call,derived")
    for name in sections:
        _SECTIONS[name]()


if __name__ == "__main__":
    main()
