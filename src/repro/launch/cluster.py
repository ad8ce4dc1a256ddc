"""Launcher for the paper's pipeline: one-pass randomized kernel K-means.

Single-device by default; --distributed runs the mesh pipeline
(distributed/cluster.py) over however many devices exist.

Usage:
  PYTHONPATH=src python -m repro.launch.cluster --n 4000 --k 2 --r 2 --l 10
  PYTHONPATH=src python -m repro.launch.cluster --dataset seg --k 7 --l 5
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="rings", choices=["rings", "seg",
                                                           "blobs"])
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--r", type=int, default=2)
    ap.add_argument("--l", type=int, default=10, help="oversampling")
    ap.add_argument("--kernel", default="polynomial")
    ap.add_argument("--degree", type=int, default=2)
    ap.add_argument("--gamma", type=float, default=0.0)
    ap.add_argument("--block", type=int, default=512)
    ap.add_argument("--backend", default="onepass-srht",
                    choices=["onepass-srht", "onepass-gaussian", "nystrom",
                             "exact"],
                    help="approximation backend (single-device path; "
                         "--distributed always runs the sharded one-pass)")
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from repro.api import KernelKMeans
    from repro.core import (make_kernel, clustering_accuracy, nmi,
                            kernel_approx_error_streaming)
    from repro.data import blob_ring, segmentation_proxy, gaussian_blobs

    key = jax.random.PRNGKey(args.seed)
    if args.dataset == "rings":
        X, labels = blob_ring(key, n=args.n)
        k = 2
    elif args.dataset == "seg":
        X, labels = segmentation_proxy(key, n=args.n if args.n != 4000
                                       else 2310)
        k = 7
    else:
        X, labels = gaussian_blobs(key, n=args.n, p=16, k=args.k)
        k = args.k
    k = args.k or k
    kernel_params = ({"gamma": args.gamma, "degree": args.degree}
                     if args.kernel == "polynomial" else
                     {"gamma": args.gamma} if args.kernel == "rbf" else {})
    kern = make_kernel(args.kernel, **kernel_params)

    t0 = time.time()
    if args.distributed:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.sketch import next_pow2
        from repro.distributed.cluster import \
            distributed_one_pass_kernel_kmeans
        from repro.serve import data_mesh
        ndev = jax.device_count()
        mesh = data_mesh()
        n_pad = next_pow2(X.shape[1])
        n_pad = max(n_pad, ndev * ((n_pad + ndev - 1) // ndev))
        Xp = jnp.pad(X, ((0, 0), (0, n_pad - X.shape[1])))
        Xp = jax.device_put(Xp, NamedSharding(mesh, P(None, "data")))
        res = distributed_one_pass_kernel_kmeans(
            jax.random.PRNGKey(args.seed + 1), kern, Xp, k=k, r=args.r,
            mesh=mesh, oversampling=args.l, block=args.block)
        pred = np.asarray(res.labels)[: X.shape[1]]
        Y = np.asarray(res.Y)[:, : X.shape[1]]
    else:
        backend_params = ({"oversampling": args.l}
                          if args.backend.startswith("onepass-") else {})
        est = KernelKMeans(k=k, r=args.r, kernel=args.kernel,
                           kernel_params=kernel_params,
                           backend=args.backend,
                           backend_params=backend_params, block=args.block)
        est.fit(X, key=jax.random.PRNGKey(args.seed + 1))
        pred, Y = np.asarray(est.labels_), est.embedding_
    dt = time.time() - t0

    err = kernel_approx_error_streaming(kern, X, jnp.asarray(Y),
                                        block=args.block)
    print(f"n={X.shape[1]} k={k} r={args.r} l={args.l} "
          f"kernel={args.kernel} distributed={args.distributed}")
    print(f"wall time        {dt:.2f} s")
    print(f"approx error     {err:.4f}")
    print(f"accuracy         {clustering_accuracy(labels, pred, k):.4f}")
    print(f"nmi              {nmi(labels, pred):.4f}")
    print(f"sketch memory    {X.shape[1] * (args.r + args.l) * 4 / 2**20:.1f}"
          f" MiB (O(r'n); full K would be "
          f"{X.shape[1] ** 2 * 4 / 2**30:.2f} GiB)")


if __name__ == "__main__":
    main()
