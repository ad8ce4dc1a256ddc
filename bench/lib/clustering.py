"""The program's objects as a configuration file describes them."""
from __future__ import annotations

import jax

from bench.lib import data, device


def policy(cfg, interpret: bool):
    """ComputePolicy with each kernel compiled as the configuration says,
    on one chip. interpret runs the Pallas kernels in interpret mode
    (tests on the CPU only)."""
    device.use_program()
    from repro.serve import ComputePolicy
    compiled = {name: mode == "compiled"
                for name, mode in cfg["kernels"].items()}
    return ComputePolicy(fit_fused=compiled["fit_sketch"],
                         embed_fused=compiled["extend_embed"],
                         assign_fused=compiled["kmeans_assign"],
                         interpret=bool(interpret))


def estimator(cfg, gamma: float, pol):
    device.use_program()
    from repro.api import KernelKMeans
    return KernelKMeans(k=cfg["k"], r=cfg["r"], kernel=cfg["kernel"],
                        kernel_params={"gamma": gamma},
                        backend=cfg["backend"],
                        backend_params={"oversampling": cfg["oversampling"]},
                        block=cfg["block"], n_restarts=cfg["n_restarts"],
                        max_iter=cfg["max_iter"], policy=pol)


def make_data(cfg, seed: int, n: int):
    """(X on the device, rbf gamma, the key jobs fold their index into)."""
    k_data, k_gamma, k_jobs = jax.random.split(data.root_key(seed), 3)
    X, _ = jax.block_until_ready(data.blobs(k_data, data.centres_key(), n=n,
                                            p=cfg["p"], k=cfg["k"]))
    return X, data.median_gamma(X, k_gamma, cfg["gamma_sample"]), k_jobs
