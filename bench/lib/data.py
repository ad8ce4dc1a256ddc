"""Inputs made from the seed, on the device, in one jitted call each.

`blobs` is a copy of the program's `repro.data.gaussian_blobs` (k
isotropic clusters, centres N(0, 1), spread 0.1), kept here so the inputs
cannot move with the program. The centres are part of the configuration,
drawn from a fixed key (`centres_key`), as a deployment fits one data set;
the seed draws the points. So every seed sees clusters of the same
geometry and the median-heuristic gamma falls on the same grid value.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key for any whole-number seed (the driver's exceed 32 bits):
    the seed is hashed by numpy's SeedSequence to 31 bits."""
    state = np.random.SeedSequence([int(seed), int(stream)]).generate_state(1)
    return jax.random.PRNGKey(int(state[0]) >> 1)


def host_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def centres_key() -> jax.Array:
    """The key of every configuration's cluster centres."""
    return root_key(0, 7)


@functools.partial(jax.jit, static_argnames=("n", "p", "k"))
def blobs(key: jax.Array, k_centres: jax.Array, *, n: int, p: int, k: int):
    """X (p, n) float32 and labels (n,): k clusters, spread 0.1, centres
    drawn from k_centres and points from key."""
    k2, k3 = jax.random.split(key)
    centers = jax.random.normal(k_centres, (k, p))
    labels = jax.random.randint(k2, (n,), 0, k)
    X = centers[labels].T + 0.1 * jax.random.normal(k3, (p, n))
    return X, labels.astype(jnp.int32)


GAMMA_STEPS_PER_OCTAVE = 8


def median_gamma(X: jax.Array, key: jax.Array, sample: int) -> float:
    """RBF gamma = 1 / median squared distance over `sample` seeded
    points, in float64 on the host, rounded to the nearest
    2^(j / GAMMA_STEPS_PER_OCTAVE). The program specialises its kernels
    to gamma's value, so the grid keeps the set of compiled programs
    small across seeds."""
    idx = jax.random.choice(key, X.shape[1], (min(sample, X.shape[1]),),
                            replace=False)
    S = np.asarray(X[:, idx], np.float64)
    sq = np.sum(S * S, axis=0)
    d2 = sq[:, None] + sq[None, :] - 2.0 * (S.T @ S)
    g = 1.0 / float(np.median(d2[np.triu_indices(S.shape[1], 1)]))
    return float(2.0 ** (round(GAMMA_STEPS_PER_OCTAVE * np.log2(g))
                         / GAMMA_STEPS_PER_OCTAVE))


@functools.partial(jax.jit, static_argnames=("total",))
def query_pool(X: jax.Array, key: jax.Array, noise: float, *, total: int
               ) -> jax.Array:
    """`total` query columns: seeded training points plus Gaussian noise
    of standard deviation `noise`."""
    k_idx, k_noise = jax.random.split(key)
    idx = jax.random.randint(k_idx, (total,), 0, X.shape[1])
    return X[:, idx] + noise * jax.random.normal(k_noise,
                                                 (X.shape[0], total))
