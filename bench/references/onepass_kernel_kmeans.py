"""Plain reference for one-pass RBF kernel K-means (fit and assignment).

Straight from the published description, in float32 on the device for
the two contractions with the kernel matrix and float64 on the host for
everything after them. It imports nothing of the program and takes
nothing the program made: the sketch it applies is the one the fit's key
defines, drawn here with the same JAX random calls the fit's contract
names (the fit key splits into a sketch key and a K-means key; the
sketch key into the SRHT sign and row-sample keys).

  Omega = D H R, n x r' (SRHT rows of the n_pad-point Hadamard, r' = r + l)
  W     = K Omega, K = exp(-gamma ||x_i - x_j||^2), built in row blocks
  Alg. 1 lines 3-6 (Halko et al. 2011, sec. 5.5): Q = qr(W),
        B (Q^T Omega) = Q^T W, B = V S V^T, U = Q V_r, Y = S_r^1/2 U^T
  K-means: Lloyd from k-means++ seeds, best of several restarts
  extension: y(x) = S_r^-1/2 U^T kappa(X, x), label = nearest centroid

`precision` picks the contraction: "highest" (float32, what the
configuration states) or "high", float32 carried as three bfloat16
products (hi*hi + hi*lo + lo*hi), which is what XLA's `high` does on a
TPU; written out here so the control computes the same on any backend.

Departure from the paper's MATLAB: Lloyd runs to convergence (at most
100 iterations) instead of 20, so the reference partition is a fixed
point.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from scipy.optimize import linear_sum_assignment

PRECISIONS = ("highest", "high")
ROW_BLOCK = 2048
K_BLOCK_ENTRIES = 1 << 28   # a block of K rows holds at most 1 GiB


def _dot(a, b, precision: str):
    """a @ b in float32 at the given precision."""
    if precision == "highest":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    if precision != "high":
        raise ValueError(f"precision must be one of {PRECISIONS}")
    bf = jnp.bfloat16

    def split(x):
        hi = x.astype(bf)
        return hi, (x - hi.astype(jnp.float32)).astype(bf)

    a_hi, a_lo = split(a)
    b_hi, b_lo = split(b)

    def d(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    return d(a_hi, b_hi) + (d(a_hi, b_lo) + d(a_lo, b_hi))


def _rbf(A, B, gamma, precision):
    """exp(-gamma ||a - b||^2), A (p, m), B (p, n) -> (m, n)."""
    an = jnp.sum(A * A, axis=0)[:, None]
    bn = jnp.sum(B * B, axis=0)[None, :]
    z = _dot(A.T, B, precision)
    return jnp.exp(-gamma * jnp.maximum(an + bn - 2.0 * z, 0.0))


def srht_omega(key: jax.Array, n: int, r_prime: int) -> np.ndarray:
    """The dense n x r' SRHT test matrix the fit key defines, float64.

    Omega[i, c] = sign_i (-1)^popcount(i & row_c) / sqrt(n_pad), the
    Sylvester-Hadamard entry of sampled column row_c."""
    n_pad = 1 << (n - 1).bit_length()
    k_sketch, _ = jax.random.split(key)
    k_signs, k_rows = jax.random.split(k_sketch)
    signs = np.asarray(jax.random.rademacher(k_signs, (n_pad,),
                                             dtype=jnp.float32))[:n]
    rows = np.asarray(jax.random.choice(k_rows, n_pad, (r_prime,),
                                        replace=False)).astype(np.int64)
    bits = np.arange(n, dtype=np.int64)[:, None] & rows[None, :]
    parity = np.zeros(bits.shape, np.int64)
    while bits.any():
        parity ^= bits & 1
        bits >>= 1
    return (signs[:, None].astype(np.float64)
            * np.where(parity == 1, -1.0, 1.0) / np.sqrt(n_pad))


@functools.partial(jax.jit, static_argnames=("rows", "precision"))
def _sketch_rows(X, Omega, start, gamma, *, rows: int, precision: str):
    Xb = jax.lax.dynamic_slice_in_dim(X, start, rows, axis=1)
    return _dot(_rbf(Xb, X, gamma, precision), Omega, precision)


def sketch(X: jax.Array, gamma: float, omega: np.ndarray,
           precision: str = "highest") -> np.ndarray:
    """W = K Omega (n, r') in float32 on the device, up to ROW_BLOCK rows
    of K at a time (fewer where n is large); returned as float64 on the
    host."""
    n = X.shape[1]
    rows = min(max(128, min(ROW_BLOCK, K_BLOCK_ENTRIES // n) // 128 * 128),
               n)
    n_pad = -(-n // rows) * rows
    Xp = jnp.pad(X, ((0, 0), (0, n_pad - n)))
    Om = jnp.pad(jnp.asarray(omega, jnp.float32), ((0, n_pad - n), (0, 0)))
    # The padded columns of X contribute nothing: their Omega rows are 0.
    blocks = [np.asarray(_sketch_rows(Xp, Om, jnp.int32(s), gamma,
                                      rows=rows, precision=precision))
              for s in range(0, n_pad, rows)]
    return np.concatenate(blocks, axis=0)[:n].astype(np.float64)


def eig(W: np.ndarray, omega: np.ndarray, r: int):
    """Alg. 1 lines 3-6 in float64: (eigvals (r,), U (n, r))."""
    Q, _ = np.linalg.qr(W)
    qto = Q.T @ omega
    qtw = Q.T @ W
    # B qto = qtw, solved as qto^T B^T = qtw^T
    bt, *_ = np.linalg.lstsq(qto.T, qtw.T, rcond=None)
    B = 0.5 * (bt + bt.T)
    evals, V = np.linalg.eigh(B)
    evals = np.maximum(evals[::-1], 0.0)
    V = V[:, ::-1]
    return evals[:r], Q @ V[:, :r]


def kmeans(Y: np.ndarray, k: int, seed: int, restarts: int = 10,
           max_iter: int = 100):
    """Lloyd from k-means++ seeds, best objective of `restarts`; Y (n, r).
    Returns (labels (n,), centroids (k, r))."""
    rng = np.random.default_rng(seed)
    yy = np.sum(Y * Y, axis=1)
    best = None
    for _ in range(restarts):
        C = np.empty((k, Y.shape[1]))
        C[0] = Y[rng.integers(len(Y))]
        d2 = np.sum((Y - C[0]) ** 2, axis=1)
        for i in range(1, k):
            C[i] = Y[rng.choice(len(Y), p=d2 / d2.sum())]
            d2 = np.minimum(d2, np.sum((Y - C[i]) ** 2, axis=1))
        labels = None
        for _ in range(max_iter):
            D = yy[:, None] + np.sum(C * C, axis=1)[None, :] - 2.0 * Y @ C.T
            new = np.argmin(D, axis=1)
            if labels is not None and np.array_equal(new, labels):
                break
            labels = new
            for c in range(k):
                members = Y[labels == c]
                if len(members):
                    C[c] = members.mean(axis=0)
        obj = float(np.sum(np.min(D, axis=1)))
        if best is None or obj < best[0]:
            best = (obj, labels.copy(), C.copy())
    return best[1], best[2]


@functools.partial(jax.jit, static_argnames=("precision",))
def _embed_block(X, P, Xq, gamma, *, precision: str):
    return _dot(P, _rbf(X, Xq, gamma, precision), precision)


def embed(X: jax.Array, proj: np.ndarray, Xq: np.ndarray, gamma: float,
          precision: str = "highest", block: int = 512) -> np.ndarray:
    """y = proj kappa(X, x) for every column of Xq, (r, b) float64."""
    P = jnp.asarray(proj, jnp.float32)
    b = Xq.shape[1]
    b_pad = -(-b // block) * block
    Xqp = np.zeros((Xq.shape[0], b_pad), np.float32)
    Xqp[:, :b] = Xq
    out = [np.asarray(_embed_block(X, P, jnp.asarray(Xqp[:, s:s + block]),
                                   gamma, precision=precision))
           for s in range(0, b_pad, block)]
    return np.concatenate(out, axis=1)[:, :b].astype(np.float64)


def nearest(Y: np.ndarray, C: np.ndarray):
    """(labels, squared distance) of the columns of Y (r, b) to the
    centroids C (k, r)."""
    D = np.sum((Y.T[:, None, :] - C[None, :, :]) ** 2, axis=2)
    return np.argmin(D, axis=1), np.min(D, axis=1)


@functools.partial(jax.jit, static_argnames=("precision",))
def _cross_rows(A, B, Omega_b, gamma, *, precision: str):
    return _dot(_rbf(A, B, gamma, precision), Omega_b, precision)


class Fit:
    """The reference fit of X under one fit key, at `precision`."""

    def __init__(self, X: jax.Array, gamma: float, key: jax.Array, r: int,
                 r_prime: int, k: int, seed: int,
                 precision: str = "highest"):
        n = X.shape[1]
        self.X, self.gamma, self.precision = X, gamma, precision
        self.omega = srht_omega(key, n, r_prime)
        self.W = sketch(X, gamma, self.omega, precision)
        self.eigvals, self.U = eig(self.W, self.omega, r)
        self.Y = np.sqrt(self.eigvals)[:, None] * self.U.T
        self.labels, self.centroids = kmeans(self.Y.T, k, seed)
        self.proj = (1.0 / np.sqrt(self.eigvals))[:, None] * self.U.T

    def applied_sketch(self, n_applied: int) -> np.ndarray:
        """The sketch of the first n_applied columns alone, in the rows of
        all n: W[:a] - K[:a, a:] Omega[a:] over rows < a, zero below
        (the state a streaming fit holds once n_applied columns are
        folded in)."""
        n = self.W.shape[0]
        out = np.zeros_like(self.W)
        if n_applied == n:
            out[:] = self.W
        elif n_applied > 0:
            tail = np.asarray(_cross_rows(
                self.X[:, :n_applied], self.X[:, n_applied:],
                jnp.asarray(self.omega[n_applied:], jnp.float32),
                self.gamma, precision=self.precision), np.float64)
            out[:n_applied] = self.W[:n_applied] - tail
        return out


def label_mismatches(got: np.ndarray, want: np.ndarray, k: int) -> int:
    """Points whose labels differ once the cluster ids are matched (the
    assignment of ids that agrees on most points)."""
    got = np.asarray(got, np.int64)
    want = np.asarray(want, np.int64)
    m = max(k, int(got.max()) + 1, int(want.max()) + 1)
    table = np.zeros((m, m), np.int64)
    np.add.at(table, (got, want), 1)
    rows, cols = linear_sum_assignment(-table)
    return int(len(got) - table[rows, cols].sum())


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Frobenius norm of the difference over the reference's."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-300))
