"""Randomized sketching: SRHT test matrices and the one-pass eigendecomposition.

This is the computational heart of the paper (Alg. 1 lines 1-6):

    Omega = D H R            (n x r'), never materialized
    W     = K Omega          one streaming pass over column stripes of K
    Q     = r leading left singular vectors of W
    solve B (Q^T Omega) = Q^T W          <- the one-pass trick from [Halko et
                                            al. 2011, sec. 5.5]: no second
                                            pass over K to form Q^T K Q
    B     = V Sigma V^T  (eigh, PSD-projected)
    Y     = Sigma^{1/2} V^T Q^T  in R^{r x n}

`H` is the (normalized) Walsh-Hadamard transform, applied via FWHT in
O(n log n); on TPU the hot path is the Pallas kernel in
`repro.kernels.fwht` — this module's `fwht` is the pure-jnp oracle and the
CPU execution path. Cross-device FWHT lives in `repro.distributed.dfwht`.

Two call surfaces: `randomized_eig` returns the LowRankEig alone (Y, the
eigvals, and the orthonormal eigenvector basis U = Q V of K_hat);
`randomized_eig_with_state` additionally returns the sketch state (SRHT
signs/rows or the Gaussian Omega), which fully determines the fit given
(key, X) — repro.serve persists it inside the FittedModel artifact so a
deployment is reproducible from the artifact alone (ROADMAP "Serve
subsystem").
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.kernels_fn import KernelFn, stripe_iterator


# ---------------------------------------------------------------------------
# Walsh-Hadamard transform (pure-jnp reference / CPU path)
# ---------------------------------------------------------------------------

def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


@functools.partial(jax.jit, static_argnums=(1,))
def fwht(x: jnp.ndarray, normalize: bool = True) -> jnp.ndarray:
    """Fast Walsh-Hadamard transform along axis 0. x: (n, ...), n = 2^m.

    Iterative radix-2 butterflies; `n` is static so the python loop unrolls
    into log2(n) fused stages under jit. normalize=True applies 1/sqrt(n) so
    H is orthonormal (scaling cancels in Alg. 1 but keeps conditioning sane).
    """
    n = x.shape[0]
    if n & (n - 1):
        raise ValueError(f"FWHT needs power-of-two length, got {n}")
    orig_shape = x.shape
    x = x.reshape(n, -1)
    h = 1
    while h < n:
        x = x.reshape(n // (2 * h), 2, h, -1)
        a, b = x[:, 0], x[:, 1]
        x = jnp.stack([a + b, a - b], axis=1)
        h *= 2
    x = x.reshape(orig_shape)
    if normalize:
        x = x / jnp.sqrt(jnp.asarray(n, x.dtype))
    return x


# ---------------------------------------------------------------------------
# SRHT: Omega = D H R, held implicitly
# ---------------------------------------------------------------------------

class SRHT(NamedTuple):
    """Implicit Omega = D H R in R^{n_pad x r'} restricted to the top n rows.

    signs: (n_pad,) +-1 diagonal of D
    rows:  (r',) row indices sampled uniformly WITHOUT replacement (R)
    n:     true (unpadded) dimension
    n_pad: power-of-two padded dimension
    """
    signs: jnp.ndarray
    rows: jnp.ndarray
    n: int
    n_pad: int

    @property
    def r_prime(self) -> int:
        return self.rows.shape[0]


def make_srht(key: jax.Array, n: int, r_prime: int) -> SRHT:
    n_pad = next_pow2(n)
    k1, k2 = jax.random.split(key)
    signs = jax.random.rademacher(k1, (n_pad,), dtype=jnp.float32)
    rows = jax.random.choice(k2, n_pad, (r_prime,), replace=False)
    return SRHT(signs=signs, rows=rows, n=n, n_pad=n_pad)


def srht_apply_t(srht: SRHT, M: jnp.ndarray,
                 fwht_fn: Optional[Callable] = None) -> jnp.ndarray:
    """Compute Omega^T M = R^T H (D M) for M of shape (n, b) -> (r', b).

    This is the ONLY way Omega touches data: scale rows by D, FWHT over the
    (zero-padded) row axis, gather the sampled rows. O(n_pad log n_pad * b).
    `fwht_fn` lets callers swap in the Pallas kernel or the distributed FWHT.
    """
    fwht_fn = fwht_fn or fwht
    n, b = M.shape
    if n != srht.n:
        raise ValueError(f"expected {srht.n} rows, got {n}")
    Mp = jnp.pad(M, ((0, srht.n_pad - n), (0, 0)))
    Mp = Mp * srht.signs[:, None]
    Mp = fwht_fn(Mp)
    return Mp[srht.rows]


def srht_apply(srht: SRHT, V: jnp.ndarray,
               fwht_fn: Optional[Callable] = None) -> jnp.ndarray:
    """Compute Omega V for V of shape (r', b) -> (n, b). (D H R V; H, D sym.)"""
    fwht_fn = fwht_fn or fwht
    scatter = jnp.zeros((srht.n_pad, V.shape[1]), V.dtype).at[srht.rows].set(V)
    out = fwht_fn(scatter)
    out = out * srht.signs[:, None]
    return out[:srht.n]


def srht_rows_at(idx: jnp.ndarray, signs: jnp.ndarray, rows: jnp.ndarray,
                 n_pad) -> jnp.ndarray:
    """Materialize rows `idx` (int32, may be traced) of the implicit
    Omega = D H R, given `signs` = the D entries of those rows.

    Omega[i, c] = signs[i] * (-1)^popcount(i & rows[c]) / sqrt(n_pad) —
    the Sylvester/Hadamard entry formula, i.e. exactly the value
    srht_apply_t would produce from the one-hot e_i column. O(b * r')
    time and memory for b rows, so the block updates of the streaming
    accumulator (repro.stream.accumulate) and the sharded fit
    (repro.distributed.fit) can apply the symmetric cross-term
    K_block @ Omega[rows] without a full FWHT over dead rows.
    """
    bits = jnp.bitwise_and(idx[:, None], rows.astype(jnp.int32)[None, :])
    parity = jax.lax.population_count(bits) & 1
    scale = 1.0 / jnp.sqrt(jnp.asarray(n_pad, jnp.float32))
    vals = jnp.where(parity == 1, -scale, scale)
    return signs[:, None] * vals


class GaussianSketch(NamedTuple):
    """Dense Gaussian Omega — the memory-hungry baseline Alg. 1 replaces."""
    omega: jnp.ndarray  # (n, r')


def make_gaussian(key: jax.Array, n: int, r_prime: int) -> GaussianSketch:
    return GaussianSketch(jax.random.normal(key, (n, r_prime)) /
                          jnp.sqrt(jnp.asarray(r_prime, jnp.float32)))


# ---------------------------------------------------------------------------
# One-pass randomized eigendecomposition (Alg. 1 lines 2-6)
# ---------------------------------------------------------------------------

class LowRankEig(NamedTuple):
    Y: jnp.ndarray        # (r, n) linearized samples: K_hat = Y^T Y
    Q: jnp.ndarray        # (n, r)
    eigvals: jnp.ndarray  # (r,) eigenvalues of B (>= 0)
    U: jnp.ndarray        # (n, r) orthonormal eigenvector basis Q V of K_hat


class SketchedEig(NamedTuple):
    """randomized_eig result WITH the sketch state Alg. 1 consumed.

    The sketch (SRHT signs/rows or the dense Gaussian Omega) fully
    determines the fit given (key, X); exposing it makes a fit
    reproducible and serializable — repro.serve persists it inside the
    FittedModel artifact.
    """
    eig: LowRankEig
    sketch: Tuple        # SRHT or GaussianSketch NamedTuple


def sketch_stream(kernel: KernelFn, X: jnp.ndarray, srht: SRHT,
                  block: int = 512,
                  fwht_fn: Optional[Callable] = None) -> jnp.ndarray:
    """W = K Omega in ONE streaming pass over column stripes of K.

    W^T = Omega^T K; stripe j of K contributes columns j of Omega^T K, i.e.
    rows j of W. Peak memory O(n * block + n * r') — K never materialized.
    """
    n = srht.n
    W = jnp.zeros((n, srht.r_prime), jnp.float32)
    for start, stripe in stripe_iterator(kernel, X, block):
        wt_block = srht_apply_t(srht, stripe, fwht_fn)   # (r', width)
        W = jax.lax.dynamic_update_slice(W, wt_block.T, (start, 0))
    return W


def one_pass_core(W: jnp.ndarray, omega_t_q_fn, r: int) -> LowRankEig:
    """Lines 3-6 of Alg. 1 given the sketch W = K Omega.

    omega_t_q_fn: callable Q -> Omega^T Q (n x r' -> r' x r'), so the core
    solve never revisits K and never materializes Omega.

    Note on Alg. 1 line 3: the paper writes "Q in R^{n x r}", but truncating
    the basis to r columns BEFORE the core solve throws away the
    oversampling benefit (the residual Q^T K (I - QQ^T) Omega pollutes the
    lstsq solve whenever the rank-r basis is inexact). Halko et al. (sec.
    5.5), which the paper cites for this step, keep the full r' = r + l
    columns of Q and truncate at the final eigendecomposition — that is what
    reproduces the paper's own Table 1 accuracy (err 0.40 == exact), so we
    follow Halko. The truncated variant is available for ablation via
    truncate_basis=True in randomized_eig.
    """
    # Line 3: orthonormal basis for range(W), r' columns (see note above).
    Q, _ = jnp.linalg.qr(W)                       # (n, r')
    # Line 4: solve B (Q^T Omega) = (Q^T W).
    QtO = omega_t_q_fn(Q).T                       # (r', r')
    QtW = Q.T @ W                                 # (r', r')
    # B QtO = QtW  =>  QtO^T B^T = QtW^T ; B symmetric in exact arithmetic.
    Bt, *_ = jnp.linalg.lstsq(QtO.T, QtW.T)
    B = 0.5 * (Bt + Bt.T)
    # Line 5: eigendecomposition, projected to PSD, truncated to rank r.
    evals, V = jnp.linalg.eigh(B)
    evals = jnp.maximum(evals[::-1], 0.0)         # descending, clipped
    V = V[:, ::-1]
    # Line 6: Y = Sigma^{1/2} V^T Q^T = Sigma^{1/2} U^T  in R^{r x n},
    # where U = Q V is the (orthonormal) eigenvector basis of
    # K_hat = U Sigma U^T — the out-of-sample extension operator
    # (repro.serve) is Sigma^{-1/2} U^T.
    U = Q @ V[:, :r]
    Y = jnp.sqrt(evals[:r])[:, None] * U.T
    return LowRankEig(Y=Y, Q=Q[:, :r], eigvals=evals[:r], U=U)


def randomized_eig_with_state(key: jax.Array, kernel: KernelFn,
                              X: jnp.ndarray, r: int,
                              oversampling: int = 10, block: int = 512,
                              sketch_type: str = "srht",
                              fwht_fn: Optional[Callable] = None,
                              truncate_basis: bool = False) -> SketchedEig:
    """randomized_eig that also returns the sketch state (SRHT / Gaussian).

    repro.serve persists the sketch in the fitted artifact so a
    deployment is reproducible from the artifact alone.
    """
    n = X.shape[1]
    r_prime = r + oversampling
    if sketch_type == "srht":
        sketch = make_srht(key, n, r_prime)
        W = sketch_stream(kernel, X, sketch, block, fwht_fn)

        def omega_t_q(Q):
            return srht_apply_t(sketch, Q, fwht_fn)
    elif sketch_type == "gaussian":
        sketch = make_gaussian(key, n, r_prime)
        W = jnp.zeros((n, r_prime), jnp.float32)
        for start, stripe in stripe_iterator(kernel, X, block):
            W = jax.lax.dynamic_update_slice(
                W, stripe.T @ sketch.omega, (start, 0))  # rows = stripe^T Om

        def omega_t_q(Q):
            return sketch.omega.T @ Q
    else:
        raise ValueError(f"unknown sketch_type {sketch_type!r}")
    if truncate_basis:
        # Literal Alg. 1 line 3: project the sketch onto its r leading left
        # singular vectors before the core solve (ablation; loses the
        # oversampling benefit — see one_pass_core docstring).
        U, S, Vt = jnp.linalg.svd(W, full_matrices=False)
        W = (U[:, :r] * S[None, :r]) @ Vt[:r]
    return SketchedEig(eig=one_pass_core(W, omega_t_q, r), sketch=sketch)


def randomized_eig(key: jax.Array, kernel: KernelFn, X: jnp.ndarray, r: int,
                   oversampling: int = 10, block: int = 512,
                   sketch_type: str = "srht",
                   fwht_fn: Optional[Callable] = None,
                   truncate_basis: bool = False) -> LowRankEig:
    """End-to-end one-pass randomized eigendecomposition of K = kappa(X, X).

    sketch_type: 'srht' (the paper's structured Omega = D H R) or 'gaussian'
    (the dense baseline whose memory/time cost motivates SRHT).
    truncate_basis: ablation flag — truncate Q to r columns BEFORE the core
    solve (Alg. 1 line 3 read literally; see one_pass_core docstring).
    """
    return randomized_eig_with_state(key, kernel, X, r, oversampling, block,
                                     sketch_type, fwht_fn,
                                     truncate_basis).eig
