"""Pure-jnp oracle for the fused fit-sketch accumulate kernel."""
import jax.numpy as jnp

from repro.kernels.gram.ref import gram_stripe_ref


def fit_sketch_ref(X: jnp.ndarray, Omega: jnp.ndarray, C: jnp.ndarray,
                   Ocross: jnp.ndarray, V: jnp.ndarray = None,
                   kind: str = "polynomial", gamma: float = 0.0,
                   degree: int = 2):
    """All four contractions of K = kappa(X, C) the fit update consumes.

    X (p, m), Omega (m, r'), C (p, b), Ocross (b, r'), V (8, m) row 0
    the row-validity mask (None = all valid). Returns
      new_rows (b, r') = K^T Omega    (the b new sketch rows)
      delta    (m, r') = K Ocross     (cross-term update, caller masks)
      rn_rows  (m,)    = row sums of K*K
      rn_cols  (b,)    = V-masked column sums of K*K
    """
    K = gram_stripe_ref(X, C, kind=kind, gamma=gamma, degree=degree)
    vm = (jnp.ones((X.shape[1],), jnp.float32) if V is None
          else V[0].astype(jnp.float32))
    new_rows = K.T @ Omega
    delta = K @ Ocross
    rn_rows = jnp.sum(K * K, axis=1)
    rn_cols = vm @ (K * K)
    return new_rows, delta, rn_rows, rn_cols


def fit_sketch_inplace_ref(X: jnp.ndarray, Omega: jnp.ndarray,
                           W: jnp.ndarray, rn: jnp.ndarray, q: int, b: int,
                           kind: str = "polynomial", gamma: float = 0.0,
                           degree: int = 2):
    """One fit block [q, q+b) folded into the state (W, rn), in the
    layout of fit_sketch_inplace: Omega (m_pad, rp) the sketch rows, W
    (S, rp), rn (8, S) with the row norms in row 0. Returns (W, rn)
    with W[:q] += K[:q] Omega[q:q+b], W[q:q+b] = K^T Omega[:q+b], and
    the row and column sums of K*K likewise in rn[0], for K =
    kappa(X[:, :q+b], X[:, q:q+b])."""
    K = gram_stripe_ref(X[:, :q + b], X[:, q:q + b], kind=kind,
                        gamma=gamma, degree=degree)
    K2 = K * K
    W = W.at[:q].add(K[:q] @ Omega[q:q + b])
    W = W.at[q:q + b].set(K.T @ Omega[:q + b])
    rn = rn.at[0, :q].add(jnp.sum(K2[:q], axis=1))
    rn = rn.at[0, q:q + b].set(jnp.sum(K2, axis=0))
    return W, rn
