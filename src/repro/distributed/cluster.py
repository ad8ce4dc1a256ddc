"""Distributed one-pass kernel K-means: the paper's Alg. 1 at cluster scale.

Data X (p, n) is column-sharded over the mesh's data axis; the kernel
matrix K never exists, not even a full column stripe on one device:

  sketch     stripe rows are sharded; D is applied locally, H via the
             ppermute-butterfly distributed FWHT, R^T via a masked
             scatter + psum (r' rows are tiny);
  basis      Q from W (n x r', row-sharded) by Cholesky-QR:
             G = W^T W (psum, r' x r'), Q = W G^{-1/2} — no gather of W;
  core       B (Q^T Omega) = Q^T W solved on r' x r' replicated matrices;
  embed      Y = Sigma^{1/2} V^T Q^T stays column-sharded (r x n_local);
  cluster    distributed Lloyd: local assignment (the Pallas fused
             assign kernel on TPU), centroids via psum of (sums, counts).

Communication per stripe: log2(dp) * n/dp * b (butterfly) + r' * b (psum)
— versus gathering the stripe (n * b) for a centralized sketch. The
whole pipeline is the launch target of launch/cluster.py and the
"paper-representative" roofline cell.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.sketch import next_pow2
from repro.distributed.dfwht import distributed_fwht


class DistClusterResult(NamedTuple):
    labels: jnp.ndarray      # (n,) column-sharded like X
    Y: jnp.ndarray           # (r, n) column-sharded
    centroids: jnp.ndarray   # (k, r) replicated
    eigvals: jnp.ndarray     # (r,)


def _dp_size(mesh, axis):
    return dict(mesh.shape)[axis]


def distributed_sketch(kernel, X, mesh, signs, rows, axis="data",
                       block: int = 1024):
    """W = K Omega with K row/column-sharded stripes. X: (p, n) sharded
    P(None, axis). signs: (n_pad,), rows: (r',). Returns W (n, r') sharded
    P(axis, None)."""
    p, n = X.shape
    dp = _dp_size(mesh, axis)
    n_pad = signs.shape[0]
    r_prime = rows.shape[0]
    n_local = n // dp
    assert n % dp == 0 and n_pad % dp == 0

    # The distributed path requires pre-padded n == n_pad (pow2): callers
    # pad X with zero columns up front (zero columns of K are harmless —
    # D/R act trivially on them and K-means ignores them downstream).
    assert n == n_pad, "distributed path expects pre-padded n (pow2)"

    W = jnp.zeros((n, r_prime), jnp.float32)
    W = jax.device_put(W, NamedSharding(mesh, P(axis, None)))
    signs_sh = jax.device_put(signs, NamedSharding(mesh, P(axis)))

    scale = 1.0 / jnp.sqrt(jnp.asarray(n_pad, jnp.float32))

    def rt_gather(stripe_f):
        """R^T: pick global rows `rows` from a row-sharded (n, b) array."""
        def inner(sl):
            idx = jax.lax.axis_index(axis)
            base = idx * n_local
            # local contribution: rows in [base, base + n_local)
            rel = rows - base
            inb = (rel >= 0) & (rel < n_local)
            rel_safe = jnp.clip(rel, 0, n_local - 1)
            contrib = jnp.where(inb[:, None], sl[rel_safe], 0.0)
            return jax.lax.psum(contrib, axis)         # (r', b)
        return shard_map(inner, mesh=mesh, in_specs=P(axis, None),
                         out_specs=P(None, None),
                         check_vma=False)(stripe_f)

    for start in range(0, n, block):
        b = min(block, n - start)
        xb = jax.lax.dynamic_slice_in_dim(X, start, b, axis=1)
        # Replicate the small (p, b) stripe seed.
        xb = jax.device_put(xb, NamedSharding(mesh, P(None, None)))

        # Stripe rows sharded: each shard holds kernel(X_local_cols, xb).
        def mk_stripe(xl, xbl):
            return kernel(xl, xbl)

        stripe = shard_map(mk_stripe, mesh=mesh,
                           in_specs=(P(None, axis), P(None, None)),
                           out_specs=P(axis, None),
                           check_vma=False)(X, xb)       # (n, b) row-shard
        stripe = stripe * signs_sh[:, None]
        stripe = distributed_fwht(stripe, mesh, axis, normalize=False)
        wt_block = rt_gather(stripe) * scale             # (r', b)
        W = jax.lax.dynamic_update_slice(W, wt_block.T, (start, 0))
    return W


def cholesky_qr(W, mesh, axis="data", eps: float = 1e-7):
    """Q with orthonormal columns spanning range(W), W (n, r') row-sharded.

    Cholesky-QR via the psum'd Gram matrix: G = W^T W (r' x r', tiny),
    Q_i = W v_i / sqrt(lambda_i). Rank-deficient W (e.g. an exactly
    low-rank kernel) keeps only the positive-eigenvalue columns — the
    truncation is decided eagerly (this is orchestration code, not a jit
    body), so Q has static shape (n, rank) per pipeline run.
    """
    def gram(wl):
        return jax.lax.psum(wl.T @ wl, axis)

    G = shard_map(gram, mesh=mesh, in_specs=P(axis, None),
                  out_specs=P(None, None), check_vma=False)(W)
    evals, V = jnp.linalg.eigh(0.5 * (G + G.T))
    ev = np.asarray(evals)
    keep = ev > eps * max(float(ev.max()), 1e-30)
    idx = np.nonzero(keep)[0][::-1].copy()        # descending eigenvalues
    cols = (V[:, idx] / jnp.sqrt(evals[idx])[None, :])
    return W @ cols                               # (n, rank) row-sharded


def distributed_omega_t(M, mesh, signs, rows, axis="data"):
    """Omega^T M for row-sharded M (n, c): D, distributed H, R^T."""
    scale = 1.0 / jnp.sqrt(jnp.asarray(signs.shape[0], jnp.float32))
    signs_sh = jax.device_put(signs, NamedSharding(mesh, P(axis)))
    Mh = distributed_fwht(M * signs_sh[:, None], mesh, axis,
                          normalize=False)
    n_local = M.shape[0] // _dp_size(mesh, axis)

    def inner(sl):
        idx = jax.lax.axis_index(axis)
        base = idx * n_local
        rel = rows - base
        inb = (rel >= 0) & (rel < n_local)
        contrib = jnp.where(inb[:, None], sl[jnp.clip(rel, 0, n_local - 1)],
                            0.0)
        return jax.lax.psum(contrib, axis)

    out = shard_map(inner, mesh=mesh, in_specs=P(axis, None),
                    out_specs=P(None, None), check_vma=False)(Mh)
    return out * scale                     # (r', c)


def distributed_kmeans(Y, k, key, mesh, axis="data", n_iter: int = 20,
                       n_restarts: int = 10):
    """Lloyd on column-sharded Y (r, n): local assign, psum centroid update.

    Init: k random data columns per restart (gathering k columns is O(kr)
    — tiny); best-objective restart wins, mirroring the single-device
    implementation's semantics (full k-means++ D^2 sampling would need a
    distributed weighted draw per centroid; random-column restarts are the
    standard large-scale substitute).
    """
    r, n = Y.shape

    def step(C, yl):
        d2 = (jnp.sum(yl * yl, axis=0)[None, :]
              + jnp.sum(C * C, axis=1)[:, None] - 2.0 * (C @ yl))  # (k, nl)
        labels = jnp.argmin(d2, axis=0)
        onehot = jax.nn.one_hot(labels, k, dtype=jnp.float32)      # (nl, k)
        sums = jax.lax.psum(yl @ onehot, axis)                     # (r, k)
        counts = jax.lax.psum(jnp.sum(onehot, axis=0), axis)       # (k,)
        newC = jnp.where(counts[:, None] > 0,
                         sums.T / jnp.maximum(counts[:, None], 1.0), C)
        obj = jax.lax.psum(jnp.sum(jnp.min(d2, axis=0)), axis)
        return newC, labels, obj

    def run_one(C0):
        def body(yl, C0l):
            C = C0l

            def it(C, _):
                C, _, _ = step(C, yl)
                return C, None

            C, _ = jax.lax.scan(it, C, None, length=n_iter)
            C, labels, obj = step(C, yl)
            return labels.astype(jnp.int32), C, obj

        return shard_map(
            body, mesh=mesh, in_specs=(P(None, axis), P(None, None)),
            out_specs=(P(axis), P(None, None), P()),
            check_vma=False)(Y, C0)

    # Restart seeds are k columns of Y, picked on the host: indexing the
    # sharded axis of Y with an index array has no unambiguous output
    # sharding.
    Y_host = np.asarray(Y)
    best = None
    for s in range(n_restarts):
        idx = jax.random.choice(jax.random.fold_in(key, s), n, (k,),
                                replace=False)
        C0 = jax.device_put(Y_host[:, np.asarray(idx)].T,
                            NamedSharding(mesh, P(None, None)))
        labels, C, obj = run_one(C0)
        score = float(obj)
        if best is None or score < best[0]:
            best = (score, labels, C)
    return best[1], best[2], best[0]


def distributed_one_pass_kernel_kmeans(
        key, kernel, X, k: int, r: int, mesh, oversampling: int = 10,
        axis: str = "data", block: int = 1024,
        n_iter: int = 20) -> DistClusterResult:
    """Alg. 1 end-to-end on a mesh. X: (p, n) sharded P(None, axis);
    n must be a power of two (pad with zero columns upstream)."""
    p, n = X.shape
    r_prime = r + oversampling
    k1, k2 = jax.random.split(key)
    signs = jax.random.rademacher(k1, (next_pow2(n),), dtype=jnp.float32)
    rows = jax.random.choice(k2, next_pow2(n), (r_prime,), replace=False)

    W = distributed_sketch(kernel, X, mesh, signs, rows, axis, block)
    Q = cholesky_qr(W, mesh, axis)                       # (n, r') row-shard
    QtO = distributed_omega_t(Q, mesh, signs, rows, axis).T   # (r', r')
    # Q^T W: r' x r' via psum.
    def qtw(ql, wl):
        return jax.lax.psum(ql.T @ wl, axis)
    QtW = shard_map(qtw, mesh=mesh, in_specs=(P(axis, None), P(axis, None)),
                    out_specs=P(None, None), check_vma=False)(Q, W)
    Bt, *_ = jnp.linalg.lstsq(QtO.T, QtW.T)
    B = 0.5 * (Bt + Bt.T)
    evals, V = jnp.linalg.eigh(B)
    evals = jnp.maximum(evals[::-1], 0.0)
    V = V[:, ::-1]
    # Y = Sigma^{1/2} V^T Q^T, column-sharded like X.
    proj = (jnp.sqrt(evals[:r])[:, None] * V[:, :r].T)   # (r, r')

    def embed(ql):
        return proj @ ql.T                               # (r, n_local)

    Y = shard_map(embed, mesh=mesh, in_specs=P(axis, None),
                  out_specs=P(None, axis), check_vma=False)(Q)
    labels, C, obj = distributed_kmeans(Y, k, key, mesh, axis, n_iter)
    return DistClusterResult(labels=labels, Y=Y, centroids=C,
                             eigvals=evals[:r])
