"""Pallas TPU kernel: blocked kernel-matrix stripe with fused nonlinearity.

The streaming pass of Alg. 1 consumes K in column stripes K[:, j:j+w] =
kappa(X, X[:, j:j+w]). On TPU this is an MXU matmul (X^T X_b, contraction
over the feature dim p) followed by a cheap VPU nonlinearity. Fusing the
nonlinearity into the same kernel means the raw inner-product tile never
round-trips to HBM: arithmetic intensity of the stripe pass doubles for
small p (the regime the paper targets — p=2..19 in its experiments).

Tiling: grid over row tiles i of the stripe; each instance holds
X_i (p, bm) and X_b (p, w) in VMEM (X_b is re-fetched per row tile via a
constant index map; Pallas keeps it resident across the grid since the
block index is unchanged), emits a (bm, w) tile of K. MXU dims: (bm x p) @
(p x w) — bm, w multiples of 128; p padded to 8 lanes by Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.registry import KERNEL_PRECISION


def _gram_kernel(xi_ref, xb_ref, o_ref, *, kind: str, gamma: float,
                 degree: int):
    xi = xi_ref[...]                    # (p, bm)
    xb = xb_ref[...]                    # (p, w)
    z = jax.lax.dot_general(xi, xb, (((0,), (0,)), ((), ())),
                            precision=KERNEL_PRECISION,
                            preferred_element_type=jnp.float32)  # (bm, w)
    if kind == "polynomial":
        k = (z + gamma) ** degree
    elif kind == "rbf":
        xn = jnp.sum(xi * xi, axis=0)[:, None]
        yn = jnp.sum(xb * xb, axis=0)[None, :]
        k = jnp.exp(-gamma * jnp.maximum(xn + yn - 2.0 * z, 0.0))
    else:  # linear
        k = z
    o_ref[...] = k.astype(o_ref.dtype)


def gram_stripe_call(X: jnp.ndarray, Xb: jnp.ndarray, kind: str,
                     gamma: float, degree: int, row_tile: int,
                     interpret: bool) -> jnp.ndarray:
    """K stripe kappa(X, Xb); X (p, n), Xb (p, w), n % row_tile == 0."""
    p, n = X.shape
    w = Xb.shape[1]
    return pl.pallas_call(
        functools.partial(_gram_kernel, kind=kind, gamma=gamma,
                          degree=degree),
        out_shape=jax.ShapeDtypeStruct((n, w), jnp.float32),
        grid=(n // row_tile,),
        in_specs=[
            pl.BlockSpec((p, row_tile), lambda i: (0, i)),
            pl.BlockSpec((p, w), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, w), lambda i: (i, 0)),
        interpret=interpret,
    )(X, Xb)
