"""Pallas TPU kernel: fused gram->projection serving stripe.

The out-of-sample extension y(x) = Sigma^{-1/2} U^T kappa(X_train, x)
(serve/extend.py) consumes the (n, w) kernel stripe kappa(X_train, X_q)
only to contract it against the tiny projection P = Sigma^{-1/2} U^T
(r, n). Running gram and projection as two executables round-trips the
(n, w) stripe through HBM; this kernel keeps it on-chip: each grid
instance builds one (bm, w) gram tile (MXU matmul + fused VPU
nonlinearity, same tiling as kernels/gram) and immediately contracts it
with the matching (r, bm) tile of P into a VMEM-resident (r, w)
accumulator. The (n, w) stripe never exists outside VMEM, so stripe HBM
traffic drops from O(n*w + n*(p+r)) to O(n*(p+r) + w*(p+r)).

Tiling: grid over row tiles i of the training set; instance i holds
X_i (p, bm), P_i (r, bm) and X_q (p, w) in VMEM (X_q and the (r, w)
output use constant index maps, so Pallas keeps both resident across the
grid — the output block is revisited, zeroed at i=0 and accumulated into
thereafter). MXU dims: (bm x p) @ (p x w) then (r x bm) @ (bm x w);
bm, w multiples of 128, r padded to 8 sublanes by the ops.py wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.registry import KERNEL_PRECISION


def _extend_embed_kernel(xi_ref, pi_ref, xb_ref, o_ref, *, kind: str,
                         gamma: float, degree: int):
    i = pl.program_id(0)
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
    pi = pi_ref[...]                    # (r, bm)
    part = jax.lax.dot_general(pi, k, (((1,), (0,)), ((), ())),
                               precision=KERNEL_PRECISION,
                               preferred_element_type=jnp.float32)  # (r, w)

    @pl.when(i == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += part.astype(o_ref.dtype)


def extend_embed_call(X: jnp.ndarray, P: jnp.ndarray, Xb: jnp.ndarray,
                      kind: str, gamma: float, degree: int, row_tile: int,
                      interpret: bool) -> jnp.ndarray:
    """P @ kappa(X, Xb); X (p, n), P (r, n), Xb (p, w), n % row_tile == 0."""
    p, n = X.shape
    r = P.shape[0]
    w = Xb.shape[1]
    return pl.pallas_call(
        functools.partial(_extend_embed_kernel, kind=kind, gamma=gamma,
                          degree=degree),
        out_shape=jax.ShapeDtypeStruct((r, w), jnp.float32),
        grid=(n // row_tile,),
        in_specs=[
            pl.BlockSpec((p, row_tile), lambda i: (0, i)),
            pl.BlockSpec((r, row_tile), lambda i: (0, i)),
            pl.BlockSpec((p, w), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((r, w), lambda i: (0, 0)),
        interpret=interpret,
    )(X, P, Xb)
