"""Pallas TPU kernel: fused Lloyd assignment (distance + argmin).

Per iteration, K-means computes an (n, k) distance matrix only to take its
row-wise argmin. Fusing the -2 Y C^T matmul (MXU), the norm corrections and
the argmin (VPU) means the (n, k) intermediate never leaves VMEM: HBM
traffic drops from O(n*k + n*r) to O(n*r + n) per iteration, which is the
memory-bound term for the small-r regime of the paper (r = 2..16, k <= 100).

Tiling: grid over row tiles of Y; centroids (k, r) are tiny and pinned in
VMEM for the whole sweep. Tiles are (bm, r_pad) x (r_pad, k_pad) on the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.registry import KERNEL_PRECISION


def _assign_kernel(y_ref, c_ref, lab_ref, d2_ref, *, k: int):
    y = y_ref[...]                      # (bm, r)
    c = c_ref[...]                      # (k_pad, r)
    z = jax.lax.dot_general(y, c, (((1,), (1,)), ((), ())),
                            precision=KERNEL_PRECISION,
                            preferred_element_type=jnp.float32)  # (bm, k_pad)
    yn = jnp.sum(y * y, axis=1)[:, None]
    cn = jnp.sum(c * c, axis=1)[None, :]
    d2 = jnp.maximum(yn + cn - 2.0 * z, 0.0)
    # Mask padded centroids out of the argmin.
    k_pad = d2.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, k_pad), 1)
    d2 = jnp.where(col < k, d2, jnp.inf)
    lab_ref[...] = jnp.argmin(d2, axis=1).astype(jnp.int32)
    d2_ref[...] = jnp.min(d2, axis=1)


def assign_call(Y: jnp.ndarray, C: jnp.ndarray, k: int, row_tile: int,
                interpret: bool):
    n, r = Y.shape
    k_pad = C.shape[0]
    return pl.pallas_call(
        functools.partial(_assign_kernel, k=k),
        out_shape=(jax.ShapeDtypeStruct((n,), jnp.int32),
                   jax.ShapeDtypeStruct((n,), jnp.float32)),
        grid=(n // row_tile,),
        in_specs=[
            pl.BlockSpec((row_tile, r), lambda i: (i, 0)),
            pl.BlockSpec((k_pad, r), lambda i: (0, 0)),
        ],
        out_specs=(pl.BlockSpec((row_tile,), lambda i: (i,)),
                   pl.BlockSpec((row_tile,), lambda i: (i,))),
        interpret=interpret,
    )(Y, C)
