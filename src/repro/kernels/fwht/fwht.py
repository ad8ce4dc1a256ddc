"""Pallas TPU kernel: Fast Walsh-Hadamard transform along axis 0.

TPU adaptation of the paper's pthread-parallel C/mex FWHT (DESIGN.md §3).

Tiling strategy
---------------
x is (n, c), n = 2^m. The grid runs over column tiles; each program instance
holds an (n_block, col_tile) slab in VMEM and performs ALL log2(n_block)
butterfly stages over it before writing back — HBM traffic is exactly one
read + one write per super-stage instead of one per stage (the naive
pay-per-stage schedule is log2(n)x more HBM traffic; that is the whole
perf argument for fusing stages in VMEM).

For n larger than a VMEM slab, ops.py factorizes H_n = (H_a (x) I_b) .
(I_a (x) H_b): two grid sweeps of this same kernel around a transpose, so
the per-sweep working set stays (<= 2^10, 128) floats. Butterflies are VPU
adds/subs on (8,128)-aligned tiles; there is no MXU work in this kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fwht_kernel(x_ref, o_ref, *, n: int, scale: float):
    """All log2(n) stages fused over a VMEM-resident (n, ct) slab."""
    x = x_ref[...]                      # (n, ct) in VMEM
    ct = x.shape[1]
    h = 1
    while h < n:
        x = x.reshape(n // (2 * h), 2, h, ct)
        a = x[:, 0]
        b = x[:, 1]
        x = jnp.stack([a + b, a - b], axis=1)
        h *= 2
    o_ref[...] = x.reshape(n, ct) * scale


def fwht_1level(x: jnp.ndarray, col_tile: int = 128, normalize: bool = True,
                interpret: bool = False) -> jnp.ndarray:
    """FWHT for n small enough that an (n, col_tile) slab fits VMEM."""
    n, c = x.shape
    if n & (n - 1):
        raise ValueError(f"power-of-two length required, got {n}")
    col_tile = min(col_tile, c)
    if c % col_tile:
        pad = col_tile - c % col_tile
        x = jnp.pad(x, ((0, 0), (0, pad)))
    cp = x.shape[1]
    scale = float(1.0 / (n ** 0.5)) if normalize else 1.0
    out = pl.pallas_call(
        functools.partial(_fwht_kernel, n=n, scale=scale),
        out_shape=jax.ShapeDtypeStruct((n, cp), x.dtype),
        grid=(cp // col_tile,),
        in_specs=[pl.BlockSpec((n, col_tile), lambda j: (0, j))],
        out_specs=pl.BlockSpec((n, col_tile), lambda j: (0, j)),
        interpret=interpret,
    )(x)
    return out[:, :c]
