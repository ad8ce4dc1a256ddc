"""Pallas TPU kernel: fused gram-stripe -> sketch-accumulate for fit.

The one-pass training update (stream/accumulate.py) consumes each
(m, b) kernel block Kc = kappa(X, C) three ways: contracted against the
sketch rows Omega[:m] into the b new sketch rows (new_rows = Kc^T Omega),
contracted against the block's own sketch rows into the cross-term update
of the already-applied sketch rows (delta = Kc Omega[q:q+b]), and
squared-and-summed both ways for the Frobenius ledger row_norms2. Running
those as separate executables round-trips the (m, b) block through HBM
between the gram build and every contraction — the exact traffic
kernels/extend_embed deletes on the serving path. This kernel applies the
same trick to training: each grid instance builds one (bm, b) gram tile
(MXU matmul + fused VPU nonlinearity, same tiling as kernels/gram) and
immediately contracts/reduces it into all four outputs, with the (b, r')
sketch accumulator VMEM-resident across the grid (constant output index
map, zeroed at i=0, accumulated into thereafter — the extend_embed
accumulator pattern). The (m, b) block never exists outside VMEM.

Tiling: grid over row tiles i of X; instance i holds X_i (p, bm),
O_i (bm, r'), V_i (8, bm) plus the resident C (p, b), Ocross (b, r'),
and the resident accumulators acc (b, r') / rn_col (8, b). Outputs
delta (bm, r') and rn_row (bm, 128) are written tile by tile. MXU dims:
(bm x p)@(p x b), (b x bm)@(bm x r'), (bm x b)@(b x r'); bm, b, r'
multiples of 128, masks in 8-sublane rows.

Border bound: a fit block needs only the rows [0, q+b) of X. The tile
count nt = cdiv(q+b, bm) is a scalar-prefetch operand; every moving
block's index map is clamped to tile nt-1, so steps i >= nt issue no
new DMA, and `pl.when(i < nt)` skips their compute. The grid stays
static (m / bm steps), so one executable serves every block of a fit.

Exactness of padding/masking (see ops.py): garbage gram rows (padded or
invalid X columns) are annihilated by zero rows of O (new_rows), masked
by V (rn_col) or sliced/masked by the caller (delta, rn_row); garbage
gram COLUMNS (padded C columns) are annihilated by zero rows of Ocross
(delta), excluded by the static b_real column mask (rn_row) or sliced by
the caller (new_rows, rn_col).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.registry import KERNEL_PRECISION


def _fit_sketch_kernel(nt_ref, xi_ref, oi_ref, xb_ref, ocr_ref, vi_ref,
                       acc_ref, dl_ref, rnr_ref, rnc_ref, *, kind: str,
                       gamma: float, degree: int, b_real: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        rnc_ref[...] = jnp.zeros_like(rnc_ref)

    @pl.when(i < nt_ref[0])
    def _():
        xi = xi_ref[...]                    # (p, bm)   X row tile
        xb = xb_ref[...]                    # (p, w)    block columns C
        z = jax.lax.dot_general(xi, xb, (((0,), (0,)), ((), ())),
                                precision=KERNEL_PRECISION,
                                preferred_element_type=jnp.float32)
        if kind == "polynomial":
            k = (z + gamma) ** degree
        elif kind == "rbf":
            xn = jnp.sum(xi * xi, axis=0)[:, None]
            yn = jnp.sum(xb * xb, axis=0)[None, :]
            k = jnp.exp(-gamma * jnp.maximum(xn + yn - 2.0 * z, 0.0))
        else:  # linear
            k = z
        oi = oi_ref[...]                    # (bm, rp)  sketch rows of tile
        acc_part = jax.lax.dot_general(k, oi, (((0,), (0,)), ((), ())),
                                       precision=KERNEL_PRECISION,
                                       preferred_element_type=jnp.float32)
        ocr = ocr_ref[...]                  # (w, rp)   sketch rows of block
        delta = jax.lax.dot_general(k, ocr, (((1,), (0,)), ((), ())),
                                    precision=KERNEL_PRECISION,
                                    preferred_element_type=jnp.float32)
        k2 = k * k
        colmask = jax.lax.broadcasted_iota(jnp.int32, (1, k.shape[1]),
                                           1) < b_real
        rnr = jnp.sum(jnp.where(colmask, k2, 0.0), axis=1, keepdims=True)
        vi = vi_ref[...]                    # (8, bm)   row 0 = validity
        rnc_part = jax.lax.dot_general(vi, k2, (((1,), (0,)), ((), ())),
                                       precision=KERNEL_PRECISION,
                                       preferred_element_type=jnp.float32)
        acc_ref[...] += acc_part.astype(acc_ref.dtype)   # (w, rp) resident
        rnc_ref[...] += rnc_part.astype(rnc_ref.dtype)   # (8, w) resident
        dl_ref[...] = delta.astype(dl_ref.dtype)         # (bm, rp) per tile
        rnr_ref[...] = jnp.broadcast_to(rnr, rnr_ref.shape).astype(
            rnr_ref.dtype)                               # (bm, 128) per tile


def fit_sketch_call(nt: jnp.ndarray, X: jnp.ndarray, Omega: jnp.ndarray,
                    C: jnp.ndarray, Ocross: jnp.ndarray, V: jnp.ndarray,
                    kind: str, gamma: float, degree: int, b_real: int,
                    row_tile: int, interpret: bool):
    """All four fit contractions of kappa(X, C); m % row_tile == 0.

    nt (1,) int32 = how many leading row tiles to visit, 1 <= nt <=
    m // row_tile; X (p, m), Omega (m, rp), C (p, w), Ocross (w, rp),
    V (8, m) -> acc (w, rp), delta (m, rp), rn_row (m, 128), rn_col
    (8, w); b_real = count of real (unpadded) block columns, for the
    static rn_row column mask. The grid stays m // row_tile; steps
    i >= nt keep the block index of tile nt-1 (no DMA) and compute
    nothing, so delta and rn_row rows from nt * row_tile on are left
    unwritten: callers must not read them.
    """
    p, m = X.shape
    rp = Omega.shape[1]
    w = C.shape[1]

    def row(i, nt_ref):
        return jnp.minimum(i, nt_ref[0] - 1)

    return pl.pallas_call(
        functools.partial(_fit_sketch_kernel, kind=kind, gamma=gamma,
                          degree=degree, b_real=b_real),
        out_shape=(
            jax.ShapeDtypeStruct((w, rp), jnp.float32),
            jax.ShapeDtypeStruct((m, rp), jnp.float32),
            jax.ShapeDtypeStruct((m, 128), jnp.float32),
            jax.ShapeDtypeStruct((8, w), jnp.float32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m // row_tile,),
            in_specs=[
                pl.BlockSpec((p, row_tile), lambda i, n: (0, row(i, n))),
                pl.BlockSpec((row_tile, rp), lambda i, n: (row(i, n), 0)),
                pl.BlockSpec((p, w), lambda i, n: (0, 0)),
                pl.BlockSpec((w, rp), lambda i, n: (0, 0)),
                pl.BlockSpec((8, row_tile), lambda i, n: (0, row(i, n))),
            ],
            out_specs=(
                pl.BlockSpec((w, rp), lambda i, n: (0, 0)),
                pl.BlockSpec((row_tile, rp), lambda i, n: (row(i, n), 0)),
                pl.BlockSpec((row_tile, 128), lambda i, n: (row(i, n), 0)),
                pl.BlockSpec((8, w), lambda i, n: (0, 0)),
            ),
        ),
        interpret=interpret,
    )(nt, X, Omega, C, Ocross, V)
