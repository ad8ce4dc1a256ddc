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

Two entry points share the tile body (_gram_tile, _fold_tile):

* fit_sketch_call, functional, as above: delta and rn_row come back for
  every row (the sharded fit engine sums them across the mesh).
* fit_block_call, in place: the scalar-prefetch operand is the block's
  offset q, the border q + b_real and nt are derived from it, and the
  gram tile's rows at or past the border are zeroed in the kernel, so
  Omega needs no per-block zeroing and there is no V. The sketch state
  W (S, r') and its row norms (8, S) (row 0 live) are inputs aliased to
  outputs: each visited tile writes W + delta to its rows < q and the
  row norms likewise, and leaves every other row as it was. Tiles past
  the border are neither read nor written, and delta never leaves VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.registry import KERNEL_PRECISION


def _gram_tile(xi, xb, *, kind: str, gamma: float, degree: int):
    """The (bm, w) gram tile kappa(X_i, C) of one row tile."""
    z = jax.lax.dot_general(xi, xb, (((0,), (0,)), ((), ())),
                            precision=KERNEL_PRECISION,
                            preferred_element_type=jnp.float32)
    if kind == "polynomial":
        return (z + gamma) ** degree
    if kind == "rbf":
        xn = jnp.sum(xi * xi, axis=0)[:, None]
        yn = jnp.sum(xb * xb, axis=0)[None, :]
        return jnp.exp(-gamma * jnp.maximum(xn + yn - 2.0 * z, 0.0))
    return z  # linear


def _fold_tile(k, k2, oi, ocr, e0, acc_ref, rnc_ref, *, b_real: int):
    """Fold one gram tile k and its square k2 into the resident
    accumulators; return the tile's cross-term rows delta = k Ocross
    (bm, rp) and its row norms (bm, 1). e0 (8, bm) weighs the rows in
    the column norms (row 0)."""
    acc_part = jax.lax.dot_general(k, oi, (((0,), (0,)), ((), ())),
                                   precision=KERNEL_PRECISION,
                                   preferred_element_type=jnp.float32)
    delta = jax.lax.dot_general(k, ocr, (((1,), (0,)), ((), ())),
                                precision=KERNEL_PRECISION,
                                preferred_element_type=jnp.float32)
    colmask = jax.lax.broadcasted_iota(jnp.int32, (1, k.shape[1]),
                                       1) < b_real
    rnr = jnp.sum(jnp.where(colmask, k2, 0.0), axis=1, keepdims=True)
    rnc_part = jax.lax.dot_general(e0, k2, (((1,), (0,)), ((), ())),
                                   precision=KERNEL_PRECISION,
                                   preferred_element_type=jnp.float32)
    acc_ref[...] += acc_part.astype(acc_ref.dtype)   # (w, rp) resident
    rnc_ref[...] += rnc_part.astype(rnc_ref.dtype)   # (8, w) resident
    return delta, rnr


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
        k = _gram_tile(xi_ref[...], xb_ref[...], kind=kind, gamma=gamma,
                       degree=degree)
        # vi (8, bm): row 0 = validity
        delta, rnr = _fold_tile(k, k * k, oi_ref[...], ocr_ref[...],
                                vi_ref[...], acc_ref, rnc_ref,
                                b_real=b_real)
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


def _fit_block_kernel(q_ref, xi_ref, oi_ref, xb_ref, ocr_ref, wi_ref,
                      rni_ref, acc_ref, wo_ref, rno_ref, rnc_ref, *,
                      kind: str, gamma: float, degree: int, b_real: int):
    i = pl.program_id(0)
    bm = xi_ref.shape[1]
    q = q_ref[0]
    border = q + b_real

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        rnc_ref[...] = jnp.zeros_like(rnc_ref)

    @pl.when(i * bm < border)
    def _():
        k = _gram_tile(xi_ref[...], xb_ref[...], kind=kind, gamma=gamma,
                       degree=degree)
        # Rows at or past the border (and a partial last tile's garbage,
        # NaN included) become exact zeros: they add nothing below. k*k
        # is formed from the unmasked k and masked after, as the
        # functional kernel forms it, so the two agree bit for bit.
        row = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        live = row < border
        e0 = (jax.lax.broadcasted_iota(jnp.int32, (8, bm), 0) == 0
              ).astype(jnp.float32)
        delta, rnr = _fold_tile(jnp.where(live, k, 0.0),
                                jnp.where(live, k * k, 0.0), oi_ref[...],
                                ocr_ref[...], e0, acc_ref, rnc_ref,
                                b_real=b_real)
        wi = wi_ref[...]                    # (bm, rp)  sketch state rows
        wo_ref[...] = jnp.where(row < q, wi + delta, wi)
        # The row norms as lanes: the state keeps them in row 0 of (8, m).
        rnr_t = jnp.transpose(jnp.broadcast_to(rnr, (bm, 128)))[:8]
        sub = jax.lax.broadcasted_iota(jnp.int32, (8, bm), 0)
        col = i * bm + jax.lax.broadcasted_iota(jnp.int32, (8, bm), 1)
        rni = rni_ref[...]                  # (8, bm)   row 0 live
        rno_ref[...] = jnp.where((sub == 0) & (col < q), rni + rnr_t, rni)


def fit_block_call(q: jnp.ndarray, X: jnp.ndarray, Omega: jnp.ndarray,
                   C: jnp.ndarray, Ocross: jnp.ndarray, W: jnp.ndarray,
                   rn: jnp.ndarray, kind: str, gamma: float, degree: int,
                   b_real: int, row_tile: int, interpret: bool):
    """One fit block [q, q + b_real) folded into the sketch state in
    place; the contractions of fit_sketch_call, masked by the border.

    q (1,) int32 = the block's first column; X (p, m), its last row tile
    may be partial; Omega (m_pad, rp) the sketch rows, m_pad = cdiv(m,
    row_tile) * row_tile; C (p, w) and Ocross (w, rp) the block's
    columns and sketch rows, zero past b_real; W (S, rp) and rn (8, S)
    the state, S >= m_pad, aliased to the outputs. Returns acc (w, rp)
    = the new sketch rows, W with delta added to its rows < q, rn with
    the row norms added to row 0's columns < q, and rn_col (8, w). Only
    the tiles that hold [0, q + b_real) are read or written; every other
    row of W and rn keeps its value.
    """
    p, m = X.shape
    rp = Omega.shape[1]
    w = C.shape[1]
    tiles = -(-m // row_tile)

    def row(i, q_ref):
        return jnp.minimum(i, (q_ref[0] + b_real - 1) // row_tile)

    return pl.pallas_call(
        functools.partial(_fit_block_kernel, kind=kind, gamma=gamma,
                          degree=degree, b_real=b_real),
        out_shape=(
            jax.ShapeDtypeStruct((w, rp), jnp.float32),
            jax.ShapeDtypeStruct(W.shape, jnp.float32),
            jax.ShapeDtypeStruct(rn.shape, jnp.float32),
            jax.ShapeDtypeStruct((8, w), jnp.float32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles,),
            in_specs=[
                pl.BlockSpec((p, row_tile), lambda i, q: (0, row(i, q))),
                pl.BlockSpec((row_tile, rp), lambda i, q: (row(i, q), 0)),
                pl.BlockSpec((p, w), lambda i, q: (0, 0)),
                pl.BlockSpec((w, rp), lambda i, q: (0, 0)),
                pl.BlockSpec((row_tile, rp), lambda i, q: (row(i, q), 0)),
                pl.BlockSpec((8, row_tile), lambda i, q: (0, row(i, q))),
            ],
            out_specs=(
                pl.BlockSpec((w, rp), lambda i, q: (0, 0)),
                pl.BlockSpec((row_tile, rp), lambda i, q: (row(i, q), 0)),
                pl.BlockSpec((8, row_tile), lambda i, q: (0, row(i, q))),
                pl.BlockSpec((8, w), lambda i, q: (0, 0)),
            ),
        ),
        # Operand indices count the prefetched q: W is 5, rn is 6.
        input_output_aliases={5: 1, 6: 2},
        interpret=interpret,
    )(q, X, Omega, C, Ocross, W, rn)
