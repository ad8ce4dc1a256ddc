"""Operations and bytes of the Pallas kernels, from shapes alone.

Two kinds of count per kernel:

  *_needed   what the algorithm needs at real widths: only the rows a
             fit block reads (q + b of them) and only the real query
             columns of a served stripe. Padding, masked rows and
             bucket padding are not credited, so a kernel that sweeps
             them shows the waste as a lower roofline share.
  *_moved_bytes  the HBM bytes one call moves at its padded shapes: a
             copy of the kernels' `memory_contract()` byte models, kept
             here so a later change to the program cannot move the
             yardstick (bench/tests/test_costs.py holds the two equal at
             the kernels' registered parity shapes).

Operations count a multiply-add as two; the RBF nonlinearity per kernel
entry counts five (norm add, scale, clamp, multiply by gamma, exp).
"""
from __future__ import annotations

from typing import Tuple

F32 = 4
RBF_ENTRY_OPS = 5


# -- fit_sketch ---------------------------------------------------------------

def fit_sketch_block_needed(p: int, q: int, b: int, rp: int
                            ) -> Tuple[float, float]:
    """(flops, bytes) one block update needs: the gram border
    kappa(X[:, :q+b], C) of (q+b) x b entries, its contraction with the
    q+b sketch rows into b new rows, the cross term into the q applied
    rows, and the squared-norm sums both ways."""
    rows = q + b
    entries = rows * b
    flops = (entries * (2 * p + RBF_ENTRY_OPS)   # gram + nonlinearity
             + 2 * p * (rows + b)                # squared norms of X and C
             + 2 * entries * rp                  # new_rows = K^T Omega
             + 2 * q * b * rp                    # delta = K[:q] Omega_cross
             + 3 * entries)                      # k^2, row and column sums
    hbm = F32 * (p * rows                        # X[:, :q+b], C included
                 + rows * rp                     # Omega rows, cross included
                 + b * rp + q * rp               # new_rows, delta out
                 + q + b)                        # row and column norms out
    return float(flops), float(hbm)


def fit_sketch_fit_needed(n: int, p: int, rp: int, block: int
                          ) -> Tuple[float, float, int]:
    """(flops, bytes, calls) of a whole one-pass fit of n columns in
    blocks of `block`; the ragged tail is one narrower block."""
    flops = hbm = 0.0
    calls = 0
    for q in range(0, n, block):
        f, h = fit_sketch_block_needed(p, q, min(block, n - q), rp)
        flops += f
        hbm += h
        calls += 1
    return flops, hbm, calls


def fit_sketch_moved_bytes(p: int, m: int, b: int, rp: int,
                           row_tile: int = 256) -> float:
    """HBM bytes of one fit_sketch call at its padded shapes (the
    kernel's memory_contract)."""
    row_tile = min(row_tile, max(128, 1 << (m - 1).bit_length()))
    m_pad = -(-m // row_tile) * row_tile
    b_pad = -(-b // 128) * 128
    rp_pad = -(-rp // 128) * 128
    return F32 * (p * m_pad + m_pad * rp_pad + p * b_pad + b_pad * rp_pad
                  + 8 * m_pad + b_pad * rp_pad + m_pad * rp_pad
                  + m_pad * 128 + 8 * b_pad)


# -- extend_embed -------------------------------------------------------------

def extend_embed_needed(p: int, n: int, r: int, queries: int, stripes: int
                        ) -> Tuple[float, float]:
    """(flops, bytes) of serving `queries` real columns in `stripes`
    stripes against n reference columns: every stripe reads the
    reference set and the projection once; each real column costs n
    kernel entries and their projection onto r coordinates."""
    flops = queries * (n * (2 * p + RBF_ENTRY_OPS + 2 * r) + 2 * p)
    hbm = F32 * (stripes * n * (p + r) + queries * (p + r))
    return float(flops), float(hbm)


def extend_embed_moved_bytes(p: int, n: int, r: int, w: int,
                             row_tile: int = 256) -> float:
    """HBM bytes of one extend_embed stripe at its padded shapes (the
    kernel's memory_contract)."""
    row_tile = min(row_tile, max(128, 1 << (n - 1).bit_length()))
    n_pad = -(-n // row_tile) * row_tile
    r_pad = -(-r // 8) * 8
    w_pad = -(-w // 128) * 128
    return F32 * (p * n_pad + r_pad * n_pad + p * w_pad + r_pad * w_pad)


# -- kmeans_assign ------------------------------------------------------------

def kmeans_assign_moved_bytes(n: int, r: int, k: int,
                              row_tile: int = 1024) -> float:
    """HBM bytes of one kmeans_assign sweep at its padded shapes (the
    kernel's memory_contract)."""
    row_tile = min(row_tile, max(8, 1 << (n - 1).bit_length()))
    n_pad = -(-n // row_tile) * row_tile
    r_pad = -(-r // 128) * 128
    k_pad = -(-k // 8) * 8
    return F32 * (n_pad * r_pad + k_pad * r_pad + n_pad + n_pad)
