"""Public jit'd wrapper for the fused fit-sketch accumulate kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fit_sketch.fit_sketch import fit_sketch_call
from repro.kernels.fit_sketch.ref import fit_sketch_ref
from repro.kernels.registry import (KernelContract, KernelEntry,
                                    register_contract, register_kernel)


def _is_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = x.shape[axis]
    rem = size % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, mult - rem)
    return jnp.pad(x, pads)


def padded_shapes(m: int, b: int, rp: int, row_tile: int = 256
                  ) -> tuple[int, int, int, int]:
    """(row_tile, m_pad, b_pad, rp_pad) the kernel actually runs at.

    The single source of truth for the tiling: fit_sketch_pallas pads
    with exactly these values, and the "fit_scaling" bench section
    (serve/bench.py) derives the fused fit engine's HBM byte count from
    them — each padded operand crosses HBM once, that IS the kernel's
    memory contract.
    """
    row_tile = min(row_tile, max(128, 1 << (m - 1).bit_length()))
    m_pad = -(-m // row_tile) * row_tile
    b_pad = -(-b // 128) * 128
    rp_pad = -(-rp // 128) * 128
    return row_tile, m_pad, b_pad, rp_pad


def border_tiles(m: int, border: int, row_tile: int = 256
                 ) -> tuple[int, int]:
    """(tiles visited, tiles in the grid) of a call over m rows bounded
    to `border` rows: the host-side count of what fit_sketch_pallas's
    `border` makes the kernel sweep."""
    row_tile, m_pad, _, _ = padded_shapes(m, 1, 1, row_tile)
    tiles = m_pad // row_tile
    return min(max(-(-border // row_tile), 1), tiles), tiles


def memory_contract(p: int, m: int, b: int, rp: int, row_tile: int = 256
                    ) -> dict:
    """Declared HBM byte model for one fused fit-block call.

    Every operand block crosses HBM exactly once per distinct grid
    coordinate (moving operands stream, constant-index operands stay
    VMEM-resident), so the f32 traffic is the sum of the padded operand
    footprints. serve/bench.py reports THESE numbers and
    `repro.analysis` cross-checks them against the kernel's BlockSpecs
    at every registered parity case (rule C001).

    This is the full-sweep upper bound (no `border`). A call bounded to
    `border` rows moves X, Omega, V, delta and the row norms for its
    nt = border_tiles(m, border)[0] leading tiles only.
    """
    row_tile, m_pad, b_pad, rp_pad = padded_shapes(m, b, rp, row_tile)
    hbm = 4.0 * (p * m_pad             # X (p, m_pad) streamed
                 + m_pad * rp_pad      # Omega rows streamed
                 + p * b_pad           # C block, resident
                 + b_pad * rp_pad      # Ocross, resident
                 + 8 * m_pad           # V validity mask, streamed
                 + b_pad * rp_pad      # new_rows accumulator, resident
                 + m_pad * rp_pad      # delta out, streamed
                 + m_pad * 128         # row-norm out, streamed
                 + 8 * b_pad)          # col-norm out, resident
    return {"row_tile": row_tile, "m_pad": m_pad, "b_pad": b_pad,
            "rp_pad": rp_pad, "hbm_bytes": hbm}


@functools.partial(jax.jit, static_argnames=("kind", "gamma", "degree",
                                             "row_tile", "interpret"))
def fit_sketch_pallas(X: jnp.ndarray, Omega: jnp.ndarray, C: jnp.ndarray,
                      Ocross: jnp.ndarray, V: jnp.ndarray | None = None,
                      kind: str = "polynomial", gamma: float = 0.0,
                      degree: int = 2, row_tile: int = 256,
                      interpret: bool | None = None,
                      border: jnp.ndarray | int | None = None):
    """Fused fit-block contractions of K = kappa(X, C), one executable.

    X (p, m) samples as columns, Omega (m, r') sketch rows (callers zero
    the rows of invalid/garbage X columns — that zeroing is what makes
    the padding exact), C (p, b) block columns, Ocross (b, r') the
    block's own sketch rows, V (8, m) optional row-validity mask in row
    0 (None = all m rows valid). Returns
      (new_rows (b, r'), delta (m, r'), rn_rows (m,), rn_cols (b,))
    matching fit_sketch_ref. Pads m to the row tile, b and r' to 128
    lanes; padded Omega/Ocross rows are zero and padded V columns are
    zero, so every padded contribution is annihilated (exact, not
    approximate), and padded output rows/columns are sliced off.

    border (traced int, optional): only the leading `border` rows of X
    matter — a fit block's [0, q+b). The kernel then visits the row
    tiles that hold them and no others; rows past the border must
    already be zero in Omega and V (as above), so new_rows and rn_cols
    are bit-identical to the full sweep. delta and rn_rows are computed
    for every row of those tiles; rows of later tiles are left
    unwritten (arbitrary values), so a caller reads them only below
    the border. None sweeps all m rows.
    """
    interp = _is_cpu() if interpret is None else interpret
    m = X.shape[1]
    b = C.shape[1]
    rp = Omega.shape[1]
    row_tile, m_pad, _, _ = padded_shapes(m, b, rp, row_tile)
    tiles = m_pad // row_tile
    if border is None:
        nt = np.full((1,), tiles, np.int32)
    else:
        nt = jnp.clip(-(-jnp.asarray(border, jnp.int32) // row_tile), 1,
                      tiles).reshape(1)
    if V is None:
        V = jnp.zeros((8, m), jnp.float32).at[0].set(1.0)
    Xp = _pad_to(X, 1, row_tile)
    Op = _pad_to(_pad_to(Omega, 0, row_tile), 1, 128)
    Cp = _pad_to(C, 1, 128)
    Ocrp = _pad_to(_pad_to(Ocross, 0, 128), 1, 128)
    Vp = _pad_to(V, 1, row_tile)
    acc, delta, rnr, rnc = fit_sketch_call(nt, Xp, Op, Cp, Ocrp, Vp, kind,
                                           gamma, degree, b, row_tile,
                                           interp)
    return acc[:b, :rp], delta[:m, :rp], rnr[:m, 0], rnc[0, :b]


def _fit_sketch_build(key, case):
    p, m, b, rp = case["p"], case["m"], case["b"], case["rp"]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    X = jax.random.normal(k1, (p, m), jnp.float32)
    Omega = jax.random.normal(k2, (m, rp), jnp.float32)
    C = jax.random.normal(k3, (p, b), jnp.float32)
    Ocr = jax.random.normal(k4, (b, rp), jnp.float32)
    valid = case.get("valid", m)
    if valid < m:
        # Mirror the fit caller's contract: Omega rows of invalid
        # columns are zeroed, V masks them out of the column norms.
        Omega = Omega.at[valid:].set(0.0)
    V = jnp.zeros((8, m), jnp.float32).at[0, :valid].set(1.0)
    kw = {k: case[k] for k in ("kind", "gamma", "degree") if k in case}
    return (X, Omega, C, Ocr, V), kw, kw


register_kernel(KernelEntry(
    name="fit_sketch", op=fit_sketch_pallas, ref=fit_sketch_ref,
    cases=(
        {"p": 2, "m": 100, "b": 12, "rp": 12},
        {"p": 19, "m": 555, "b": 64, "rp": 33, "kind": "rbf",
         "gamma": 0.5},
        {"p": 7, "m": 1024, "b": 128, "rp": 140, "valid": 700},
        {"p": 3, "m": 97, "b": 1, "rp": 5, "kind": "linear"},
        {"p": 5, "m": 300, "b": 37, "rp": 20, "kind": "polynomial",
         "gamma": 1.0, "degree": 3, "valid": 123},
    ),
    build=_fit_sketch_build, rtol=2e-3, atol=2e-3))


def _fit_sketch_declared(case: dict) -> dict:
    return memory_contract(case["p"], case["m"], case["b"], case["rp"])


register_contract(KernelContract(name="fit_sketch",
                                 declared=_fit_sketch_declared))
