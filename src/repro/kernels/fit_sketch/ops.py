"""Public wrappers for the fused fit-sketch accumulate kernel.

Two entry points share one tile body (fit_sketch.py):

* fit_sketch_pallas: functional; returns the block's new rows, the
  cross-term rows delta of every row, and both norms. The sharded fit
  engine psums its parts across the mesh.
* fit_sketch_inplace: the single-host fit's per-block update; folds the
  block into the sketch state it is given, in the kernel's layout
  (to_kernel_state), touching only the row tiles of the border [0, q+b).
  delta never leaves VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fit_sketch.fit_sketch import (fit_block_call,
                                                 fit_sketch_call)
from repro.kernels.fit_sketch.ref import (fit_sketch_inplace_ref,
                                          fit_sketch_ref)
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
    with exactly these values, and `memory_contract` counts the fused
    fit engine's HBM bytes from them — each padded operand crosses HBM
    once, that IS the kernel's memory contract.
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
    footprints. `repro.analysis` cross-checks THESE numbers against the
    kernel's BlockSpecs at every registered parity case (rule C001), and
    bench/tests/test_costs.py holds the benchmark's byte model equal
    to them.

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


def to_kernel_state(W: jnp.ndarray | None, rn: jnp.ndarray | None,
                    n: int, rp: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The sketch state (W (n, rp), row norms (n,)) in the layout
    fit_sketch_inplace updates: W (S, rp_pad) and the norms in row 0 of
    (8, S), S = n padded to its row tile, zero outside; a pass over any
    m <= n columns tiles S evenly, as its row tile divides n's. None is
    the empty state. Always new buffers: the caller may donate them."""
    _, S, _, rp_pad = padded_shapes(n, 1, rp)
    Wk = jnp.zeros((S, rp_pad), jnp.float32)
    rnk = jnp.zeros((8, S), jnp.float32)
    if W is not None:
        Wk = Wk.at[:n, :rp].set(W)
        rnk = rnk.at[0, :n].set(rn)
    return Wk, rnk


def from_kernel_state(Wk: jnp.ndarray, rnk: jnp.ndarray, n: int, rp: int
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(W (n, rp), row norms (n,)) read out of the kernel's layout, in
    new buffers (lax.slice, not indexing, which hands back Wk itself
    when nothing is cut): the state itself is donated to the next
    block update."""
    return (jax.lax.slice(Wk, (0, 0), (n, rp)),
            jax.lax.slice(rnk, (0, 0), (1, n))[0])


def kernel_rows(Omega: jnp.ndarray) -> jnp.ndarray:
    """The sketch rows Omega (m, rp) of a pass over m columns, zero-padded
    to (m_pad, rp_pad): the layout fit_sketch_inplace reads."""
    m, rp = Omega.shape
    _, m_pad, _, rp_pad = padded_shapes(m, 1, rp)
    return jnp.pad(Omega, ((0, m_pad - m), (0, rp_pad - rp)))


def block_memory_contract(p: int, m: int, b: int, rp: int, border: int
                          ) -> dict:
    """Declared HBM byte model for one fit_sketch_inplace call bounded to
    `border` rows: X, Omega and the state (W and row norms, each read
    and written) move for the nt = border_tiles(m, border)[0] leading
    tiles; C, Ocross, the new rows and the column norms once."""
    row_tile, m_pad, b_pad, rp_pad = padded_shapes(m, b, rp)
    nt = border_tiles(m, border)[0]
    hbm = 4.0 * (nt * row_tile * (p            # X tiles
                                  + rp_pad     # Omega tiles
                                  + 2 * rp_pad  # W read and written
                                  + 2 * 8)     # row norms read, written
                 + b_pad * (p                  # C block, resident
                            + 2 * rp_pad       # Ocross, new rows
                            + 8))              # col-norm out
    return {"row_tile": row_tile, "m_pad": m_pad, "b_pad": b_pad,
            "rp_pad": rp_pad, "hbm_bytes": hbm}


def fit_sketch_inplace(X: jnp.ndarray, Omega: jnp.ndarray, W: jnp.ndarray,
                       rn: jnp.ndarray, q, *, b: int,
                       kind: str = "polynomial", gamma: float = 0.0,
                       degree: int = 2, interpret: bool | None = None):
    """Fold fit block [q, q+b) of X (p, m) into the sketch state.

    Omega is kernel_rows() of the pass's m sketch rows; W (S, rp_pad)
    and rn (8, S) are the state in to_kernel_state's layout, S >= m_pad.
    q (int or traced int32) is the block's first column, q + b <= m.
    Returns (W, rn): the rows < q of W gain K[:q] Omega[q:q+b], rows
    [q, q+b) become K^T Omega[:q+b], and rn's row 0 likewise, for K =
    kappa(X[:, :q+b], X[:, q:q+b]) (fit_sketch_inplace_ref). The kernel
    updates W and rn in place (aliased) and reads and writes only the
    row tiles of [0, q+b); outside the kernel only O(b) work remains.
    Not jitted, so a registered case's q stays concrete; the fit jits it
    as fit_sketch_inplace_jit and donates the state.
    """
    interp = _is_cpu() if interpret is None else interpret
    m = X.shape[1]
    row_tile, m_pad, _, rp_pad = padded_shapes(m, b, Omega.shape[1])
    S = W.shape[0]
    if (Omega.shape[0] != m_pad or W.shape[1] != Omega.shape[1]
            or S < m_pad or S % row_tile or rn.shape != (8, S)):
        raise ValueError(
            f"fit_sketch_inplace: Omega {Omega.shape}, W {W.shape}, rn "
            f"{rn.shape} are not the kernel's layout for m={m} "
            f"(kernel_rows, to_kernel_state)")
    q = jnp.asarray(q, jnp.int32)
    C = _pad_to(jax.lax.dynamic_slice_in_dim(X, q, b, axis=1), 1, 128)
    Ocr = _pad_to(jax.lax.dynamic_slice_in_dim(Omega, q, b, axis=0), 0,
                  128)
    acc, W, rn, rnc = fit_block_call(q.reshape(1), X, Omega, C, Ocr, W, rn,
                                     kind, gamma, degree, b, row_tile,
                                     interp)
    W = jax.lax.dynamic_update_slice(W, acc[:b], (q, 0))
    rn = jax.lax.dynamic_update_slice(rn, rnc[:1, :b], (0, q))
    return W, rn


# Jitted under the kernel's own name: the custom-call in a device trace
# is named after the innermost jitted function around it.
fit_sketch_inplace_jit = jax.jit(
    fit_sketch_inplace,
    static_argnames=("b", "kind", "gamma", "degree", "interpret"))


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


def _fit_sketch_inplace_build(key, case):
    p, m, b, rp, q = case["p"], case["m"], case["b"], case["rp"], case["q"]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    X = jax.random.normal(k1, (p, m), jnp.float32)
    Omega = kernel_rows(jax.random.normal(k2, (m, rp), jnp.float32))
    # A state whose first q rows hold earlier blocks' sums.
    W, rn = to_kernel_state(
        jnp.where(jnp.arange(m)[:, None] < q,
                  jax.random.normal(k3, (m, rp), jnp.float32), 0.0),
        jnp.where(jnp.arange(m) < q,
                  jax.random.uniform(k4, (m,), jnp.float32), 0.0), m, rp)
    kw = {k: case[k] for k in ("q", "b", "kind", "gamma", "degree")
          if k in case}
    return (X, Omega, W, rn), kw, kw


register_kernel(KernelEntry(
    name="fit_sketch_inplace", op=fit_sketch_inplace,
    ref=fit_sketch_inplace_ref,
    cases=(
        {"p": 2, "m": 100, "b": 12, "rp": 12, "q": 40},
        {"p": 19, "m": 555, "b": 64, "rp": 33, "q": 256, "kind": "rbf",
         "gamma": 0.5},
        {"p": 7, "m": 1024, "b": 128, "rp": 140, "q": 512},
        {"p": 3, "m": 97, "b": 1, "rp": 5, "q": 96, "kind": "linear"},
        {"p": 5, "m": 300, "b": 37, "rp": 20, "q": 0, "kind": "polynomial",
         "gamma": 1.0, "degree": 3},
        {"p": 5, "m": 700, "b": 60, "rp": 17, "q": 640, "kind": "rbf",
         "gamma": 0.3},
    ),
    build=_fit_sketch_inplace_build, rtol=2e-3, atol=2e-3))


def _fit_sketch_inplace_declared(case: dict) -> dict:
    return block_memory_contract(case["p"], case["m"], case["b"],
                                 case["rp"], case["q"] + case["b"])


register_contract(KernelContract(name="fit_sketch_inplace",
                                 declared=_fit_sketch_inplace_declared))
