"""Public jit'd wrapper for the fused gram->projection stripe kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.extend_embed.extend_embed import extend_embed_call
from repro.kernels.extend_embed.ref import extend_embed_ref
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


def padded_shapes(n: int, r: int, w: int, row_tile: int = 256
                  ) -> tuple[int, int, int, int]:
    """(row_tile, n_pad, r_pad, w_pad) the kernel actually runs at.

    The single source of truth for the tiling: extend_embed_pallas pads
    with exactly these values, and `memory_contract` counts the fused
    engine's HBM bytes from them (each padded operand crosses HBM
    once — that IS the kernel's memory contract).
    """
    row_tile = min(row_tile, max(128, 1 << (n - 1).bit_length()))
    n_pad = -(-n // row_tile) * row_tile
    r_pad = -(-r // 8) * 8
    w_pad = -(-w // 128) * 128
    return row_tile, n_pad, r_pad, w_pad


def memory_contract(p: int, n: int, r: int, w: int, row_tile: int = 256
                    ) -> dict:
    """Declared HBM byte model for one fused serving stripe.

    X and P stream over the row-tile grid (each padded element crosses
    HBM once); the query block Xb and the (r, w) output stay
    VMEM-resident across the whole sweep and cross once each.
    `repro.analysis` cross-checks these bytes against the BlockSpecs
    (rule C001), and the benchmark's byte model (bench/lib/costs.py)
    is held equal to them by bench/tests/test_costs.py.
    """
    row_tile, n_pad, r_pad, w_pad = padded_shapes(n, r, w, row_tile)
    hbm = 4.0 * (p * n_pad             # X (p, n_pad) streamed
                 + r_pad * n_pad       # P streamed
                 + p * w_pad           # Xb query block, resident
                 + r_pad * w_pad)      # embedded out, resident
    return {"row_tile": row_tile, "n_pad": n_pad, "r_pad": r_pad,
            "w_pad": w_pad, "hbm_bytes": hbm}


@functools.partial(jax.jit, static_argnames=("kind", "gamma", "degree",
                                             "row_tile", "interpret"))
def extend_embed_pallas(X: jnp.ndarray, P: jnp.ndarray, Xb: jnp.ndarray,
                        kind: str = "polynomial", gamma: float = 0.0,
                        degree: int = 2, row_tile: int = 256,
                        interpret: bool | None = None) -> jnp.ndarray:
    """Fused serving stripe P @ kappa(X, Xb) -> (r, w), one executable.

    X (p, n) training data, P (r, n) projection Sigma^{-1/2} U^T, Xb (p, w)
    query block. Pads n to the row tile, w to 128 lanes, r to 8 sublanes.

    Padding is exact, not approximate: padded columns of X produce garbage
    gram ROWS (nonzero for rbf, where kappa(0, x) != 0) but the matching
    padded columns of P are zero, so they are annihilated in the
    contraction; padded w columns and padded r rows are sliced off.
    """
    interp = _is_cpu() if interpret is None else interpret
    p, n = X.shape
    r = P.shape[0]
    w = Xb.shape[1]
    row_tile, _, _, _ = padded_shapes(n, r, w, row_tile)
    Xp = _pad_to(X, 1, row_tile)
    Pp = _pad_to(_pad_to(P, 1, row_tile), 0, 8)
    Xbp = _pad_to(Xb, 1, 128)
    out = extend_embed_call(Xp, Pp, Xbp, kind, gamma, degree, row_tile,
                            interp)
    return out[:r, :w]


def _extend_embed_build(key, case):
    k1, k2, k3 = jax.random.split(key, 3)
    X = jax.random.normal(k1, (case["p"], case["n"]), jnp.float32)
    P = jax.random.normal(k2, (case["r"], case["n"]), jnp.float32)
    Xb = jax.random.normal(k3, (case["p"], case["w"]), jnp.float32)
    kw = {k: case[k] for k in ("kind", "gamma", "degree") if k in case}
    return (X, P, Xb), kw, kw


register_kernel(KernelEntry(
    name="extend_embed", op=extend_embed_pallas, ref=extend_embed_ref,
    cases=(
        {"p": 2, "n": 100, "r": 2, "w": 12},
        {"p": 19, "n": 555, "r": 3, "w": 64, "kind": "rbf", "gamma": 0.5},
        {"p": 7, "n": 1024, "r": 16, "w": 128},
        {"p": 3, "n": 97, "r": 5, "w": 1, "kind": "linear"},
        {"p": 2, "n": 250, "r": 2, "w": 23, "kind": "polynomial",
         "gamma": 1.0, "degree": 3},
    ),
    build=_extend_embed_build, rtol=2e-3, atol=2e-3))


def _extend_embed_declared(case: dict) -> dict:
    return memory_contract(case["p"], case["n"], case["r"], case["w"])


register_contract(KernelContract(name="extend_embed",
                                 declared=_extend_embed_declared))
