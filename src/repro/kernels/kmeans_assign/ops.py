"""Public jit'd wrapper for the fused K-means assignment kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import numpy as np

from repro.kernels.kmeans_assign.kmeans_assign import assign_call
from repro.kernels.kmeans_assign.ref import assign_ref
from repro.kernels.registry import (KernelContract, KernelEntry,
                                    register_contract, register_kernel)


def _is_cpu() -> bool:
    return jax.default_backend() == "cpu"


def padded_shapes(n: int, r: int, k: int, row_tile: int = 1024
                  ) -> tuple[int, int, int, int]:
    """(row_tile, n_pad, r_pad, k_pad) the kernel actually runs at — the
    single source of truth for the tiling (assign_pallas pads with
    exactly these values; memory_contract derives bytes from them).

    The (n_pad,) outputs are 1-D, and XLA lays a 1-D TPU array of 1024
    or more 32-bit elements out in 1024-element tiles; Mosaic refuses a
    block that does not match. So a row tile is either the whole padded
    array (n <= 512) or a multiple of 1024."""
    row_tile = min(row_tile, max(8, 1 << (n - 1).bit_length()))
    n_pad = -(-n // row_tile) * row_tile
    if row_tile < n_pad and row_tile % 1024:
        raise ValueError(f"row_tile {row_tile} must be a multiple of 1024 "
                         f"when it splits the {n_pad} padded rows")
    r_pad = -(-r // 128) * 128
    k_pad = -(-k // 8) * 8
    return row_tile, n_pad, r_pad, k_pad


def memory_contract(n: int, r: int, k: int, row_tile: int = 1024) -> dict:
    """Declared HBM byte model for one fused assignment sweep: Y streams
    over the row-tile grid, the centroids stay VMEM-resident, and only
    the two (n,) outputs come back — the (n, k) distance matrix never
    leaves VMEM. Cross-checked against the BlockSpecs by
    `repro.analysis` (rule C001)."""
    row_tile, n_pad, r_pad, k_pad = padded_shapes(n, r, k, row_tile)
    hbm = 4.0 * (n_pad * r_pad         # Y streamed
                 + k_pad * r_pad       # centroids, resident
                 + n_pad               # labels out (int32)
                 + n_pad)              # min-d2 out (f32)
    return {"row_tile": row_tile, "n_pad": n_pad, "r_pad": r_pad,
            "k_pad": k_pad, "hbm_bytes": hbm}


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def assign_pallas(Y: jnp.ndarray, C: jnp.ndarray, row_tile: int = 1024,
                  interpret: bool | None = None):
    """Fused assignment: Y (n, r), C (k, r) -> (labels (n,), min_d2 (n,)).

    Pads n to the row tile, r to 128 lanes, k to 8 sublanes; padded rows are
    sliced off, padded centroids masked inside the kernel.
    """
    interp = _is_cpu() if interpret is None else interpret
    n, r = Y.shape
    k = C.shape[0]
    row_tile, n_pad, r_pad, k_pad = padded_shapes(n, r, k, row_tile)
    Yp = jnp.pad(Y, ((0, n_pad - n), (0, r_pad - r)))
    Cp = jnp.pad(C, ((0, k_pad - k), (0, r_pad - r)))
    labels, d2 = assign_call(Yp, Cp, k, row_tile, interp)
    return labels[:n], d2[:n]


def _assign_build(key, case):
    k1, k2 = jax.random.split(key)
    Y = jax.random.normal(k1, (case["n"], case["r"]), jnp.float32)
    C = jax.random.normal(k2, (case["k"], case["r"]), jnp.float32)
    return (Y, C), {}, {}


def _assign_compare(got, want, rtol, atol):
    # Distances must match tightly; labels can differ only on exact ties.
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=rtol, atol=atol)
    mism = np.asarray(got[0]) != np.asarray(want[0])
    assert mism.mean() < 0.01


register_kernel(KernelEntry(
    name="kmeans_assign", op=assign_pallas, ref=assign_ref,
    cases=({"n": 50, "r": 2, "k": 2}, {"n": 1000, "r": 2, "k": 7},
           {"n": 513, "r": 16, "k": 100}, {"n": 31, "r": 5, "k": 3}),
    build=_assign_build, rtol=1e-4, atol=1e-4,
    compare=_assign_compare))


def _assign_declared(case: dict) -> dict:
    return memory_contract(case["n"], case["r"], case["k"])


register_contract(KernelContract(name="kmeans_assign",
                                 declared=_assign_declared))
