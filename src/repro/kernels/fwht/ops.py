"""Public jit'd wrapper for the FWHT Pallas kernel.

Handles the two-level factorization H_n = (H_a (x) I_b)(I_a (x) H_b) for n
beyond a single VMEM slab: sweep 1 applies H_b inside contiguous length-b
blocks, sweep 2 applies H_a across blocks (via a transpose so the strided
butterflies become contiguous again). Both sweeps reuse the same fused-stage
kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fwht.fwht import fwht_1level
from repro.kernels.fwht.ref import fwht_ref
from repro.kernels.registry import (KernelContract, KernelEntry,
                                    register_contract, register_kernel)

# Max rows for a single-level slab. The fused stages keep several
# slab-sized temporaries live next to the double-buffered in/out blocks:
# compiled for a v5e (16 MiB scoped VMEM), a (2^11, 128) f32 slab needs
# 17.9 MiB once the column grid has two steps, a (2^10, 128) slab fits.
_MAX_SINGLE = 1 << 10


def sweep_shapes(n: int, c: int) -> tuple:
    """The (rows, cols) slab per fwht_1level sweep fwht_pallas issues —
    one slab for n <= _MAX_SINGLE, else the two-level factorization."""
    if n <= _MAX_SINGLE:
        return ((n, c),)
    if n > _MAX_SINGLE ** 2:
        raise ValueError(f"FWHT length {n} exceeds the two-level limit "
                         f"{_MAX_SINGLE ** 2}")
    b = _MAX_SINGLE
    return ((b, (n // b) * c), (n // b, b * c))


def memory_contract(n: int, c: int, col_tile: int = 128) -> dict:
    """Declared HBM byte model: each sweep reads + writes its padded
    slab exactly once — the fused-stage schedule's whole perf argument
    (the naive pay-per-stage schedule is log2(n)x more). Cross-checked
    against fwht_1level's BlockSpecs by `repro.analysis` (rule C001)."""
    hbm = 0.0
    for rows, cols in sweep_shapes(n, c):
        ct = min(col_tile, cols)
        cp = -(-cols // ct) * ct
        hbm += 2 * 4.0 * rows * cp
    return {"sweeps": sweep_shapes(n, c), "hbm_bytes": hbm}


def _is_cpu() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("normalize", "col_tile",
                                             "interpret"))
def fwht_pallas(x: jnp.ndarray, normalize: bool = True, col_tile: int = 128,
                interpret: bool | None = None) -> jnp.ndarray:
    """FWHT along axis 0 of (n, c); n = 2^m. Pallas on TPU, interpret on CPU."""
    interp = _is_cpu() if interpret is None else interpret
    n, c = x.shape
    if n & (n - 1):
        raise ValueError(f"power-of-two length required, got {n}")
    if n <= _MAX_SINGLE:
        return fwht_1level(x, col_tile, normalize, interp)
    # Two-level: n = a * b with b = _MAX_SINGLE.
    (b, _), (a, _) = sweep_shapes(n, c)
    # Sweep 1: H_b within blocks. (a*b, c) -> treat as a separate columns.
    xb = x.reshape(a, b, c).transpose(1, 0, 2).reshape(b, a * c)
    xb = fwht_1level(xb, col_tile, False, interp)
    # Sweep 2: H_a across blocks.
    xa = xb.reshape(b, a, c).transpose(1, 0, 2).reshape(a, b * c)
    xa = fwht_1level(xa, col_tile, False, interp)
    out = xa.reshape(a, b, c)
    if normalize:
        out = out / jnp.sqrt(jnp.asarray(n, x.dtype))
    return out.reshape(n, c)


def _fwht_build(key, case):
    x = jax.random.normal(key, (case["n"], case["c"]), jnp.float32)
    return (x,), {}, {}


register_kernel(KernelEntry(
    name="fwht", op=fwht_pallas, ref=fwht_ref,
    cases=({"n": 8, "c": 3}, {"n": 512, "c": 128}, {"n": 4096, "c": 1},
           {"n": 1 << 14, "c": 2}),
    build=_fwht_build, rtol=2e-4, atol=2e-4))


def _fwht_declared(case: dict) -> dict:
    return memory_contract(case["n"], case["c"])


register_contract(KernelContract(name="fwht", declared=_fwht_declared))
