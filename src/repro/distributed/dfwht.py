"""Distributed FWHT: mesh-collective butterfly (DESIGN.md §3).

The paper parallelizes H with pthreads (11x on 16 threads); at cluster
scale the transform rows are sharded over the mesh, so we use the Kronecker
factorization H_n = H_dev (x) H_local:

  1. local FWHT on each shard's rows (Pallas kernel on TPU),
  2. log2(ndev) butterfly stages across devices via `jax.lax.ppermute`
     (each stage: exchange the full local block with the XOR-partner and
     combine +/-).

Stage k moves n/ndev * c elements per device — total collective traffic
log2(ndev) * n * c / ndev per device, the classic hypercube FWHT schedule.
This is exactly what the one-pass sketch needs to precondition a
row-sharded kernel stripe without gathering it.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.sketch import fwht as _fwht_ref


def butterfly_stages(xl: jnp.ndarray, axis: str, ndev: int) -> jnp.ndarray:
    """H_dev butterfly across devices, inside a shard_map body.

    xl is one device's (n/ndev, ...) row slab after its LOCAL
    (unnormalized) FWHT; log2(ndev) ppermute stages exchange the full
    slab with the XOR-partner and combine +/-. Shared by
    `distributed_fwht` and the sharded fit engine (distributed/fit.py),
    which inlines the transform into its per-block update body.
    """
    idx = jax.lax.axis_index(axis)
    h = 1
    while h < ndev:
        perm = [(i, i ^ h) for i in range(ndev)]
        other = jax.lax.ppermute(xl, axis, perm=perm)
        low = (idx & h) == 0
        xl = jnp.where(low, xl + other, other - xl)
        h *= 2
    return xl


def distributed_fwht(x: jnp.ndarray, mesh, axis: str = "data",
                     normalize: bool = True,
                     local_fwht: Optional[Callable] = None) -> jnp.ndarray:
    """FWHT along axis 0 of (n, c), rows sharded P(axis, None) on `mesh`.

    n and the axis size must be powers of two. `local_fwht` defaults to the
    pure-jnp FWHT; pass repro.kernels.fwht_pallas on TPU.
    """
    n = x.shape[0]
    ndev = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    if n & (n - 1) or ndev & (ndev - 1):
        raise ValueError(f"n={n} and axis size={ndev} must be powers of two")
    lf = local_fwht or (lambda v: _fwht_ref(v, normalize=False))

    def body(xl):
        # xl: (n/ndev, c) local block. Step 1: H_local.
        xl = lf(xl)
        # Step 2: H_dev butterfly across devices.
        xl = butterfly_stages(xl, axis, ndev)
        if normalize:
            xl = xl / jnp.sqrt(jnp.asarray(n, xl.dtype))
        return xl

    spec = P(axis, *(None,) * (x.ndim - 1))
    # Every mesh axis other than `axis` sees replicated data.
    return shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec,
                     check_vma=False)(x)
