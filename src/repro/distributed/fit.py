"""Communication-avoiding mesh-sharded one-pass fit (training-side mesh).

The serving stack sharded its hot loop long ago (extend.ShardedExtender);
this module does the same for TRAINING: each device owns an n/d row-slab
of the padded sample space, and every block update of the streaming
sketch accumulator (stream/accumulate.py) runs as one jitted shard_map in
which a device only ever touches its own slab:

    Kc_local = kappa(X_slab, C)                 (L, b)  local gram stripe
    new rows = Omega^T pad(Kc): local (masked, sign-scaled) FWHT +
               butterfly_stages (distributed/dfwht.py) + one psum of the
               gathered (r', b) sampled rows — the ONLY sketch collective
    cross    = Kc_local @ Omega[q:q+b]          (L, r') purely local
    norms    = one psum of the (b,) masked column sums

Communication per block is r'*b + b floats — independent of n, the
paper's point restated for the fit path. The per-stripe psum and the
cross-term matmul are independent ops inside one jitted body, so XLA
overlaps the collective with the next contraction's compute.

Bit-identity contract: the DEFAULT path reproduces the single-host
update value-for-value (tests/test_sharded_fit.py pins 1-device
bit-identity; multi-device parity is fp-tolerance, tests/fit_dist_checks)
because every step is either the same arithmetic in the same order
(mask-then-sign matches the canonical zero-pad-then-sign, the local
FWHT + butterfly is the canonical normalized FWHT's Kronecker
factorization, zero-appended reductions are bit-neutral) or exact data
movement (gathers, masked scatters, psum over the slab partition). The
FUSED path (policy.fit_fused -> kernels/fit_sketch) instead materializes
the Omega row slab and contracts on the MXU — fp-tolerance parity, same
trade the fused serving stripe makes.

Eigendecomposition stays single-host: `eig()` gathers the tiny (cap, r')
sketch — the whole point of sketching is that this is the only thing
worth gathering — and runs the canonical Alg. 1 core, bit-identical by
construction.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.kernels_fn import KernelFn
from repro.core.sketch import SRHT, fwht, srht_rows_at
from repro.distributed.dfwht import butterfly_stages


class ShardedFitEngine:
    """Mesh-sharded executor for SketchAccumulator block updates.

    Owns the device placement: a persistent (p, N) column-sharded data
    buffer (N = the padded row space: SRHT's n_pad, or capacity rounded
    up to a shard multiple for Gaussian), the sharded sketch constants
    (signs slab / Omega slab), and one jitted shard_map executable per
    block width b — the block offset q is traced, so chunked ingest with
    ragged tails compiles a bounded handful of executables.

    The accumulator keeps its logical (cap, r') view of W/row_norms2;
    `pad_rows`/`pad_vec` place them row-sharded once and `gather` pulls
    the [:cap] slice back to host only at eig/persist boundaries.
    """

    def __init__(self, mesh, axis: str, sketch, kernel: KernelFn, p: int,
                 *, fit_fused: bool = False, interpret: bool = False,
                 kernel_statics: Optional[Tuple[str, float, int]] = None,
                 local_fwht: Optional[Callable] = None):
        if axis not in mesh.axis_names:
            raise ValueError(f"mesh has no axis {axis!r}; "
                             f"have {mesh.axis_names}")
        self.mesh = mesh
        self.axis = axis
        self.shards = d = dict(mesh.shape)[axis]
        self.sketch = sketch
        self.kernel = kernel
        self.p = int(p)
        self.fit_fused = bool(fit_fused)
        self.interpret = bool(interpret)
        self.kernel_statics = kernel_statics
        if fit_fused and kernel_statics is None:
            raise ValueError(
                "fit_fused needs the kernel statics (kind, gamma, degree) "
                "for the Pallas fit_sketch kernel — fit through "
                "KernelKMeans (which passes them from the spec) or give "
                "SketchAccumulator kernel_statics=")
        self._is_srht = isinstance(sketch, SRHT)
        if self._is_srht:
            self.capacity = int(sketch.n)
            N = int(sketch.n_pad)
            if d & (d - 1):
                raise ValueError(f"sharded SRHT fit needs a power-of-two "
                                 f"device count, got {d}")
            if d > N:
                raise ValueError(f"{d} devices cannot shard the "
                                 f"{N}-row padded sample space")
        else:
            self.capacity = int(sketch.omega.shape[0])
            N = -(-self.capacity // d) * d
        self.N = N
        self.L = N // d
        self._local_fwht = local_fwht or (
            lambda v: fwht(v, normalize=False))
        self._row_sh = NamedSharding(mesh, P(axis))
        self._mat_sh = NamedSharding(mesh, P(axis, None))
        self._col_sh = NamedSharding(mesh, P(None, axis))
        if self._is_srht:
            self._aux = jax.device_put(sketch.signs, self._row_sh)
        else:
            omega_pad = jnp.zeros((N, sketch.omega.shape[1]),
                                  jnp.float32).at[:self.capacity].set(
                                      sketch.omega)
            self._aux = jax.device_put(omega_pad, self._mat_sh)
        self._Xbuf = jax.device_put(jnp.zeros((self.p, N), jnp.float32),
                                    self._col_sh)
        self._n_cols = 0
        self._set_cache: Dict[int, Callable] = {}
        self._apply_cache: Dict[int, Callable] = {}
        # Stand-alone executables for the norm-ledger update (see
        # _build_apply for why they cannot live inside the body).
        self._square_fn = jax.jit(lambda K: K * K)
        self._rowsum_fns: Dict[int, Callable] = {}
        self._colsum_fns: Dict[int, Callable] = {}
        self._merge_fns: Dict[int, Callable] = {}

    # -- data placement ---------------------------------------------------

    def ingest(self, cols: jnp.ndarray) -> None:
        """Append columns to the sharded data buffer (one executable per
        distinct chunk width; the start offset is traced)."""
        cols = jnp.asarray(cols, jnp.float32)
        w = int(cols.shape[1])
        if self._n_cols + w > self.capacity:
            raise ValueError(f"sharded buffer capacity {self.capacity} "
                             f"exceeded at {self._n_cols} + {w} columns")
        fn = self._set_cache.get(w)
        if fn is None:
            fn = jax.jit(
                lambda X, c, s: jax.lax.dynamic_update_slice(X, c, (0, s)),
                out_shardings=self._col_sh)
            self._set_cache[w] = fn
        self._Xbuf = fn(self._Xbuf, cols, jnp.asarray(self._n_cols,
                                                      jnp.int32))
        self._n_cols += w

    def pad_rows(self, W: jnp.ndarray) -> jnp.ndarray:
        """(cap, r') -> row-sharded (N, r')."""
        Wp = jnp.zeros((self.N, W.shape[1]), jnp.float32)
        Wp = Wp.at[:W.shape[0]].set(jnp.asarray(W, jnp.float32))
        return jax.device_put(Wp, self._mat_sh)

    def pad_vec(self, v: jnp.ndarray) -> jnp.ndarray:
        """(cap,) -> row-sharded (N,)."""
        vp = jnp.zeros((self.N,), jnp.float32)
        vp = vp.at[:v.shape[0]].set(jnp.asarray(v, jnp.float32))
        return jax.device_put(vp, self._row_sh)

    def gather(self, arr: jnp.ndarray) -> jnp.ndarray:
        """Pull the logical [:cap] rows back to a replicated host array —
        the eig/persist boundary, the only time sketch state moves."""
        return jnp.asarray(np.asarray(arr)[:self.capacity])

    # -- the sharded block update -----------------------------------------

    def apply(self, W_pad: jnp.ndarray, rn_pad: jnp.ndarray, q: int,
              b: int):
        """Fold columns [q, q+b) into the padded sharded (W, row_norms2);
        pure in its array arguments, like SketchAccumulator._apply."""
        fn = self._apply_cache.get(b)
        if fn is None:
            fn = self._build_apply(int(b))
            self._apply_cache[b] = fn
        return fn(self._Xbuf, W_pad, rn_pad, self._aux,
                  jnp.asarray(q, jnp.int32))

    def _build_apply(self, b: int) -> Callable:
        mesh, ax, d = self.mesh, self.axis, self.shards
        L, N = self.L, self.N
        kern = self.kernel
        srht = self._is_srht
        sketch = self.sketch
        fused, interp = self.fit_fused, self.interpret
        statics = self.kernel_statics
        local_fwht = self._local_fwht
        if srht:
            rows_const = jnp.asarray(sketch.rows, jnp.int32)

        def body(xl, wl, rnl, aux_l, c, q, cross):
            # xl (p, L) data slab, wl (L, r'), rnl (L,), aux_l the signs
            # slab (L,) [srht] or Omega slab (L, r') [gaussian],
            # c (p, b) and cross (b, r') replicated, q traced scalar.
            dev = jax.lax.axis_index(ax)
            gids = dev * L + jax.lax.iota(jnp.int32, L)
            valid = gids < q + b               # border rows [0, q+b)
            applied = gids < q                 # already-folded rows
            isnew = valid & jnp.logical_not(applied)
            if fused:
                kind, gamma, degree = statics
                from repro.kernels.fit_sketch.ops import fit_sketch_pallas
                if srht:
                    O_l = srht_rows_at(gids, aux_l, rows_const, N)
                else:
                    O_l = aux_l
                O_l = jnp.where(valid[:, None], O_l, 0.0)
                V = jnp.zeros((8, L), jnp.float32).at[0].set(
                    valid.astype(jnp.float32))
                # Sweeps the whole local slab (no `border`); the local
                # bound would be clip(q + b - dev * L, 0, L).
                accp, delta, rn_rows, rn_cols = fit_sketch_pallas(
                    xl, O_l, c, cross, V, kind=kind, gamma=gamma,
                    degree=degree, interpret=interp)
                new_rows = jax.lax.psum(accp, ax)          # (b, r')
                colsum = jax.lax.psum(rn_cols, ax)         # (b,)
            else:
                # optimization_barrier: materialize the gram stripe once.
                # Without it XLA clones the cheap producer chain into
                # each consumer fusion, and the clone feeding the norm
                # reduction picks up FMAs the eager canonical path (one
                # executable per op) never emits — a 1-ulp break in the
                # bit-identity contract.
                Kl = jax.lax.optimization_barrier(kern(xl, c))  # (L, b)
                Kv = jnp.where(valid[:, None], Kl, 0.0)
                if srht:
                    # Canonical order: zero-pad (the mask), THEN signs —
                    # matches srht_apply_t on the zero-padded border.
                    Ml = Kv * aux_l[:, None]
                    Fl = local_fwht(Ml)
                    Fl = butterfly_stages(Fl, ax, d)
                    Fl = Fl / jnp.sqrt(jnp.asarray(N, Fl.dtype))
                    base = dev * L
                    inloc = (rows_const >= base) & (rows_const < base + L)
                    loc = jnp.clip(rows_const - base, 0, L - 1)
                    sel = jnp.where(inloc[:, None], Fl[loc], 0.0)
                    wt = jax.lax.psum(sel, ax)             # (r', b)
                    new_rows = wt.T
                else:
                    part = Kv.T @ aux_l                    # (b, r')
                    new_rows = jax.lax.psum(part, ax)
                # The cross-term matmul is independent of the psum above:
                # XLA overlaps the collective with this compute.
                delta = Kl @ cross                         # (L, r')
                colsum = rn_rows = None
            nidx = jnp.clip(gids - q, 0, b - 1)
            wl = jnp.where(applied[:, None], wl + delta, wl)
            wl = jnp.where(isnew[:, None], new_rows[nidx], wl)
            if fused:
                rnl = jnp.where(applied, rnl + rn_rows, rnl)
                rnl = jnp.where(isnew, colsum[nidx], rnl)
                return wl, rnl
            # Default path: the norm ledger is NOT updated here. The CPU
            # fusion emitter folds the square into the in-body
            # reductions as FMAs (optimization_barrier does not stop
            # it), and the column reduction's tree shape depends on its
            # length — both break bit-identity with the canonical eager
            # square-then-reduce executables. So the masked stripe is
            # returned (sharded) and the ledger update runs in the same
            # stand-alone square / reduce / merge executables the
            # canonical path dispatches.
            return wl, Kv

        @jax.jit
        def apply_fn(Xbuf, W, rn, aux, q):
            c = jax.lax.dynamic_slice_in_dim(Xbuf, q, b, axis=1)
            if srht:
                cross = srht_rows_at(
                    q + jnp.arange(b, dtype=jnp.int32),
                    jax.lax.dynamic_slice(sketch.signs, (q,), (b,)),
                    sketch.rows, sketch.n_pad)
            else:
                cross = jax.lax.dynamic_slice_in_dim(sketch.omega, q, b,
                                                     axis=0)
            aux_spec = P(ax) if srht else P(ax, None)
            out2 = P(ax) if fused else P(ax, None)
            return shard_map(
                body, mesh=mesh,
                in_specs=(P(None, ax), P(ax, None), P(ax), aux_spec,
                          P(None, None), P(), P(None, None)),
                out_specs=(P(ax, None), out2),
                check_vma=False)(Xbuf, W, rn, aux, c, q, cross)

        if fused:
            return apply_fn

        square = self._square_fn
        rowsum = self._rowsum_fns.setdefault(
            b, jax.jit(lambda A: jnp.sum(A, axis=1)))
        colsum_fn = self._colsum_fns.setdefault(
            b, jax.jit(lambda A: jnp.sum(A, axis=0)))
        merge = self._merge_fns.setdefault(b, self._build_merge(b))

        def apply_default(Xbuf, W, rn, aux, q):
            wl, Kv = apply_fn(Xbuf, W, rn, aux, q)
            # Norm-ledger update as stand-alone executables (square,
            # minor-axis reduce for applied rows, shape-stable column
            # reduce for new rows, masked merge) — the same
            # materialize-then-reduce sequence the canonical eager path
            # runs, hence the same bits on one device. On a multi-device
            # mesh the column reduce becomes partial-sums + all-reduce
            # under GSPMD (fp-tolerance parity there).
            K2 = square(Kv)
            return wl, merge(rn, rowsum(K2), colsum_fn(K2),
                             jnp.asarray(q, jnp.int32))

        return apply_default

    def _build_merge(self, b: int) -> Callable:
        gids = jnp.arange(self.N, dtype=jnp.int32)

        def merge(rn, inc, colsum, q):
            applied = gids < q
            isnew = (gids >= q) & (gids < q + b)
            nidx = jnp.clip(gids - q, 0, b - 1)
            rn = jnp.where(applied, rn + inc, rn)
            return jnp.where(isnew, colsum[nidx], rn)

        return jax.jit(merge)
