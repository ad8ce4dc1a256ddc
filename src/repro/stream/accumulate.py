"""Incremental one-pass sketch accumulation: fit as a stream of chunks.

The paper's sketch W = K Omega is a sum over entries of K, so it admits
exact incremental accumulation: when a new block of data points C arrives
after q applied points, the only kernel values that exist beyond the
already-applied principal block are the symmetric border

    Kc = kappa([X_applied | C], C)          (q + b, b)

and the sketch update splits along it:

    W[q:q+b]  = (Omega^T pad(Kc)).T         new rows, one FWHT over the
                                            zero-padded border columns
    W[:q]    += Kc[:q] @ Omega[q:q+b]       symmetric cross-term into the
                                            old rows, via the materialized
                                            Omega row slice (srht_rows_at)

Row norms of K accumulate the same way, giving a streaming estimate of
||K||_F^2 (and hence of the approximation error) for free.

Chunk-size invariance — the contract `KernelKMeans.partial_fit` builds
on — comes from BLOCK-GRANULAR STAGING: `add()` buffers incoming columns
and applies updates only in exact `block`-wide slices; the ragged tail is
applied on a COPY at `eig()` time, so the canonical update sequence never
depends on how callers chunked their data. One-shot `fit` routes through
this same accumulator (repro.api.backends), so a chunked partial_fit
over a full pass is bit-identical to fit at the re-eig boundary.

The sketch is built at a fixed `capacity` (SRHT pads to the next power of
two of the capacity, not of the data seen so far), so the test matrix —
and therefore the fit — is a pure function of (key, capacity) no matter
when data arrives.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Union

import jax
import jax.numpy as jnp

from repro.core.kernels_fn import KernelFn
from repro.core.sketch import (GaussianSketch, LowRankEig, SRHT,
                               make_gaussian, make_srht, one_pass_core,
                               srht_apply_t, srht_rows_at)
from repro.spans import span

Sketch = Union[SRHT, GaussianSketch]


@functools.partial(jax.jit, static_argnames=("b", "kind", "gamma",
                                             "degree", "interpret"),
                   donate_argnums=(1, 2))
def _fused_block_update(X, W, row_norms2, aux, rows, q, *, b: int,
                        kind: str, gamma: float, degree: int,
                        interpret: bool):
    """Fold columns [q, q+b) of X (p, m) into the state (W, row_norms2)
    through the in-place fit_sketch kernel; returns the updated pair.

    W and row_norms2 are the state in the kernel's layout
    (to_kernel_state) and are donated: the kernel updates them in place,
    reading and writing only the row tiles of the border [0, q+b). aux
    is the pass's sketch rows as kernel_rows() lays them out, prepared
    once per pass; rows is None (the Omega rows no longer come from the
    SRHT's sampled rows here). The offset q is traced, so every block of
    one width shares this executable, and no operation outside the
    kernel touches O(m) rows.
    """
    from repro.kernels.fit_sketch.ops import fit_sketch_inplace_jit

    del rows
    return fit_sketch_inplace_jit(X, aux, W, row_norms2, q, b=b, kind=kind,
                                  gamma=gamma, degree=degree,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("m", "n_pad"))
def _prepared_rows(aux, rows, *, m: int, n_pad: int):
    """The sketch rows Omega[:m] of a pass over m columns, in the layout
    the in-place fit_sketch kernel reads: the SRHT's rows materialized
    from its signs `aux` and sampled `rows`, or, when rows is None, the
    dense Gaussian Omega `aux`."""
    from repro.kernels.fit_sketch.ops import kernel_rows

    if rows is None:
        return kernel_rows(aux[:m])
    return kernel_rows(srht_rows_at(jnp.arange(m, dtype=jnp.int32),
                                    aux[:m], rows, n_pad))


class SketchAccumulator:
    """Streaming accumulation of the one-pass sketch state.

    key:         PRNGKey the test matrix is drawn from (same key +
                 capacity => same sketch, whatever the chunking)
    kernel:      KernelFn kappa(X, Z)
    capacity:    maximum total columns this accumulator will ever hold;
                 the SRHT/Gaussian test matrix is sized to it up front
    r:           target rank of `eig()`
    oversampling/block/sketch_type/fwht_fn/truncate_basis: exactly the
                 one-pass backend knobs (repro.api.backends)
    policy:      optional serve.ComputePolicy. policy.mesh routes every
                 block update through the mesh-sharded fit engine
                 (distributed/fit.py, bit-identical on one device);
                 policy.fit_fused routes it through the fused
                 fit_sketch Pallas kernel (fp-tolerance parity), which
                 updates the state in place: self.W / self.row_norms2
                 then hold it in the kernel's layout
                 (kernels/fit_sketch/ops.py:to_kernel_state).
    kernel_statics: (kind, gamma, degree) for the fused kernel; required
                 whenever fit_fused resolves on.

    add(X_chunk) stages columns and applies full-block updates;
    eig() applies the staged tail on a copy and runs Alg. 1 lines 3-6
    on the effective sketch; state_arrays() exports the persistable
    state (FittedModel stream_* leaves) and from_model() resumes from it.
    """

    def __init__(self, key: jax.Array, kernel: KernelFn, capacity: int,
                 r: int, *, oversampling: int = 10, block: int = 512,
                 sketch_type: str = "srht",
                 fwht_fn: Optional[Callable] = None,
                 truncate_basis: bool = False,
                 policy=None, kernel_statics=None):
        capacity = int(capacity)
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        r_prime = int(r) + int(oversampling)
        if sketch_type == "srht":
            sketch: Sketch = make_srht(key, capacity, r_prime)
        elif sketch_type == "gaussian":
            sketch = make_gaussian(key, capacity, r_prime)
        else:
            raise ValueError(f"unknown sketch_type {sketch_type!r}")
        self._bind(kernel, int(r), sketch, None, None, 0, None,
                   block=block, truncate_basis=truncate_basis,
                   fwht_fn=fwht_fn, policy=policy,
                   kernel_statics=kernel_statics)

    def _bind(self, kernel, r, sketch, W, row_norms2, n_applied, X, *,
              block, truncate_basis, fwht_fn, policy=None,
              kernel_statics=None) -> None:
        """W / row_norms2 None: the empty state."""
        self.kernel = kernel
        self.r = int(r)
        self.sketch = sketch
        self.n_applied = int(n_applied)
        self._X = X
        self.block = int(block)
        self.truncate_basis = bool(truncate_basis)
        self.fwht_fn = fwht_fn
        self.reeigs = 0
        self.last_fro2 = 0.0
        self.last_approx_err = 0.0
        self.policy = policy
        self.kernel_statics = kernel_statics
        self._engine = None
        if policy is not None:
            self._fit_fused, self._fit_interpret = policy.resolve_fit()
        else:
            self._fit_fused, self._fit_interpret = False, False
        if self._fit_fused and kernel_statics is None:
            raise ValueError(
                "fit_fused needs the kernel statics (kind, gamma, degree) "
                "for the Pallas fit_sketch kernel — fit through "
                "KernelKMeans (which passes them from the spec) or give "
                "SketchAccumulator kernel_statics=")
        # The single-host fused path keeps the state in the in-place
        # kernel's layout and the pass's sketch rows prepared for it.
        self._inplace = self._fit_fused and policy.mesh is None
        self._rows_m, self._rows = -1, None
        if self._inplace:
            from repro.kernels.fit_sketch.ops import to_kernel_state
            W, row_norms2 = to_kernel_state(W, row_norms2, self.capacity,
                                            self.r_prime)
        elif W is None:
            W = jnp.zeros((self.capacity, self.r_prime), jnp.float32)
            row_norms2 = jnp.zeros((self.capacity,), jnp.float32)
        self.W = W
        self.row_norms2 = row_norms2
        if X is not None:
            self._ensure_engine(int(X.shape[0]))

    def _ensure_engine(self, p: int) -> None:
        """Build the mesh-sharded fit engine on first sight of data (the
        row count p is not known before then): pads the current sketch
        state row-sharded and loads any existing columns into the
        sharded data buffer. From then on self.W / self.row_norms2 hold
        the PADDED sharded (N, r') / (N,) arrays; eig() and
        state_arrays() gather the logical [:capacity] rows back."""
        if (self._engine is not None or self.policy is None
                or self.policy.mesh is None):
            return
        from repro.distributed.fit import ShardedFitEngine

        self._engine = ShardedFitEngine(
            self.policy.mesh, self.policy.mesh_axis, self.sketch,
            self.kernel, p, fit_fused=self._fit_fused,
            interpret=self._fit_interpret,
            kernel_statics=self.kernel_statics)
        self.W = self._engine.pad_rows(self.W)
        self.row_norms2 = self._engine.pad_vec(self.row_norms2)
        if self._X is not None:
            self._engine.ingest(self._X)

    # -- resume ----------------------------------------------------------

    @classmethod
    def from_arrays(cls, kernel: KernelFn, r: int, sketch: Sketch,
                    W: jnp.ndarray, row_norms2: jnp.ndarray,
                    n_applied: int, X: Optional[jnp.ndarray], *,
                    block: int = 512, truncate_basis: bool = False,
                    fwht_fn: Optional[Callable] = None,
                    policy=None, kernel_statics=None
                    ) -> "SketchAccumulator":
        """Rebuild an accumulator around existing state (see from_model)."""
        acc = cls.__new__(cls)
        acc._bind(kernel, r, sketch, jnp.asarray(W, jnp.float32),
                  jnp.asarray(row_norms2, jnp.float32), n_applied,
                  None if X is None else jnp.asarray(X, jnp.float32),
                  block=block, truncate_basis=truncate_basis,
                  fwht_fn=fwht_fn, policy=policy,
                  kernel_statics=kernel_statics)
        if acc.n_added < acc.n_applied or acc.n_added > acc.capacity:
            raise ValueError(
                f"inconsistent stream state: {acc.n_added} columns of data "
                f"for n_applied={acc.n_applied}, capacity={acc.capacity}")
        return acc

    @classmethod
    def from_model(cls, model, *, fwht_fn: Optional[Callable] = None,
                   policy=None, kernel_statics=None
                   ) -> "SketchAccumulator":
        """Resume accumulation from a (possibly published) FittedModel.

        The artifact's stream_* leaves carry the applied sketch state;
        columns of X_train past stream_counts[0] are the staged tail and
        re-enter the pending buffer, so resume-then-eig reproduces the
        pre-publish eig exactly.
        """
        spec = model.spec
        if getattr(model, "stream_counts", None) is None:
            raise ValueError(
                "model carries no streaming state (stream_counts is "
                "missing): only one-pass fits made through "
                "SketchAccumulator can resume partial_fit")
        sketch_type = spec.sketch_type
        if sketch_type == "srht":
            sketch: Sketch = SRHT(signs=model.sketch_signs,
                                  rows=model.sketch_rows,
                                  n=int(model.stream_counts[1]),
                                  n_pad=int(model.sketch_signs.shape[0]))
        elif sketch_type == "gaussian":
            sketch = GaussianSketch(omega=model.sketch_omega)
        else:
            raise ValueError(
                f"backend {spec.backend!r} has no streaming sketch state")
        return cls.from_arrays(
            model.kernel_fn(), spec.r, sketch, model.stream_w,
            model.stream_row_norms2, int(model.stream_counts[0]),
            model.X_train, block=spec.block,
            truncate_basis=bool(
                spec.backend_params.get("truncate_basis", False)),
            fwht_fn=fwht_fn, policy=policy, kernel_statics=kernel_statics)

    # -- views -----------------------------------------------------------

    @property
    def capacity(self) -> int:
        return (self.sketch.n if isinstance(self.sketch, SRHT)
                else int(self.sketch.omega.shape[0]))

    @property
    def r_prime(self) -> int:
        return (self.sketch.r_prime if isinstance(self.sketch, SRHT)
                else int(self.sketch.omega.shape[1]))

    @property
    def n_added(self) -> int:
        """Total columns added (applied + staged)."""
        return 0 if self._X is None else int(self._X.shape[1])

    @property
    def n_pending(self) -> int:
        """Staged columns not yet folded into the canonical W."""
        return self.n_added - self.n_applied

    @property
    def X_all(self) -> jnp.ndarray:
        """All columns added so far, (p, n_added) — the model's X_train."""
        if self._X is None:
            raise RuntimeError("no data accumulated; call add() first")
        return self._X

    # -- accumulation ----------------------------------------------------

    def add(self, X_chunk: jnp.ndarray) -> "SketchAccumulator":
        """Fold one data chunk (p, b) in; applies any full blocks now."""
        X_chunk = jnp.asarray(X_chunk, jnp.float32)
        if X_chunk.ndim != 2 or X_chunk.shape[1] < 1:
            raise ValueError(f"chunk must be (p, b>=1), got "
                             f"{getattr(X_chunk, 'shape', None)}")
        if self._X is not None and X_chunk.shape[0] != self._X.shape[0]:
            raise ValueError(f"chunk has p={X_chunk.shape[0]}, accumulator "
                             f"holds p={self._X.shape[0]}")
        if self.n_added + int(X_chunk.shape[1]) > self.capacity:
            raise ValueError(
                f"capacity {self.capacity} exceeded: have {self.n_added} "
                f"columns, chunk adds {int(X_chunk.shape[1])}")
        # Build the engine BEFORE concatenating — _ensure_engine loads
        # the pre-existing columns into the sharded buffer, then the new
        # chunk goes in once below.
        self._ensure_engine(int(X_chunk.shape[0]))
        self._X = (X_chunk if self._X is None
                   else jnp.concatenate([self._X, X_chunk], axis=1))
        if self._engine is not None:
            self._engine.ingest(X_chunk)
        with span("fit.accumulate"):
            while self.n_added - self.n_applied >= self.block:
                self.W, self.row_norms2 = self._apply(
                    self.W, self.row_norms2, self.n_applied, self.block)
                self.n_applied += self.block
        return self

    def _apply(self, W, row_norms2, q, b):
        """One block update: fold columns [q, q+b) of the data into
        (W, row_norms2); pure — returns the updated pair.

        Dispatch: mesh policy -> the sharded engine (bit-identical to
        the canonical path on one device); fit_fused policy -> the
        single-host Pallas fit_sketch path (fp-tolerance parity, like
        fused serving); otherwise the canonical eager update. On the
        fused path the span also carries `tiles`, the row tiles the
        kernel visits, and `tiles_total`, the tiles of its grid."""
        args = {}
        if self._engine is None and self._fit_fused:
            from repro.kernels.fit_sketch.ops import border_tiles
            args["tiles"], args["tiles_total"] = border_tiles(
                self.n_added, int(q) + int(b))
        with span("fit.block", q=int(q), b=int(b), **args):
            if self._engine is not None:
                return self._engine.apply(W, row_norms2, q, b)
            if self._fit_fused:
                return self._apply_fused(W, row_norms2, q, b)
            return self._apply_eager(W, row_norms2, q, b)

    def _apply_eager(self, W, row_norms2, q, b):
        """The canonical block update, in eager ops.

        Every shape is fixed for the whole pass (m columns added,
        capacity rows) and the offset q is a device scalar, so each
        eager op compiles once per fit, not once per block. The border
        is computed against all m columns with the rows past q+b
        zeroed; each entry is the value kappa(X[:, :q+b], C) would give,
        and the zero rows add nothing to any reduction."""
        X = self._X
        m = int(X.shape[1])
        qd = jnp.asarray(q, jnp.int32)
        rows = jnp.arange(m, dtype=jnp.int32)
        C = jax.lax.dynamic_slice_in_dim(X, qd, b, axis=1)
        Kc = jnp.where((rows < qd + b)[:, None], self.kernel(X, C), 0.0)
        Kp = jnp.zeros((self.capacity, b), jnp.float32).at[:m].set(Kc)
        if isinstance(self.sketch, SRHT):
            new_rows = srht_apply_t(self.sketch, Kp, self.fwht_fn).T
            cross = srht_rows_at(
                qd + jnp.arange(b, dtype=jnp.int32),
                jax.lax.dynamic_slice(self.sketch.signs, (qd,), (b,)),
                self.sketch.rows, self.sketch.n_pad)
        else:
            new_rows = Kp.T @ self.sketch.omega
            cross = jax.lax.dynamic_slice_in_dim(self.sketch.omega, qd, b,
                                                 axis=0)
        # Norms over a zero-padded stripe of the sharded engine's fixed
        # row space (n_pad / capacity): the same reduction lengths, so
        # distributed/fit.py reproduces these bits on one device.
        n_red = (self.sketch.n_pad if isinstance(self.sketch, SRHT)
                 else self.capacity)
        K2 = jnp.zeros((n_red, b), jnp.float32).at[:m].set(Kc)
        K2 = K2 * K2
        applied = rows < qd
        W_m = jnp.where(applied[:, None], W[:m] + Kc @ cross, W[:m])
        rn_m = jnp.where(applied, row_norms2[:m] + jnp.sum(K2, axis=1)[:m],
                         row_norms2[:m])
        W = jax.lax.dynamic_update_slice(W.at[:m].set(W_m), new_rows,
                                         (qd, 0))
        row_norms2 = jax.lax.dynamic_update_slice(
            row_norms2.at[:m].set(rn_m), jnp.sum(K2, axis=0), (qd,))
        return W, row_norms2

    def _apply_fused(self, W, row_norms2, q, b):
        """Single-host block update through the in-place fit_sketch
        Pallas kernel: gram-stripe -> sketch-accumulate in one pass, W
        and row_norms2 (the kernel's layout) updated in place and
        donated. The Omega rows of every column added so far are
        materialized once per pass (fit.prepare), when the column count
        has changed since the last block (the price of trading the FWHT
        for an MXU contraction; the distributed engine shards that slab
        instead)."""
        kind, gamma, degree = self.kernel_statics
        m = self.n_added
        if self._rows_m != m:
            from repro.kernels.fit_sketch.ops import padded_shapes
            if isinstance(self.sketch, SRHT):
                aux, rows = self.sketch.signs, self.sketch.rows
            else:
                aux, rows = self.sketch.omega, None
            self._rows = None           # free the last pass's rows first
            with span("fit.prepare", m=m,
                      m_pad=padded_shapes(m, 1, 1)[1]):
                self._rows = _prepared_rows(
                    aux, rows, m=m,
                    n_pad=int(getattr(self.sketch, "n_pad", 0)))
            self._rows_m = m
        return _fused_block_update(
            self._X, W, row_norms2, self._rows, None,
            jnp.asarray(q, jnp.int32), b=int(b), kind=kind,
            gamma=float(gamma), degree=int(degree),
            interpret=self._fit_interpret)

    def _effective_state(self):
        """(W, row_norms2, n_eff) with the staged tail applied on a COPY
        — the canonical block alignment is never disturbed, so later
        adds keep the chunk-invariant update sequence. In sharded mode
        the result is gathered back to the logical (capacity, .) host
        view: eig() always runs the canonical single-host core on it,
        which is what makes sharded eig bit-identical by construction
        (the sketch is the ONLY thing small enough to be worth
        gathering — the paper's point)."""
        tail = self.n_added - self.n_applied
        W, rn, n_eff = self.W, self.row_norms2, self.n_applied
        if tail:
            if self._inplace:           # the tail's update donates these
                W, rn = jnp.copy(W), jnp.copy(rn)
            W, rn = self._apply(W, rn, self.n_applied, tail)
            # The tail ends the pass: the next block folds in more
            # columns, for which the sketch rows are prepared anew.
            self._rows_m, self._rows = -1, None
            n_eff = self.n_added
        return (*self._logical(W, rn), n_eff)

    def _logical(self, W, rn):
        """(W (capacity, r'), row_norms2 (capacity,)) from the state as
        this accumulator holds it: gathered from the mesh, or read out
        of the in-place kernel's layout (new buffers, never the ones the
        next block update donates)."""
        if self._engine is not None:
            return self._engine.gather(W), self._engine.gather(rn)
        if self._inplace:
            from repro.kernels.fit_sketch.ops import from_kernel_state
            return from_kernel_state(W, rn, self.capacity, self.r_prime)
        return W, rn

    # -- eigendecomposition ----------------------------------------------

    def eig(self, r: Optional[int] = None) -> LowRankEig:
        """Alg. 1 lines 3-6 on the effective sketch (tail included).

        Also refreshes `last_fro2` (exact streaming ||K||_F^2) and
        `last_approx_err` (sqrt(1 - sum(eigvals^2) / ||K||_F^2), the
        free residual estimate the drift monitor thresholds on).
        """
        with span("fit.eig"):
            r = self.r if r is None else int(r)
            W, rn, n_eff = self._effective_state()
            if n_eff < 1:
                raise RuntimeError("no data accumulated; call add() first")
            Wn = W[:n_eff]
            if self.truncate_basis:
                U, S, Vt = jnp.linalg.svd(Wn, full_matrices=False)
                Wn = (U[:, :r] * S[None, :r]) @ Vt[:r]
            if isinstance(self.sketch, SRHT):
                if n_eff == self.capacity:
                    def omega_t_q(Q):
                        return srht_apply_t(self.sketch, Q, self.fwht_fn)
                else:
                    def omega_t_q(Q):
                        Qp = jnp.zeros((self.capacity, Q.shape[1]),
                                       Q.dtype).at[:n_eff].set(Q)
                        return srht_apply_t(self.sketch, Qp, self.fwht_fn)
            else:
                def omega_t_q(Q):
                    return self.sketch.omega[:n_eff].T @ Q
            out = one_pass_core(Wn, omega_t_q, r)
            fro2 = float(jnp.sum(rn))
            tail2 = max(fro2 - float(jnp.sum(out.eigvals ** 2)), 0.0)
            self.last_fro2 = fro2
            self.last_approx_err = (tail2 / fro2) ** 0.5 if fro2 > 0 else 0.0
            self.reeigs += 1
            return out

    # -- persistence -----------------------------------------------------

    def state_arrays(self) -> Dict[str, jnp.ndarray]:
        """The persistable stream state, keyed as FittedModel leaves.

        Staged (pending) columns are NOT separate state: they are the
        trailing columns of the model's X_train, recovered by
        from_model() via stream_counts[0].
        """
        if isinstance(self.sketch, SRHT):
            st = {"sketch_signs": self.sketch.signs,
                  "sketch_rows": self.sketch.rows}
        else:
            st = {"sketch_omega": self.sketch.omega}
        st["stream_w"], st["stream_row_norms2"] = self._logical(
            self.W, self.row_norms2)
        st["stream_counts"] = jnp.array([self.n_applied, self.capacity],
                                        jnp.int32)
        return st

    def __repr__(self) -> str:
        kind = ("srht" if isinstance(self.sketch, SRHT) else "gaussian")
        return (f"SketchAccumulator({kind}, r={self.r}, "
                f"r'={self.r_prime}, {self.n_added}/{self.capacity} cols, "
                f"{self.n_pending} pending)")
