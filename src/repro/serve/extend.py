"""Out-of-sample extension: embed and assign new points against a fit.

Every approximation backend (repro.api.backends) reduces to the same
extension operator: eigenpairs (U, Sigma) over a set of REFERENCE points
(`model.extension_ref` — the training set for one-pass/exact fits, the m
sampled landmarks for Nystrom fits), and a new point x embeds as

    y(x) = Sigma^{-1/2} U^T kappa(ref, x)              in R^r

For one-pass/exact this reproduces the fitted Y exactly on training
points whenever the kernel matrix is (numerically) rank <= r' — for a
training point x_j, kappa(X_train, x_j) = K e_j = U Sigma U^T e_j and the
formula collapses to Sigma^{1/2} U^T e_j = Y e_j. For Nystrom fits
(U, Sigma) are the landmark-gram eigenpairs and the identity is exact BY
CONSTRUCTION for every kernel (the fitted Y *is* this formula evaluated
on the training columns), and the per-stripe kernel cost drops from
n x block to m x block.

Memory model (`Extender`): the (n_ref, b) kernel block kappa(ref, X_query)
is never materialized beyond n_ref x min(b, block) — query columns stream
in stripes of the SAME `block` the training pass used, so serving never
exceeds the training-time memory budget no matter how many queries arrive
at once. Two stripe engines implement that contract:

  fused (the serving default off-CPU)  one Pallas executable per stripe:
      kernels/extend_embed builds each (row_tile, block) gram tile and
      contracts it against P = Sigma^{-1/2} U^T on-chip, so even the
      n x block stripe only ever exists as one VMEM tile — the (n, block)
      block never round-trips through HBM between gram and projection.
  two-pass (the CPU default)  one jitted gram_stripe executable plus one
      jitted projection executable per stripe, (n, block) materialized
      between them (kernels_fn.stripe_iterator, pad_tail=True).

Both engines run every stripe — ragged tails included — through one
jitted executable per bucket shape (queries are zero-padded to a column
multiple of the stripe width), so steady-state serving never retraces.

Pallas path selection is EXPLICIT: `fused=None` picks the Pallas engine
off-CPU; `fused=True` on CPU runs it in interpret mode (with a warning
unless `interpret=True` was passed, which is how CI forces the Pallas
path on CPU); `fused=True, interpret=False` on CPU and `fused=False,
interpret=<anything>` are conflicting settings and raise. The same rules
govern the Pallas kmeans_assign assignment path (`assign_fused=`).

Mesh-sharded path (`ShardedExtender`): the extension matmul
P kappa(X_train, x) is the serving-time hot loop, and it shards the same
way the sharded fit does (distributed/fit.py): X_train and P both
column-sharded over the mesh's data axis, each device computing its
n/shards x block stripe of the kernel against the replicated query block
fused into its (r, block) partial projection, combined by ONE psum of the
tiny (r, block) partials. Per-device kernel memory drops from n x block
to n/shards x block and embedding throughput scales with device count;
see docs/SERVING.md.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.kernels_fn import stripe_iterator
from repro.core.kmeans import _sq_dists
from repro.kernels.extend_embed.ops import extend_embed_pallas
from repro.kernels.kmeans_assign.ops import assign_pallas
from repro.serve.artifact import FittedModel
from repro.serve.policy import (ComputePolicy, merge_legacy_kwargs,
                                resolve_pallas_path)

__all__ = ["Extender", "ShardedExtender", "embed", "assign",
           "embed_sharded", "resolve_pallas_path"]

# Keep in sync with core/nystrom._ABS_EIG_FLOOR: the Nystrom fit floors
# its truncation threshold here so fit and serve agree on which
# directions are rank-deficient.
_EIG_EPS = 1e-7

# kernel_fn() falls back to these when the spec omits a param (see
# kernels_fn registry defaults); the Pallas static args must agree.
_STATIC_DEFAULTS = {"polynomial": {"gamma": 0.0, "degree": 2},
                    "rbf": {"gamma": 1.0}, "linear": {}}


def _kernel_statics(spec) -> Tuple[str, float, int]:
    kp = dict(_STATIC_DEFAULTS.get(spec.kernel, {}))
    kp.update(spec.kernel_params)
    return spec.kernel, float(kp.get("gamma", 0.0)), int(kp.get("degree", 2))


# resolve_pallas_path moved to serve/policy.py (absorbed into
# ComputePolicy); re-exported above so existing imports keep working.


@jax.jit
def _project_stripe(proj: jnp.ndarray, stripe: jnp.ndarray) -> jnp.ndarray:
    """P = Sigma^{-1/2} U^T applied to one (n, block) stripe -> (r, block).

    The second executable of the two-pass engine — the (n, block) stripe
    is an HBM round-trip between gram and this matmul (the fused engine
    exists to delete exactly that traffic).
    """
    return proj @ stripe


@functools.partial(jax.jit, static_argnames=("kind", "gamma", "degree",
                                             "block", "interpret"))
def _fused_stripe(X: jnp.ndarray, proj: jnp.ndarray, Xqp: jnp.ndarray,
                  start: jnp.ndarray, *, kind: str, gamma: float,
                  degree: int, block: int, interpret: bool) -> jnp.ndarray:
    """One fused serving stripe; `start` is traced so all stripes of a
    bucket — ragged tail included — share this single executable."""
    xb = jax.lax.dynamic_slice_in_dim(Xqp, start, block, axis=1)
    return extend_embed_pallas(X, proj, xb, kind=kind, gamma=gamma,
                               degree=degree, interpret=interpret)


def _projection(model: FittedModel) -> jnp.ndarray:
    """P = Sigma^{-1/2} U^T (r, n). Eigenvalues below _EIG_EPS
    (rank-deficient directions) map to 0 rather than exploding; those
    coordinates carry no kernel mass anyway."""
    inv_sqrt = jnp.where(model.eigvals > _EIG_EPS,
                         1.0 / jnp.sqrt(model.eigvals), 0.0)
    return inv_sqrt[:, None] * model.U.T


class Extender:
    """Single-device extension engine: fused Pallas stripe or two-pass.

    Holds the precomputed projection P = Sigma^{-1/2} U^T and the resolved
    path choices, so serving front-ends (MicroBatcher/AsyncBatcher)
    construct one Extender and reuse its executables.

    policy: a ComputePolicy; embed_fused picks the extend_embed stripe
    engine, assign_fused the Pallas kmeans_assign argmin, interpret the
    Pallas interpret-mode override for both (see
    policy.resolve_pallas_path for the conflict rules). The `fused=` /
    `interpret=` / `assign_fused=` kwargs are the deprecated spelling of
    the same three fields.
    """

    def __init__(self, model: FittedModel, block: Optional[int] = None, *,
                 policy: Optional[ComputePolicy] = None,
                 fused: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 assign_fused: Optional[bool] = None):
        policy = merge_legacy_kwargs(
            policy, {"embed_fused": fused, "interpret": interpret,
                     "assign_fused": assign_fused}, "Extender")
        self.model = model
        self.policy = policy
        self.block = block or model.spec.block
        self._interpret_arg = policy.interpret
        self.fused, self._interpret = policy.resolve_embed()
        self.assign_fused, self._assign_interpret = policy.resolve_assign()
        # Backend-agnostic: the reference set the kernel stripes run
        # against (training points, or the Nystrom landmarks).
        self._ref = model.extension_ref
        self._proj = _projection(model)
        self._statics = _kernel_statics(model.spec)

    def embed(self, Xq: jnp.ndarray,
              block: Optional[int] = None) -> jnp.ndarray:
        """Embed query points Xq (p, b) -> Y_q (r, b), streaming over
        columns in stripes of `block` (callers may narrow per bucket)."""
        model = self.model
        if Xq.shape[0] != model.spec.p:
            raise ValueError(f"query dim {Xq.shape[0]} != model dim "
                             f"{model.spec.p}")
        block = block or self.block
        b = Xq.shape[1]
        out = jnp.zeros((model.spec.r, b), jnp.float32)
        if self.fused:
            kind, gamma, degree = self._statics
            b_pad = -(-b // block) * block
            Xqp = (Xq if b_pad == b
                   else jnp.pad(Xq, ((0, 0), (0, b_pad - b))))
            for start in range(0, b, block):
                yb = _fused_stripe(self._ref, self._proj, Xqp,
                                   jnp.asarray(start), kind=kind,
                                   gamma=gamma, degree=degree, block=block,
                                   interpret=self._interpret)
                width = min(block, b - start)
                out = jax.lax.dynamic_update_slice(out, yb[:, :width],
                                                   (0, start))
            return out
        kern = model.kernel_fn()
        for start, stripe in stripe_iterator(kern, Xq, block,
                                             lhs=self._ref,
                                             pad_tail=True):
            yb = _project_stripe(self._proj, stripe)
            width = min(block, b - start)
            out = jax.lax.dynamic_update_slice(out, yb[:, :width],
                                               (0, start))
        return out

    def assign(self, Xq: jnp.ndarray, block: Optional[int] = None,
               fused: Optional[bool] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Assign queries to fitted clusters: (labels (b,), sq dist (b,)).

        `fused` overrides the constructor's assignment-path choice for
        this call (re-resolved, so the CPU conflict rules still apply;
        the constructor's interpret arg is only replayed when the Pallas
        path is requested — fused=False per call always means the jnp
        argmin, even on an interpret=True extender).
        """
        if fused is None:
            use_fused, interp = self.assign_fused, self._assign_interpret
        else:
            use_fused, interp = resolve_pallas_path(
                fused, self._interpret_arg if fused else None,
                "Pallas kmeans_assign")
        Yq = self.embed(Xq, block).T                     # (b, r)
        if use_fused:
            return assign_pallas(Yq, self.model.centroids,
                                 interpret=interp)
        return _assign_jnp(Yq, self.model.centroids)


def embed(model: FittedModel, Xq: jnp.ndarray, block: Optional[int] = None,
          fused: Optional[bool] = None,
          interpret: Optional[bool] = None, *,
          policy: Optional[ComputePolicy] = None) -> jnp.ndarray:
    """One-shot embed Xq (p, b) -> (r, b). Serving paths should hold an
    `Extender` and reuse it; this constructs a throwaway one (the jitted
    stripe executables are shared module-level, so only the tiny
    projection precompute is repaid)."""
    policy = merge_legacy_kwargs(
        policy, {"embed_fused": fused, "interpret": interpret}, "embed")
    return Extender(model, block, policy=policy).embed(Xq)


@jax.jit
def _assign_jnp(Yq: jnp.ndarray, C: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    d2 = _sq_dists(Yq, C)
    return jnp.argmin(d2, axis=1).astype(jnp.int32), jnp.min(d2, axis=1)


def assign(model: FittedModel, Xq: jnp.ndarray,
           block: Optional[int] = None, fused: Optional[bool] = None,
           embed_fused: Optional[bool] = None,
           interpret: Optional[bool] = None, *,
           policy: Optional[ComputePolicy] = None
           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Assign queries to fitted clusters: (labels (b,), sq distance (b,)).

    policy.assign_fused routes the argmin through the Pallas
    kmeans_assign kernel (the serving default off-CPU); embed_fused picks
    the extend_embed stripe engine; interpret applies to both Pallas
    kernels (see policy.resolve_pallas_path for the explicit CPU-override
    contract). The positional fused/embed_fused/interpret kwargs are the
    deprecated spelling.
    """
    policy = merge_legacy_kwargs(
        policy, {"assign_fused": fused, "embed_fused": embed_fused,
                 "interpret": interpret}, "assign")
    return Extender(model, block, policy=policy).assign(Xq)


# ---------------------------------------------------------------------------
# Mesh-sharded extension
# ---------------------------------------------------------------------------

class ShardedExtender:
    """Extension matmul sharded over a mesh axis, one psum per stripe.

    Placement (fixed at construction, so steady-state serving never moves
    training data again):

        X_train (p, n_pad)  columns sharded P(None, axis)
        proj    (r, n_pad)  columns sharded P(None, axis)
        queries (p, block)  replicated per stripe

    n is zero-padded up to a multiple of the shard count; padded proj
    columns are zero (they come from padded U rows), so whatever kernel
    values the padded X_train columns produce are annihilated by the
    projection (exact, not approximate — this is why X_train's
    zero-padding is safe even for kernels with kappa(0, x) != 0, e.g.
    rbf).

    Per stripe each device contracts its (n_pad/shards, block) slab of
    kappa(X_train, x) into an (r, block) partial — through the fused
    extend_embed Pallas kernel when `fused` resolves on (the slab then
    never leaves VMEM either), or a jnp gram+matmul otherwise — and the
    single psum sums the partials. Communication per stripe is r * block
    floats — independent of n.
    """

    def __init__(self, model: FittedModel, mesh=None, axis: str = "data",
                 block: Optional[int] = None,
                 fused: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 assign_fused: Optional[bool] = None, *,
                 policy: Optional[ComputePolicy] = None):
        # mesh/axis may arrive positionally (the class's raison d'etre,
        # not deprecated) or inside the policy; the Pallas knobs follow
        # the standard legacy-kwarg shim.
        policy = merge_legacy_kwargs(
            policy, {"embed_fused": fused, "interpret": interpret,
                     "assign_fused": assign_fused}, "ShardedExtender")
        if mesh is None:
            mesh, axis = policy.mesh, policy.mesh_axis
        if mesh is None:
            raise ValueError("ShardedExtender needs a mesh — pass mesh= "
                             "or a policy with policy.mesh set")
        if axis not in mesh.axis_names:
            raise ValueError(f"mesh has no axis {axis!r}; "
                             f"have {mesh.axis_names}")
        self.model = model
        self.mesh = mesh
        self.axis = axis
        self.policy = policy
        self.block = block or model.spec.block
        self.shards = dict(mesh.shape)[axis]
        self._interpret_arg = policy.interpret
        self.fused, self._interpret = policy.resolve_embed(
            "fused extend_embed stripe (sharded)")
        self.assign_fused, self._assign_interpret = policy.resolve_assign()
        # Reference set (training points or Nystrom landmarks), padded to
        # a column multiple of the shard count.
        n = model.n_ref
        n_pad = -(-n // self.shards) * self.shards
        Xt = model.extension_ref
        proj = _projection(model)
        if n_pad != n:
            Xt = jnp.pad(Xt, ((0, 0), (0, n_pad - n)))
            proj = jnp.pad(proj, ((0, 0), (0, n_pad - n)))
        self._Xt = jax.device_put(Xt, NamedSharding(mesh, P(None, axis)))
        self._proj = jax.device_put(proj,
                                    NamedSharding(mesh, P(None, axis)))
        kern = model.kernel_fn()
        kind, gamma, degree = _kernel_statics(model.spec)
        block_w = self.block
        ax = self.axis
        use_fused, interp = self.fused, self._interpret

        @jax.jit
        def stripe_embed(Xt_sh, proj_sh, Xqp, start):
            xb = jax.lax.dynamic_slice_in_dim(Xqp, start, block_w, axis=1)

            def body(xl, prl, xbl):
                if use_fused:
                    part = extend_embed_pallas(
                        xl, prl, xbl, kind=kind, gamma=gamma,
                        degree=degree, interpret=interp)
                else:
                    part = prl @ kern(xl, xbl)           # (r, block)
                return jax.lax.psum(part, ax)            # (r, block)

            return shard_map(body, mesh=mesh,
                             in_specs=(P(None, ax), P(None, ax),
                                       P(None, None)),
                             out_specs=P(None, None),
                             check_vma=False)(Xt_sh, proj_sh, xb)

        self._stripe_embed = stripe_embed

    def embed(self, Xq: jnp.ndarray) -> jnp.ndarray:
        """Embed Xq (p, b) -> (r, b), streaming query columns in stripes.

        Same single-executable streaming discipline as the unsharded
        `Extender.embed`: Xq is zero-padded to a column multiple of
        `block`, every stripe (ragged tail included) runs the one jitted
        sharded executable, and padded columns are sliced off at the end.
        """
        if Xq.shape[0] != self.model.spec.p:
            raise ValueError(f"query dim {Xq.shape[0]} != model dim "
                             f"{self.model.spec.p}")
        b = Xq.shape[1]
        block = self.block
        b_pad = -(-b // block) * block
        Xqp = (Xq if b_pad == b
               else jnp.pad(Xq, ((0, 0), (0, b_pad - b))))
        out = jnp.zeros((self.model.spec.r, b_pad), jnp.float32)
        for start in range(0, b_pad, block):
            yb = self._stripe_embed(self._Xt, self._proj, Xqp,
                                    jnp.asarray(start))
            out = jax.lax.dynamic_update_slice(out, yb, (0, start))
        return out[:, :b]

    def assign(self, Xq: jnp.ndarray, fused: Optional[bool] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Sharded-embed then centroid argmin; mirrors `Extender.assign`."""
        if fused is None:
            use_fused, interp = self.assign_fused, self._assign_interpret
        else:
            use_fused, interp = resolve_pallas_path(
                fused, self._interpret_arg if fused else None,
                "Pallas kmeans_assign")
        Yq = self.embed(Xq).T                            # (b, r)
        if use_fused:
            return assign_pallas(Yq, self.model.centroids,
                                 interpret=interp)
        return _assign_jnp(Yq, self.model.centroids)


def embed_sharded(model: FittedModel, Xq: jnp.ndarray, mesh,
                  axis: str = "data",
                  block: Optional[int] = None) -> jnp.ndarray:
    """One-shot sharded embed (constructs a throwaway ShardedExtender;
    serving paths should hold one and reuse its placement/executable)."""
    return ShardedExtender(model, mesh, axis, block).embed(Xq)
