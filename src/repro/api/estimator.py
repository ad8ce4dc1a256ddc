"""KernelKMeans: the sklearn-shaped estimator over pluggable backends.

One front door for the paper's whole comparison surface:

    est = KernelKMeans(k=2, r=2, kernel="polynomial",
                       kernel_params={"gamma": 0.0, "degree": 2},
                       backend="onepass-srht").fit(X, key=0)
    est.labels_                   # training clustering
    est.predict(X_new)            # out-of-sample assignment
    est.embed(X_new)              # (r, b) linearized new points
    est.score(X_new)              # -sum of squared centroid distances
    est.save("artifacts/demo")    # servable FittedModel artifact

`fit` is spec-driven: every constructor argument lands in one frozen
`ClusteringSpec` (serve/artifact.py), the chosen backend
(repro.api.backends) produces the rank-r `Embedding`, standard K-means
clusters its columns, and the result is packaged as a `FittedModel` — so
a fit from ANY backend flows through the entire serving stack
(MicroBatcher / AsyncBatcher / ModelRegistry / VersionStore / hot-swap)
unchanged.

RNG contract: `fit(X, key)` splits the key once into (backend, kmeans)
sub-keys — exactly the split the historical `fit_model` /
`one_pass_kernel_kmeans` used, so the deprecation shims over this class
reproduce their old outputs bit-for-bit.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import backends as be
from repro.core.kernels_fn import kernel_params_for
from repro.core.kmeans import kmeans
from repro.serve import extend
from repro.serve.artifact import (ClusteringSpec, FittedModel,
                                  _cached_kernel, load_model, save_model)
from repro.spans import span

# fit_model's historical default for the paper's primary kernel.
_KERNEL_DEFAULTS = {"polynomial": {"gamma": 0.0, "degree": 2}}


def _as_key(key: Union[None, int, jax.Array]) -> jax.Array:
    if key is None:
        return jax.random.PRNGKey(0)
    if isinstance(key, (int, np.integer)):
        return jax.random.PRNGKey(int(key))
    return key


def _spec_safe(params: Dict) -> Dict:
    """The JSON-serializable subset of backend_params — runtime-only
    knobs (e.g. a fwht_fn callable for the TPU FWHT) are used by the fit
    but cannot land in the persisted spec. Numpy scalars (a caller
    passing m=np.int64(128) is routine) are real config, not runtime
    state — coerce them rather than dropping them."""
    out = {}
    for name, val in params.items():
        if isinstance(val, np.integer):
            val = int(val)
        elif isinstance(val, np.floating):
            val = float(val)
        elif isinstance(val, np.bool_):
            val = bool(val)
        try:
            json.dumps(val)
        except TypeError:
            continue
        out[name] = val
    return out


class KernelKMeans:
    """Kernel K-means at rank r through a pluggable approximation backend.

    Parameters mirror `ClusteringSpec` (the frozen config this estimator
    is driven by): `kernel` is a registry NAME (core/kernels_fn) so the
    fit is serializable; `backend` one of
    `repro.api.available_backends()`; `backend_params` its knobs
    (`oversampling` for one-pass, `m` for Nystrom — non-serializable
    values like `fwht_fn` are honoured at fit time but excluded from the
    persisted spec); `policy` an optional `serve.ComputePolicy` choosing
    the compute path end to end — `policy.mesh` shards the one-pass fit
    across devices (repro.distributed.fit), `fit_fused`/`embed_fused`/
    `assign_fused` route through the Pallas kernels. The policy is
    runtime state, not config: it never lands in the spec or artifact.

    Fitted attributes (sklearn convention, trailing underscore):
        labels_     (n,)   training cluster labels
        embedding_  (r, n) linearized training samples Y
        eigvals_    (r,)   eigenvalues of the approximation
        centroids_  (k, r) K-means centroids
        inertia_    float  K-means objective (sum of squared distances)
        spec_              the bound ClusteringSpec (n, p filled in)
        model_             the packaged FittedModel (servable artifact)
    """

    def __init__(self, k: int = 2, r: int = 2, *,
                 kernel: str = "polynomial",
                 kernel_params: Optional[Dict] = None,
                 backend: str = "onepass-srht",
                 backend_params: Optional[Dict] = None,
                 block: int = 512, n_restarts: int = 10,
                 max_iter: int = 20, policy=None):
        be.get_backend(backend)                      # fail fast
        valid = kernel_params_for(kernel)            # fail fast
        if kernel_params is None:
            kernel_params = dict(_KERNEL_DEFAULTS.get(kernel, {}))
        unknown = set(kernel_params) - valid
        if unknown:
            raise ValueError(
                f"unknown param(s) {sorted(unknown)} for kernel "
                f"{kernel!r}; valid params: {sorted(valid) or 'none'}")
        self.k = int(k)
        self.r = int(r)
        self.kernel = kernel
        self.kernel_params = dict(kernel_params)
        self.backend = backend
        self.backend_params = dict(backend_params or {})
        self.block = int(block)
        self.n_restarts = int(n_restarts)
        self.max_iter = int(max_iter)
        # policy (a serve.ComputePolicy) picks the compute path — fused
        # Pallas kernels, interpret mode, and (for one-pass fits) the
        # mesh-sharded fit engine. Runtime-only: never persisted.
        self.policy = policy
        self.model_: Optional[FittedModel] = None
        # Live streaming state (partial_fit); not part of the artifact —
        # resume from a loaded model_ rebuilds it on demand.
        self._acc = None
        self._k_km: Optional[jax.Array] = None
        # Training-side attributes; stay None on the from_model()/load()
        # path (they are not part of the artifact).
        self.labels_ = None
        self.embedding_ = None
        self.eigvals_ = None
        self.centroids_ = None
        self.inertia_: Optional[float] = None
        self.spec_ = None
        self._extender: Optional[extend.Extender] = None

    # -- fitting ---------------------------------------------------------

    def _make_spec(self, n: int, p: int) -> ClusteringSpec:
        return ClusteringSpec(
            kernel=self.kernel, kernel_params=dict(self.kernel_params),
            k=self.k, r=self.r, backend=self.backend,
            backend_params=_spec_safe(self.backend_params),
            block=self.block, n_restarts=self.n_restarts,
            max_iter=self.max_iter, n=int(n), p=int(p))

    def _kernel_fn(self):
        return _cached_kernel(self.kernel,
                              tuple(sorted(self.kernel_params.items())))

    def _policy_kwargs(self, spec: ClusteringSpec) -> Dict:
        """Backend kwargs the policy adds. Only the one-pass backends
        understand policy=/kernel_statics= — nystrom/exact have no
        sharded or fused fit path, so a policy is silently inert there
        (its serve-side knobs still apply through extender())."""
        if self.policy is None or not self.backend.startswith("onepass-"):
            return {}
        return {"policy": self.policy,
                "kernel_statics": extend._kernel_statics(spec)}

    def _package(self, spec: ClusteringSpec, X: jnp.ndarray, U, eigvals,
                 centroids, state: Dict, ref=None) -> FittedModel:
        with span("fit.package"):
            return FittedModel(
                spec=spec, X_train=jnp.asarray(X, jnp.float32),
                U=U, eigvals=eigvals, centroids=centroids,
                sketch_signs=state.get("sketch_signs"),
                sketch_rows=state.get("sketch_rows"),
                sketch_omega=state.get("sketch_omega"),
                landmarks=ref,
                landmark_idx=state.get("landmark_idx"),
                stream_w=state.get("stream_w"),
                stream_row_norms2=state.get("stream_row_norms2"),
                stream_counts=state.get("stream_counts"))

    def fit(self, X: jnp.ndarray,
            key: Union[None, int, jax.Array] = None) -> "KernelKMeans":
        """Fit on X (p, n); `key` may be a PRNGKey, an int seed, or None
        (seed 0). Returns self."""
        with span("fit", n=int(X.shape[1]), p=int(X.shape[0]),
                  backend=self.backend):
            key = _as_key(key)
            spec = self._make_spec(n=X.shape[1], p=X.shape[0])
            kern = self._kernel_fn()
            k_backend, k_km = jax.random.split(key)
            emb = be.get_backend(self.backend).fit(
                k_backend, kern, X, self.r, block=self.block,
                **self.backend_params, **self._policy_kwargs(spec))
            with span("fit.kmeans"):
                km = kmeans(k_km, emb.Y.T, self.k,
                            n_restarts=self.n_restarts,
                            max_iter=self.max_iter)
            self.model_ = self._package(spec, X, emb.U, emb.eigvals,
                                        km.centroids, emb.arrays,
                                        ref=emb.ref)
            self.inertia_ = float(km.objective)
        self.labels_ = km.labels
        self.embedding_ = emb.Y
        self.eigvals_ = emb.eigvals
        self.centroids_ = km.centroids
        self.spec_ = spec
        self._extender = None
        self._acc = None          # a fresh fit retires live stream state
        self._k_km = k_km
        return self

    # -- streaming fit ---------------------------------------------------

    def partial_fit(self, X_chunk: jnp.ndarray,
                    key: Union[None, int, jax.Array] = None, *,
                    capacity: Optional[int] = None, reeig: bool = True,
                    kmeans_mode: str = "full", minibatch_size: int = 256,
                    minibatch_steps: int = 50) -> "KernelKMeans":
        """Fold one data chunk (p, b) into a streaming fit. Returns self.

        The first call fixes the RNG exactly as `fit` does (one split
        into backend/K-means sub-keys), so a chunked pass over X is
        bit-identical to `fit(X, key)` at the re-eig boundary — the test
        matrix is sized to `capacity` up front (required on the first
        call; `capacity=n` reproduces fit, larger leaves room to keep
        streaming). When the estimator holds a model with streaming
        state (a resumed artifact, an earlier fit/partial_fit), `key`
        seeds only the K-means step and accumulation resumes from the
        persisted sketch slab.

        reeig=False accumulates without refreshing the model — the cheap
        steady-state path; any later call with reeig=True (or
        `reeig_now()`) folds the staged tail in and re-eigs.
        kmeans_mode: "full" (restarted Lloyd, the fit-parity path) or
        "minibatch" (Sculley updates in r-space for huge n —
        repro.stream.minibatch).
        """
        X_chunk = jnp.asarray(X_chunk, jnp.float32)
        # Fail fast on malformed chunks — a transposed chunk or a policy
        # swap mid-stream would otherwise surface as a shape error (or
        # silent recompile) deep inside the accumulator.
        p_fit = None
        if self._acc is not None and self._acc._X is not None:
            p_fit = int(self._acc._X.shape[0])
        elif self.model_ is not None:
            p_fit = int(self.model_.spec.p)
        if X_chunk.ndim != 2:
            raise ValueError(
                f"partial_fit chunk must be 2-D (p, b); got shape "
                f"{tuple(X_chunk.shape)}")
        if p_fit is not None and int(X_chunk.shape[0]) != p_fit:
            raise ValueError(
                f"partial_fit chunk has {int(X_chunk.shape[0])} feature "
                f"rows but this fit holds p={p_fit} — chunks are (p, b) "
                f"column blocks over a fixed feature dimension")
        if self._acc is not None and self._acc.policy != self.policy:
            raise ValueError(
                f"ComputePolicy changed mid-stream: the streaming state "
                f"was built under {self._acc.policy!r} but the estimator "
                f"now holds {self.policy!r}. The fit compute path (mesh "
                f"sharding / fused kernels) is fixed at the first "
                f"partial_fit — keep the original policy, or start a "
                f"fresh fit()")
        if self._acc is None:
            sketch_type = self.backend.split("-", 1)[1] \
                if self.backend.startswith("onepass-") else None
            if sketch_type is None:
                raise ValueError(
                    f"partial_fit needs a one-pass backend (streaming "
                    f"sketch state); backend is {self.backend!r}")
            from repro.stream.accumulate import SketchAccumulator
            k_backend, self._k_km = jax.random.split(_as_key(key))
            fwht_fn = self.backend_params.get("fwht_fn")
            pk = self._policy_kwargs(
                self._make_spec(n=0, p=int(X_chunk.shape[0])))
            if self.model_ is not None \
                    and self.model_.stream_counts is not None:
                self._acc = SketchAccumulator.from_model(self.model_,
                                                         fwht_fn=fwht_fn,
                                                         **pk)
            else:
                if capacity is None:
                    raise ValueError(
                        "partial_fit needs capacity=<total columns> on "
                        "the first call — the sketch test matrix is "
                        "sized up front (capacity=n reproduces fit; "
                        "larger keeps room to stream). Alternatively "
                        "load a model with streaming state to resume.")
                self._acc = SketchAccumulator(
                    k_backend, self._kernel_fn(), capacity, self.r,
                    oversampling=int(self.backend_params.get(
                        "oversampling", 10)),
                    block=self.block, sketch_type=sketch_type,
                    fwht_fn=fwht_fn,
                    truncate_basis=bool(self.backend_params.get(
                        "truncate_basis", False)),
                    **pk)
        self._acc.add(X_chunk)
        if reeig:
            self.reeig_now(kmeans_mode=kmeans_mode,
                           minibatch_size=minibatch_size,
                           minibatch_steps=minibatch_steps)
        return self

    def reeig_now(self, kmeans_mode: str = "full",
                  minibatch_size: int = 256,
                  minibatch_steps: int = 50) -> "KernelKMeans":
        """Re-eig the accumulated sketch and refresh model_/centroids.

        Runs `one_pass_core` on the effective sketch (staged tail
        included, applied on a copy — the canonical chunk-invariant
        state is untouched) and re-clusters the fresh embedding."""
        if self._acc is None:
            raise RuntimeError("no streaming state; call partial_fit()")
        eig = self._acc.eig()
        if kmeans_mode == "full":
            km = kmeans(self._k_km, eig.Y.T, self.k,
                        n_restarts=self.n_restarts, max_iter=self.max_iter)
            labels, centroids, objective = (km.labels, km.centroids,
                                            km.objective)
        elif kmeans_mode == "minibatch":
            from repro.stream.minibatch import minibatch_kmeans
            mb = minibatch_kmeans(self._k_km, eig.Y.T, self.k,
                                  minibatch_size, minibatch_steps)
            labels, centroids, objective = (mb.labels, mb.centroids,
                                            mb.objective)
        else:
            raise ValueError(f"unknown kmeans_mode {kmeans_mode!r}; "
                             f"have 'full' | 'minibatch'")
        X_all = self._acc.X_all
        spec = self._make_spec(n=self._acc.n_added, p=X_all.shape[0])
        self.model_ = self._package(spec, X_all, eig.U, eig.eigvals,
                                    centroids, self._acc.state_arrays())
        self.labels_ = labels
        self.embedding_ = eig.Y
        self.eigvals_ = eig.eigvals
        self.centroids_ = centroids
        self.inertia_ = float(objective)
        self.spec_ = spec
        self._extender = None
        return self

    @property
    def stream_progress(self) -> Dict:
        """Streaming fit counters: columns added/applied/pending,
        capacity, re-eigs run, and the last free approx-error estimate."""
        if self._acc is None:
            return {}
        return {"n_added": self._acc.n_added,
                "n_applied": self._acc.n_applied,
                "n_pending": self._acc.n_pending,
                "capacity": self._acc.capacity,
                "reeigs": self._acc.reeigs,
                "approx_err_estimate": self._acc.last_approx_err}

    def fit_predict(self, X: jnp.ndarray,
                    key: Union[None, int, jax.Array] = None) -> np.ndarray:
        return np.asarray(self.fit(X, key=key).labels_)

    # -- inference -------------------------------------------------------

    def _require_fit(self) -> FittedModel:
        if self.model_ is None:
            raise RuntimeError("KernelKMeans is not fitted; call fit() "
                               "or load()")
        return self.model_

    def extender(self, **kwargs) -> extend.Extender:
        """The serving extension engine over the fitted model (cached for
        the no-kwargs call so repeated predict()s reuse executables)."""
        model = self._require_fit()
        if kwargs:
            kwargs.setdefault("policy", self.policy)
            return extend.Extender(model, **kwargs)
        if self._extender is None:
            self._extender = extend.Extender(model, policy=self.policy)
        return self._extender

    def embed(self, X: jnp.ndarray) -> jnp.ndarray:
        """Out-of-sample extension of X (p, b) -> (r, b)."""
        return self.extender().embed(jnp.asarray(X, jnp.float32))

    def predict(self, X: jnp.ndarray) -> np.ndarray:
        """Assign X (p, b) to the fitted clusters -> labels (b,)."""
        labels, _ = self.extender().assign(jnp.asarray(X, jnp.float32))
        return np.asarray(labels)

    def transform(self, X: jnp.ndarray) -> jnp.ndarray:
        """sklearn-style alias of `embed` (column-major: (r, b))."""
        return self.embed(X)

    def score(self, X: Optional[jnp.ndarray] = None) -> float:
        """Negative sum of squared distances to the assigned centroids
        (higher is better, sklearn convention). X=None scores the
        training fit (the negative K-means inertia)."""
        if X is None:
            self._require_fit()
            if self.inertia_ is None:
                raise RuntimeError(
                    "training-side attributes (inertia_/labels_) are not "
                    "part of the artifact; this estimator was loaded, not "
                    "fitted — pass X to score against data")
            return -self.inertia_
        _, d2 = self.extender().assign(jnp.asarray(X, jnp.float32))
        return -float(jnp.sum(d2))

    # -- persistence -----------------------------------------------------

    def save(self, artifact_dir: str, dtype: str = "f32") -> str:
        """Persist the fitted model as a servable artifact directory."""
        return save_model(self._require_fit(), artifact_dir, dtype=dtype)

    @classmethod
    def from_model(cls, model: FittedModel) -> "KernelKMeans":
        """Rebuild an estimator around an existing FittedModel (training
        labels/embedding are not part of the artifact and stay unset)."""
        spec = model.spec
        est = cls(k=spec.k, r=spec.r, kernel=spec.kernel,
                  kernel_params=dict(spec.kernel_params),
                  backend=spec.backend,
                  backend_params=dict(spec.backend_params),
                  block=spec.block, n_restarts=spec.n_restarts,
                  max_iter=spec.max_iter)
        est.model_ = model
        est.eigvals_ = model.eigvals
        est.centroids_ = model.centroids
        est.spec_ = spec
        return est

    @classmethod
    def load(cls, artifact_dir: str) -> "KernelKMeans":
        """Load a saved artifact back into a predict/embed-ready
        estimator."""
        return cls.from_model(load_model(artifact_dir))

    def __repr__(self) -> str:
        fitted = "fitted" if self.model_ is not None else "unfitted"
        args = {"k": self.k, "r": self.r, "kernel": self.kernel,
                "backend": self.backend}
        if self.backend_params:
            args["backend_params"] = self.backend_params
        body = ", ".join(f"{k}={v!r}" for k, v in args.items())
        return f"KernelKMeans({body}) <{fitted}>"


def spec_to_estimator(spec: ClusteringSpec) -> KernelKMeans:
    """An unfitted estimator configured exactly as `spec` records — the
    refit path: `spec_to_estimator(old.spec).fit(X_new, key)`."""
    d = dataclasses.asdict(spec)
    d.pop("n", None)
    d.pop("p", None)
    return KernelKMeans(**{k: v for k, v in d.items()})
