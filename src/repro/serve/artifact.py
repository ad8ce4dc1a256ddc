"""FittedModel: the deployable artifact of a kernel-clustering fit.

A fit — whatever approximation backend produced it (see
`repro.api.backends`) — collapses to a small set of arrays that fully
determine the serving-time behaviour:

    X_train    (p, n)     training data
    U          (n_ref, r) orthonormal eigenvector basis of the
                          approximation's extension operator: rows index
                          the training points (one-pass / exact) or the
                          Nystrom landmarks
    eigvals    (r,)       matching eigenvalues (descending, >= 0)
    centroids  (k, r)     K-means centroids in the linearized space
    sketch_*              one-pass state: SRHT signs/rows or the dense
                          Gaussian Omega — not needed to serve, but
                          persisted so the fit is reproducible from the
                          artifact alone
    landmarks  (p, m)     Nystrom backend: the sampled reference points;
    landmark_idx (m,)     the extension evaluates kappa(landmarks, x)
                          against them (O(m * block) per stripe instead
                          of O(n * block)) — `extension_ref` picks the
                          right reference set per backend

plus a static `ClusteringSpec` (kernel name/params, dimensions, backend).
`ModelSpec` is a legacy alias for `ClusteringSpec` — the spec is now the
single frozen config shared by the estimator API (`repro.api.KernelKMeans`)
and the artifact.

On-disk artifact format (built on repro.distributed.checkpoint):

    <dir>/spec.json        ClusteringSpec (static metadata)
    <dir>/leaves.json      explicit leaf names of the array state, in
                           checkpoint leaf order (sorted dict keys), plus
                           the quantization map when saved with
                           dtype="bf16" ({"quantized": {leaf: "bf16"}})
    <dir>/step_0/          atomic checkpoint of the array state
        manifest.json      flat-dict paths, shapes, dtypes
        leaf_<i>.npy       one file per array

save/load reuse the checkpoint layer's atomic-rename commit, so a reader
never observes a half-written artifact, and `read_manifest` rebuilds the
restore skeleton without guessing shapes. `save_model(..., dtype="bf16")`
halves the float payload by storing bfloat16 bit patterns
(distributed/compression.py codec); load transparently restores float32.
Versioned deployments layer `serve/versions.py` on top of this format
(one artifact dir per v_<N>).
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import re
import warnings
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.kernels_fn import KernelFn, make_kernel
from repro.distributed import compression
from repro.distributed import checkpoint as ckpt
from repro.spans import span


@dataclasses.dataclass(frozen=True)
class ClusteringSpec:
    """The single frozen config of a kernel-clustering fit.

    Drives `repro.api.KernelKMeans` and is persisted verbatim in the
    artifact (spec.json), so a fit is reproducible from its spec + key.
    `backend` names a registered approximation backend
    (repro.api.backends: onepass-srht | onepass-gaussian | nystrom |
    exact); `backend_params` carries its knobs (oversampling for
    one-pass, m for Nystrom). n/p are bound at fit time from the data.

    Subsumes the pre-estimator-API `ModelSpec` (which hard-coded the
    one-pass backend as oversampling/sketch_type fields); `from_json`
    still reads those legacy artifacts.
    """
    kernel: str = "polynomial"          # registry name (core/kernels_fn)
    kernel_params: Dict = dataclasses.field(default_factory=dict)
    k: int = 2                          # clusters
    r: int = 2                          # target rank (= serving embed dim)
    backend: str = "onepass-srht"       # approximation backend
    backend_params: Dict = dataclasses.field(default_factory=dict)
    block: int = 512                    # streaming stripe width
    n_restarts: int = 10                # K-means restarts
    max_iter: int = 20                  # K-means Lloyd iterations
    n: Optional[int] = None             # training points (bound at fit)
    p: Optional[int] = None             # input dimension (bound at fit)

    # -- legacy views (pre-backend ModelSpec fields) ---------------------

    @property
    def sketch_type(self) -> Optional[str]:
        """'srht' | 'gaussian' for one-pass backends, else None."""
        if self.backend.startswith("onepass-"):
            return self.backend.split("-", 1)[1]
        return None

    @property
    def oversampling(self) -> int:
        return int(self.backend_params.get("oversampling", 10))

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=1, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "ClusteringSpec":
        d = json.loads(text)
        # Legacy ModelSpec schema: oversampling/sketch_type at top level,
        # no backend fields, no K-means params.
        if "backend" not in d:
            d["backend"] = f"onepass-{d.pop('sketch_type', 'srht')}"
            d["backend_params"] = {"oversampling": d.pop("oversampling", 10)}
        d.pop("sketch_type", None)
        return cls(**d)


# Legacy alias: every pre-estimator-API call site (and pickle/json of
# the old name) keeps working.
ModelSpec = ClusteringSpec


class FittedModel(NamedTuple):
    """Deployable fit artifact; see module docstring for the field model."""
    spec: ClusteringSpec
    X_train: jnp.ndarray               # (p, n)
    U: jnp.ndarray                     # (n_ref, r)
    eigvals: jnp.ndarray               # (r,)
    centroids: jnp.ndarray             # (k, r)
    sketch_signs: Optional[jnp.ndarray] = None   # (n_pad,)  srht only
    sketch_rows: Optional[jnp.ndarray] = None    # (r',)     srht only
    sketch_omega: Optional[jnp.ndarray] = None   # (n, r')   gaussian only
    landmarks: Optional[jnp.ndarray] = None      # (p, m)    nystrom only
    landmark_idx: Optional[jnp.ndarray] = None   # (m,)      nystrom only
    # Streaming accumulation state (repro.stream.accumulate): the applied
    # sketch slab, streamed row norms of K, and [n_applied, capacity] —
    # what partial_fit needs to resume from a published artifact. Columns
    # of X_train past n_applied are the staged (pending) tail.
    stream_w: Optional[jnp.ndarray] = None           # (capacity, r')
    stream_row_norms2: Optional[jnp.ndarray] = None  # (capacity,)
    stream_counts: Optional[jnp.ndarray] = None      # (2,) int32

    @property
    def extension_ref(self) -> jnp.ndarray:
        """Reference points the out-of-sample extension evaluates the
        kernel against: the Nystrom landmarks when present, else the
        full training set. Shape (p, n_ref)."""
        return self.landmarks if self.landmarks is not None else self.X_train

    @property
    def n_ref(self) -> int:
        """Columns of `extension_ref` — the per-stripe kernel height the
        serving path pays (m for Nystrom, n otherwise)."""
        return int(self.extension_ref.shape[1])

    @property
    def Y(self) -> jnp.ndarray:
        """Fitted linearization Sigma^{1/2} U^T in R^{r x n} (recomputed).

        Only defined when U spans the training points (one-pass / exact
        backends). A landmark-based (Nystrom) fit does not persist its
        training linearization — embed the training data through the
        extension instead (exact on training points by construction).
        """
        if self.landmarks is not None:
            raise AttributeError(
                f"backend {self.spec.backend!r} is landmark-based: U spans "
                f"the {self.n_ref} landmarks, not the training set — use "
                f"serve.extend.embed(model, model.X_train) for the "
                f"training linearization")
        return jnp.sqrt(self.eigvals)[:, None] * self.U.T

    def kernel_fn(self) -> KernelFn:
        return _cached_kernel(self.spec.kernel,
                              tuple(sorted(self.spec.kernel_params.items())))


# gram_stripe jit-caches on the kernel *callable's identity*, so serving must
# hand it the same callable every call — memoize construction per spec.
_KERNEL_CACHE: Dict[tuple, KernelFn] = {}


def _cached_kernel(name: str, params: tuple) -> KernelFn:
    key = (name, params)
    if key not in _KERNEL_CACHE:
        _KERNEL_CACHE[key] = make_kernel(name, **dict(params))
    return _KERNEL_CACHE[key]


def fit_model(key: jax.Array, X: jnp.ndarray, k: int, r: int,
              kernel: str = "polynomial",
              kernel_params: Optional[Dict] = None,
              oversampling: int = 10, block: int = 512,
              sketch_type: str = "srht",
              n_restarts: int = 10, max_iter: int = 20) -> FittedModel:
    """DEPRECATED shim — use `repro.api.KernelKMeans`.

    Delegates to the estimator front door with the matching one-pass
    backend; same key split and sub-calls as the historical function, so
    the returned FittedModel is bit-identical.
    """
    warnings.warn(
        "fit_model is deprecated; use repro.api.KernelKMeans(k=..., r=..., "
        "backend='onepass-srht', ...).fit(X, key).model_",
        DeprecationWarning, stacklevel=2)
    from repro.api import KernelKMeans   # lazy: api builds on serve
    est = KernelKMeans(k=k, r=r, kernel=kernel, kernel_params=kernel_params,
                       backend=f"onepass-{sketch_type}",
                       backend_params={"oversampling": oversampling},
                       block=block, n_restarts=n_restarts, max_iter=max_iter)
    return est.fit(X, key=key).model_


# ---------------------------------------------------------------------------
# save / load on top of repro.distributed.checkpoint
# ---------------------------------------------------------------------------

_OPTIONAL_LEAVES = ("sketch_signs", "sketch_rows", "sketch_omega",
                    "landmarks", "landmark_idx",
                    "stream_w", "stream_row_norms2", "stream_counts")


def _array_state(model: FittedModel) -> Dict[str, jnp.ndarray]:
    state = {"X_train": model.X_train, "U": model.U,
             "eigvals": model.eigvals, "centroids": model.centroids}
    for name in _OPTIONAL_LEAVES:
        val = getattr(model, name)
        if val is not None:
            state[name] = val
    return state


def save_model(model: FittedModel, artifact_dir: str,
               dtype: str = "f32") -> str:
    """Persist atomically; returns the artifact directory.

    dtype="bf16" stores every floating leaf as its bfloat16 bit pattern
    (half the bytes; ~3 decimal digits of mantissa — assignment-grade,
    see tests/test_serve.py) via the distributed/compression.py codec;
    dtype="int8" stores absmax-scaled int8 with one scale per leaf in
    leaves.json (a quarter of the bytes — what keeps the retrain loop's
    repeated VersionStore publishes cheap). Integer leaves and the spec
    are untouched and load_model transparently restores float32 arrays.
    """
    base = pathlib.Path(artifact_dir)
    base.mkdir(parents=True, exist_ok=True)
    state = _array_state(model)
    quantized: Dict[str, str] = {}
    if dtype not in ("f32", "float32"):
        state, quantized = compression.quantize_state(state, dtype)
    ckpt.save_checkpoint(str(base), step=0, state=state, blocking=True)
    # Explicit leaf names, in checkpoint leaf order (jax flattens a dict
    # in sorted-key order) — load_model must not have to reverse-engineer
    # names out of jax.tree_util.keystr formatting.
    leaves = json.dumps({"names": sorted(state), "quantized": quantized})
    spec = model.spec.to_json()
    with span("store.write", bytes=len(leaves) + len(spec)):
        (base / "leaves.json").write_text(leaves)
        (base / "spec.json").write_text(spec)
    return str(base)


# Pre-leaves.json artifacts only carry keystr-formatted paths like
# "['X_train']"; match the quoted dict key rather than strip()ing
# characters off both ends (which also eats legitimate quote/bracket
# characters inside a name).
_KEYSTR_RE = re.compile(r"\['([^\]]+)'\]")


def _leaf_names(base: pathlib.Path, manifest: Dict) -> tuple:
    """(leaf names, quantized map) of the artifact's flat array dict.

    Names come from leaves.json when present (in leaf order); legacy
    artifacts (written before names were persisted) fall back to parsing
    the manifest's keystr paths. The quantized map records which leaves
    were stored as bf16 bit patterns (empty for f32 artifacts)."""
    names_file = base / "leaves.json"
    quantized: Dict[str, str] = {}
    if names_file.exists():
        meta = json.loads(names_file.read_text())
        names: List[str] = meta["names"]
        quantized = meta.get("quantized", {})
    else:
        names = []
        for path in manifest["paths"]:
            m = _KEYSTR_RE.fullmatch(path)
            names.append(m.group(1) if m else path)
    missing = {"X_train", "U", "eigvals", "centroids"} - set(names)
    if missing:
        raise ValueError(f"artifact at {base} lacks required leaves "
                         f"{sorted(missing)}; found {names}")
    return names, quantized


def load_model(artifact_dir: str) -> FittedModel:
    base = pathlib.Path(artifact_dir)
    spec = ClusteringSpec.from_json((base / "spec.json").read_text())
    manifest = ckpt.read_manifest(str(base), step=0)
    names, quantized = _leaf_names(base, manifest)
    state_like = {}
    for name, shape, dtype in zip(names, manifest["shapes"],
                                  manifest["dtypes"]):
        state_like[name] = jnp.zeros(shape, dtype=dtype)
    state, _ = ckpt.restore_checkpoint(str(base), state_like, step=0)
    if quantized:
        state = compression.dequantize_state(state, quantized)
    return FittedModel(spec=spec, X_train=state["X_train"], U=state["U"],
                       eigvals=state["eigvals"],
                       centroids=state["centroids"],
                       sketch_signs=state.get("sketch_signs"),
                       sketch_rows=state.get("sketch_rows"),
                       sketch_omega=state.get("sketch_omega"),
                       landmarks=state.get("landmarks"),
                       landmark_idx=state.get("landmark_idx"),
                       stream_w=state.get("stream_w"),
                       stream_row_norms2=state.get("stream_row_norms2"),
                       stream_counts=state.get("stream_counts"))
