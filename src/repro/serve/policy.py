"""ComputePolicy: one frozen object for every compute-path knob.

Before this module, the Pallas/mesh knobs were scattered kwargs with
per-callsite naming drift: `Extender(fused=...)` meant the embed kernel,
`MicroBatcher(fused=...)` meant the assign kernel, `mesh=` appeared on
some front doors and not others, and the fit path had no knobs at all.
A `ComputePolicy` collapses all of them into one value-compared frozen
dataclass accepted uniformly by Extender, ShardedExtender, MicroBatcher,
AsyncBatcher, ModelRegistry (via the recorded front-end kwargs),
and — new with the sharded fit — SketchAccumulator /
KernelKMeans.fit / KernelKMeans.partial_fit.

Fields (all tri-state: None = auto, True/False = explicit):

    embed_fused   extend_embed Pallas stripe engine (serving embed).
    assign_fused  kmeans_assign Pallas argmin (serving assign).
    fit_fused     fit_sketch Pallas accumulate kernel (training).
    interpret     Pallas interpret-mode override, applied to whichever
                  of the three kernels resolves on.
    mesh          jax Mesh; not None routes BOTH serving (ShardedExtender)
                  and the one-pass fit (distributed/fit.py) through the
                  mesh-sharded path.
    mesh_axis     mesh axis name the data dimension shards over.

`resolve_pallas_path` (formerly serve/extend.py) lives here now; the
policy's `resolve_*` methods are thin wrappers over it, so the explicit
CPU-override contract is unchanged. Old per-callsite kwargs keep working
through `merge_legacy_kwargs` shims that emit a DeprecationWarning and
build the equivalent policy — behavior is bit-identical because the shim
feeds the exact same resolved values down the exact same code paths.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def resolve_pallas_path(fused: Optional[bool], interpret: Optional[bool],
                        what: str) -> Tuple[bool, bool]:
    """Resolve a (fused, interpret) request into a concrete path choice.

    Contract (the fix for the old silently-ignored CPU override):

      fused=None       Pallas off-CPU; on CPU only when interpret=True
                       explicitly opts in (how CI forces the Pallas path).
      fused=True, CPU  honoured — runs in interpret mode, warning unless
                       interpret=True was passed explicitly.
      fused=True, interpret=False, CPU   ValueError: Pallas cannot lower
                       natively on CPU; the settings conflict.
      fused=False, interpret set         ValueError: interpret only
                       applies to the Pallas path; the settings conflict.
    """
    cpu = jax.default_backend() == "cpu"
    if fused is False:
        if interpret is not None:
            raise ValueError(
                f"{what}: fused=False conflicts with interpret="
                f"{interpret} — the interpret flag only applies to the "
                f"Pallas path")
        return False, False
    if fused is None:
        fused = (not cpu) or interpret is True
        if not fused:
            return False, False
    if cpu:
        if interpret is False:
            raise ValueError(
                f"{what}: the Pallas path was requested with "
                f"interpret=False on the CPU backend, where Pallas "
                f"cannot lower natively — drop interpret=False or run "
                f"on an accelerator")
        if interpret is None:
            warnings.warn(
                f"{what}: Pallas path requested on the CPU backend; "
                f"running in interpret mode (pass interpret=True to "
                f"acknowledge, or fused=False for the jnp path)",
                stacklevel=3)
        return True, True
    return True, bool(interpret) if interpret is not None else False


def data_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """1-D "data" mesh over `devices` (default: every device) in Auto
    sharding mode, the mode the sharded fit and serving paths are
    written for (jax.make_mesh defaults to Explicit axes)."""
    devices = jax.devices() if devices is None else list(devices)
    return Mesh(np.array(devices), ("data",), axis_types=(AxisType.Auto,))


@dataclasses.dataclass(frozen=True)
class ComputePolicy:
    """Frozen compute-path selection, shared by fit and serve.

    Frozen + eq=True on purpose: ModelRegistry records the front-end
    kwargs per model row and replays/compares them by value equality on
    warm swaps, so a policy must compare by value (jax Mesh already
    does). Construct once, pass everywhere.
    """

    embed_fused: Optional[bool] = None
    assign_fused: Optional[bool] = None
    fit_fused: Optional[bool] = None
    interpret: Optional[bool] = None
    mesh: Any = None
    mesh_axis: str = "data"

    def __post_init__(self):
        if self.mesh is None:
            return
        if self.mesh_axis not in self.mesh.axis_names:
            raise ValueError(f"mesh has no axis {self.mesh_axis!r}; "
                             f"have {self.mesh.axis_names}")
        types = dict(zip(self.mesh.axis_names, self.mesh.axis_types))
        if types[self.mesh_axis] == AxisType.Explicit:
            raise ValueError(
                f"mesh axis {self.mesh_axis!r} is Explicit; the sharded "
                f"paths need Auto sharding — build the mesh with "
                f"data_mesh() or axis_types=(AxisType.Auto, ...)")

    # -- resolution (the old resolve_pallas_path call sites) -------------

    def resolve_embed(self, where: str = "fused extend_embed stripe"
                      ) -> Tuple[bool, bool]:
        return resolve_pallas_path(self.embed_fused, self.interpret, where)

    def resolve_assign(self, where: str = "Pallas kmeans_assign"
                       ) -> Tuple[bool, bool]:
        return resolve_pallas_path(self.assign_fused, self.interpret, where)

    def resolve_fit(self, where: str = "fused fit_sketch accumulate"
                    ) -> Tuple[bool, bool]:
        return resolve_pallas_path(self.fit_fused, self.interpret, where)

    def replace(self, **changes) -> "ComputePolicy":
        return dataclasses.replace(self, **changes)

    @property
    def sharded(self) -> bool:
        return self.mesh is not None

    @property
    def shards(self) -> int:
        """Device count along the data axis (1 when unsharded)."""
        if self.mesh is None:
            return 1
        return dict(self.mesh.shape)[self.mesh_axis]


def merge_legacy_kwargs(policy: Optional[ComputePolicy],
                        legacy: Dict[str, Any], where: str) -> ComputePolicy:
    """Fold deprecated per-callsite kwargs into a ComputePolicy.

    `legacy` maps ComputePolicy FIELD names (callers translate their
    local spelling first, e.g. MicroBatcher's `fused` -> `assign_fused`)
    to the values the caller received. A kwarg counts as "set" when it
    differs from the policy default (None; "data" for mesh_axis) — the
    defaults carry no information, so folding them is lossless and old
    call sites that never passed the kwargs stay warning-free.

    Rules: legacy kwargs set AND policy given -> ValueError (ambiguous);
    legacy kwargs set, no policy -> DeprecationWarning + equivalent
    policy; nothing set -> the given policy, or the default one.
    """
    defaults = {"mesh_axis": "data"}
    set_keys = sorted(k for k, v in legacy.items()
                      if v is not None and v != defaults.get(k))
    if not set_keys:
        return policy if policy is not None else ComputePolicy()
    if policy is not None:
        raise ValueError(
            f"{where}: both policy= and legacy kwarg(s) {set_keys} were "
            f"given — move the legacy values into the ComputePolicy")
    warnings.warn(
        f"{where}: kwarg(s) {set_keys} are deprecated; pass "
        f"policy=ComputePolicy(...) instead (same fields, same defaults, "
        f"bit-identical behavior)", DeprecationWarning, stacklevel=3)
    return ComputePolicy(**legacy)
