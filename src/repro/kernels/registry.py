"""Kernel parity registry: every Pallas package's (op, ref, shapes).

Each kernel package's ops.py registers a KernelEntry at import time —
its public op, its pure-jnp oracle, the seeded parity-shape grid the
oracle must match it on, and a `build` callable turning one case dict
into concrete arguments. The kernel-parity CI job and
tests/test_kernel_registry.py iterate THIS registry instead of
hard-coding imports, so a new kernel package (e.g. fit_sketch) gets
parity coverage by registering itself — no test edits.

Importing `repro.kernels` populates the registry (its __init__ imports
every package's ops module); this module itself imports none of them, so
there is no cycle.
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional, Tuple

from jax.lax import Precision

# Contraction precision of every f32 matmul inside the Pallas kernels.
# Mosaic's default rounds f32 operands to bf16 for the MXU; on a v5e that
# put the fit_sketch sketch 1.8e-3 (relative eigenvalues) away from the
# f32 jnp path at n=70,000. The kernels state float32, so they contract
# at full f32 precision.
KERNEL_PRECISION = Precision.HIGHEST


class KernelEntry(NamedTuple):
    """One kernel package's parity contract.

    op:    public jit'd wrapper; must accept interpret= (the parity
           sweep forces interpret=True so it runs anywhere).
    ref:   pure-jnp oracle with the same positional signature.
    cases: tuple of case dicts, each one parity point of the shape grid.
    build: (key, case) -> (args, op_kwargs, ref_kwargs); args are passed
           positionally to both op and ref.
    rtol/atol: allclose tolerances for the default comparison.
    compare: optional (got, want, rtol, atol) override for ops whose
           outputs need more than leaf-wise allclose (e.g. argmin label
           ties in kmeans_assign).
    """
    name: str
    op: Callable
    ref: Callable
    cases: Tuple[Dict, ...]
    build: Callable
    rtol: float = 2e-3
    atol: float = 2e-3
    compare: Optional[Callable] = None


class KernelContract(NamedTuple):
    """One kernel package's declared memory-contract model.

    declared: (case) -> dict with at least "hbm_bytes": the closed-form
           byte model for one parity case (the benchmark's byte model,
           bench/lib/costs.py, is held equal to it by
           bench/tests/test_costs.py). `repro.analysis` cross-checks it
           against the HBM traffic derived from the kernel's actual
           BlockSpecs at every registered case, so the model cannot
           silently drift from the kernel (rule C001).
    vmem_budget: per-grid-step VMEM residency ceiling in bytes the
           kernel must stay under at every registered case (rule C002).
    """
    name: str
    declared: Callable
    vmem_budget: int = 16 * 1024 * 1024


_REGISTRY: Dict[str, KernelEntry] = {}
_CONTRACTS: Dict[str, KernelContract] = {}


def register_kernel(entry: KernelEntry) -> KernelEntry:
    """Register one kernel package (idempotent per name; re-registering
    a name replaces it, so module reloads stay harmless)."""
    if not entry.cases:
        raise ValueError(f"kernel {entry.name!r} registered with no "
                         f"parity cases")
    _REGISTRY[entry.name] = entry
    return entry


def get_kernel(name: str) -> KernelEntry:
    if name not in _REGISTRY:
        raise KeyError(f"unknown kernel {name!r}; registered: "
                       f"{registered_kernels()}")
    return _REGISTRY[name]


def registered_kernels() -> list:
    """Registered kernel names, sorted."""
    return sorted(_REGISTRY)


def kernel_entries() -> Tuple[KernelEntry, ...]:
    """All entries, name-sorted — what the parity sweep iterates."""
    return tuple(_REGISTRY[n] for n in registered_kernels())


def register_contract(contract: KernelContract) -> KernelContract:
    """Register one package's memory contract (same replace semantics
    as register_kernel)."""
    _CONTRACTS[contract.name] = contract
    return contract


def get_contract(name: str) -> Optional[KernelContract]:
    """The declared contract for `name`, or None — `repro.analysis`
    reports a missing contract as C003 rather than raising here."""
    return _CONTRACTS.get(name)
