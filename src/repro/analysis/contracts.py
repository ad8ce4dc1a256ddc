"""Kernel memory-contract verifier (rules C001-C003).

The kernel packages declare closed-form byte models (`memory_contract`
in each ops.py), which the benchmark's byte models copy
(bench/lib/costs.py). Nothing about a closed form keeps it honest, so
this pass derives the SAME quantities from the kernels' actual BlockSpecs
and fails on divergence:

* Every registered package's `op` is invoked (through its own public
  wrapper, on zeros built by its own `build`) under a monkeypatched
  `pallas_call` that records grid / BlockSpecs / shapes instead of
  running the kernel.
* HBM traffic: for each operand, walk every grid point through the
  spec's index_map and count DISTINCT block coordinates — a
  constant-index (VMEM-resident) operand crosses HBM once, a moving
  operand once per distinct block — then multiply by block bytes.
* VMEM residency: sum of per-operand block bytes, double-buffered (x2)
  for moving operands, single for resident ones, checked against the
  contract's budget at every registered parity case.

Derivation is per parity case, so a drifted tile size, a forgotten
padding change, or a new output that the declared model missed all
surface as C001 the moment they land.
"""
from __future__ import annotations

import dataclasses
import inspect
import itertools
import math
import os
from typing import Callable, List, Tuple

from repro.analysis.findings import Finding

# Default per-core VMEM ceiling (TPU v4/v5 class, see the Pallas guide);
# packages can declare a tighter budget in their KernelContract.
VMEM_BUDGET_BYTES = 16 * 1024 * 1024

# Derivation walks every grid point; registered parity shapes are tiny
# (tens of steps), so a huge grid means a derivation bug, not a kernel.
_MAX_GRID_POINTS = 1 << 16


@dataclasses.dataclass(frozen=True)
class OperandReport:
    """Derived traffic for one pallas_call operand."""
    name: str                    # "in0" / "out1" ...
    block_shape: Tuple[int, ...]
    block_bytes: int
    distinct_blocks: int
    resident: bool               # constant index map -> revisited block

    @property
    def hbm_bytes(self) -> int:
        return self.distinct_blocks * self.block_bytes

    @property
    def vmem_bytes(self) -> int:
        # Moving blocks are double-buffered by the Pallas pipeline;
        # resident blocks occupy one buffer for the whole sweep.
        return self.block_bytes * (1 if self.resident else 2)


@dataclasses.dataclass(frozen=True)
class CallReport:
    """Derived totals for one captured pallas_call."""
    grid: Tuple[int, ...]
    operands: Tuple[OperandReport, ...]

    @property
    def hbm_bytes(self) -> int:
        return sum(op.hbm_bytes for op in self.operands)

    @property
    def vmem_bytes(self) -> int:
        return sum(op.vmem_bytes for op in self.operands)


@dataclasses.dataclass(frozen=True)
class _Capture:
    grid: tuple
    in_specs: tuple
    out_specs: tuple
    arg_shapes: tuple            # ((shape, itemsize), ...) matching in_specs
    out_shapes: tuple            # ((shape, itemsize), ...) matching out_specs
    prefetch: tuple = ()         # scalar-prefetch operands, as passed


def _as_tuple(x) -> tuple:
    if x is None:
        return ()
    if isinstance(x, (tuple, list)):
        return tuple(x)
    return (x,)


def capture_pallas_calls(thunk: Callable[[], object]) -> List[_Capture]:
    """Run `thunk` with pallas_call swapped for a recorder.

    The recorder never executes the kernel body — it logs the call's
    grid/specs/shapes and returns zeros of out_shape, which is enough
    for the wrappers' pad/slice plumbing to trace through. A
    `grid_spec=` (e.g. pltpu.PrefetchScalarGridSpec) supplies grid and
    specs; its leading `num_scalar_prefetch` operands are recorded as
    `prefetch` (SMEM scalars, no HBM blocks) and not as array operands.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas

    caps: List[_Capture] = []
    real = pallas.pallas_call

    def fake(kernel, *, out_shape, grid=None, in_specs=None,
             out_specs=None, grid_spec=None, **unused_kw):
        outs = _as_tuple(out_shape)
        n_pre = 0
        if grid_spec is not None:
            grid, in_specs = grid_spec.grid, grid_spec.in_specs
            out_specs = grid_spec.out_specs
            n_pre = getattr(grid_spec, "num_scalar_prefetch", 0)

        def runner(*args):
            caps.append(_Capture(
                grid=_as_tuple(grid),
                in_specs=_as_tuple(in_specs),
                out_specs=_as_tuple(out_specs),
                arg_shapes=tuple((tuple(a.shape), jnp.dtype(a.dtype).itemsize)
                                 for a in args[n_pre:]),
                out_shapes=tuple((tuple(s.shape), jnp.dtype(s.dtype).itemsize)
                                 for s in outs),
                prefetch=tuple(args[:n_pre]),
            ))
            zeros = [jnp.zeros(s.shape, s.dtype) for s in outs]
            if isinstance(out_shape, (tuple, list)):
                return type(out_shape)(zeros)
            return zeros[0]

        return runner

    pallas.pallas_call = fake
    try:
        thunk()
    finally:
        pallas.pallas_call = real
    return caps


def derive_call(cap: _Capture) -> CallReport:
    """BlockSpec-derived HBM/VMEM totals for one captured call.

    Each index map is called with the grid point followed by the
    scalar-prefetch values, which must then be concrete (a wrapper
    passes a traced bound only when its caller asks for one, and the
    registered cases do not)."""
    grid = tuple(int(g) for g in cap.grid)
    n_points = math.prod(grid) if grid else 1
    if n_points > _MAX_GRID_POINTS:
        raise ValueError(f"grid {grid} has {n_points} points; refusing "
                         f"to enumerate (derivation bug?)")
    points = list(itertools.product(*(range(g) for g in grid))) or [()]

    operands: List[OperandReport] = []

    def add(name: str, spec, itemsize: int) -> None:
        block = tuple(int(d) for d in spec.block_shape)
        coords = {tuple(int(c) for c in
                        _as_tuple(spec.index_map(*pt, *cap.prefetch)))
                  for pt in points}
        block_bytes = math.prod(block) * itemsize
        operands.append(OperandReport(
            name=name, block_shape=block, block_bytes=block_bytes,
            distinct_blocks=len(coords), resident=len(coords) == 1))

    for i, (spec, (_, itemsize)) in enumerate(
            zip(cap.in_specs, cap.arg_shapes)):
        add(f"in{i}", spec, itemsize)
    for i, (spec, (_, itemsize)) in enumerate(
            zip(cap.out_specs, cap.out_shapes)):
        add(f"out{i}", spec, itemsize)
    return CallReport(grid=grid, operands=tuple(operands))


def capture_case(entry, case: dict) -> List[CallReport]:
    """Capture + derive every pallas_call `entry.op` issues for `case`.

    The jit cache is cleared around the capture: before, so a previous
    real run of the same shapes cannot swallow the trace; after, so the
    recorder's zeros-executable cannot leak into later real runs.
    """
    import jax

    args, op_kwargs, _ = entry.build(jax.random.PRNGKey(0), case)
    kwargs = dict(op_kwargs, interpret=True)
    clear = getattr(entry.op, "clear_cache", None)
    if clear:
        clear()
    try:
        caps = capture_pallas_calls(lambda: entry.op(*args, **kwargs))
    finally:
        if clear:
            clear()
    return [derive_call(c) for c in caps]


def _anchor(obj) -> Tuple[str, int]:
    """(repo-relative path, line) for a callable, for finding anchors."""
    try:
        path = inspect.getsourcefile(obj) or "<unknown>"
        line = obj.__code__.co_firstlineno
    except (TypeError, AttributeError):
        return "<unknown>", 1
    path = path.replace(os.sep, "/")
    marker = "/src/repro/"
    idx = path.find(marker)
    if idx >= 0:
        path = "src/repro/" + path[idx + len(marker):]
    return path, line


def verify_contracts() -> List[Finding]:
    """Cross-check every registered kernel package at every parity case."""
    import repro.kernels  # noqa: F401  (imports populate the registry)
    from repro.kernels.registry import get_contract, kernel_entries

    findings: List[Finding] = []
    for entry in kernel_entries():
        contract = get_contract(entry.name)
        path, line = _anchor(entry.op)
        if contract is None:
            findings.append(Finding(
                rule="C003", path=path, line=line, symbol=entry.name,
                message=f"registered kernel {entry.name!r} declares no "
                        f"memory contract (register_contract missing)"))
            continue
        for case in entry.cases:
            reports = capture_case(entry, case)
            declared = float(contract.declared(case)["hbm_bytes"])
            derived = float(sum(r.hbm_bytes for r in reports))
            if not reports:
                findings.append(Finding(
                    rule="C001", path=path, line=line, symbol=entry.name,
                    message=f"case {case}: op issued no pallas_call to "
                            f"derive a contract from"))
                continue
            if abs(derived - declared) > 0.5:
                findings.append(Finding(
                    rule="C001", path=path, line=line, symbol=entry.name,
                    message=f"case {case}: declared {declared:.0f} B but "
                            f"BlockSpecs imply {derived:.0f} B of HBM "
                            f"traffic"))
            for i, rep in enumerate(reports):
                if rep.vmem_bytes > contract.vmem_budget:
                    findings.append(Finding(
                        rule="C002", path=path, line=line,
                        symbol=entry.name,
                        message=f"case {case}: pallas_call #{i} holds "
                                f"{rep.vmem_bytes} B resident in VMEM "
                                f"(budget {contract.vmem_budget} B)"))
    return findings
