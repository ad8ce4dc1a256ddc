"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed with JAX and compiles
for a topology that is described, not attached. Each case lowers a
kernel at the widths of the MNIST-scale deployment (70,000 x 784, RBF)
or, for the fit path, of Covertype at its published n (581,012 x 54,
r' = 17, the SRHT padded to 2^20, full blocks of 512 and a tail of 404)
and asserts that Mosaic accepted it: interpret mode cannot see a block
that does not match the device layout, or a kernel that asks for more
than the 16 MiB of scoped VMEM. Nothing runs, so this says nothing about
results or times.

The topology is described inside a module-scoped fixture, never while
the module is imported: one process at a time may load the TPU library,
and every pytest worker imports every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.extend_embed.ops import extend_embed_pallas
from repro.kernels.fit_sketch.ops import fit_sketch_pallas, padded_shapes
from repro.kernels.fwht.ops import fwht_pallas
from repro.kernels.gram.ops import gram_stripe_pallas
from repro.kernels.kmeans_assign.ops import assign_pallas
from repro.stream.accumulate import _fused_block_update

N, P = 70_000, 784           # MNIST's shape
GAMMA = 1e-3
CN, CP, CR = 581_012, 54, 17  # Covertype at its published n; r' = 7 + 10


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _f32(*shape):
    return (shape, jnp.float32)


def _fit_sketch_border(p, m, rp, b=512):
    """fit_sketch bounded to a traced border, over m rows of p features."""
    return (lambda X, O, C, Oc, V, border: fit_sketch_pallas(
                X, O, C, Oc, V, kind="rbf", gamma=GAMMA, interpret=False,
                border=border),
            [_f32(p, m), _f32(m, rp), _f32(p, b), _f32(b, rp), _f32(8, m),
             ((), jnp.int32)])


def _block_shapes(p, m, rp):
    """(X, W, row norms, prepared Omega rows) of a pass over m columns,
    the state sized to capacity m, in the in-place kernel's layout."""
    _, m_pad, _, rp_pad = padded_shapes(m, 1, rp)
    return [_f32(p, m), _f32(m_pad, rp_pad), _f32(8, m_pad),
            _f32(m_pad, rp_pad)]


def _fit_block_update(p, m, rp, b):
    """One fit block of width b over m columns through the in-place
    kernel, the sketch rows prepared for the pass."""
    return (lambda X, W, rn, rows_k, q: _fused_block_update(
                X, W, rn, rows_k, None, q, b=b, kind="rbf", gamma=GAMMA,
                degree=2, interpret=False),
            [*_block_shapes(p, m, rp), ((), jnp.int32)])


# name -> (function of arrays, argument (shape, dtype)s)
CASES = {
    "kmeans_assign_n64": (
        lambda Y, C: assign_pallas(Y, C, interpret=False),
        [_f32(64, 16), _f32(10, 16)]),
    "kmeans_assign_n65536": (
        lambda Y, C: assign_pallas(Y, C, interpret=False),
        [_f32(65_536, 16), _f32(10, 16)]),
    "extend_embed": (
        lambda X, Pr, Xb: extend_embed_pallas(X, Pr, Xb, kind="rbf",
                                              gamma=GAMMA, interpret=False),
        [_f32(P, N), _f32(16, N), _f32(P, 512)]),
    "fit_sketch": (
        lambda X, O, C, Oc, V: fit_sketch_pallas(
            X, O, C, Oc, V, kind="rbf", gamma=GAMMA, interpret=False),
        [_f32(P, N), _f32(N, 20), _f32(P, 512), _f32(512, 20),
         _f32(8, N)]),
    "fit_sketch_border": _fit_sketch_border(P, N, 20),
    "fit_block_update": _fit_block_update(P, N, 20, 512),
    "fit_sketch_border_covtype_full": _fit_sketch_border(CP, CN, CR),
    "fit_block_update_covtype_full_b512": _fit_block_update(
        CP, CN, CR, 512),
    "fit_block_update_covtype_full_b404": _fit_block_update(
        CP, CN, CR, CN % 512),
    "gram": (
        lambda X, Xb: gram_stripe_pallas(X, Xb, kind="rbf", gamma=GAMMA,
                                         interpret=False),
        [_f32(P, N), _f32(P, 512)]),
    "fwht_4096x128": (
        lambda x: fwht_pallas(x, interpret=False), [_f32(4096, 128)]),
    "fwht_2e17x64": (
        lambda x: fwht_pallas(x, interpret=False), [_f32(1 << 17, 64)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, args = CASES[name]
    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
              for shape, dtype in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- the fit's per-block update: nothing O(m) outside the kernel ----------

# Opcodes that may hold an axis of >= m rows: the kernel itself, the
# state it updates in place, and views of it.
_IN_PLACE = {"parameter", "get-tuple-element", "tuple", "bitcast", "call",
             "dynamic-update-slice"}


def _computations(hlo):
    """{computation name: [(instruction name, result dims, opcode, line)]}
    of an HLO module's text."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) (?:\(.*\) -> .* )?\{$",
                        line)
        if head:
            cur = comps.setdefault(head.group(1), [])
            continue
        if cur is None or " = " not in line:
            continue
        name, rhs = line.strip().split(" = ", 1)
        op = re.search(r"(?<![\w])([a-z][a-z0-9\-]*)\(", rhs)
        dims = [int(d) for g in re.findall(r"\[([\d,]+)\]",
                                           rhs[:op.start()])
                for d in g.split(",")]
        cur.append((name.replace("ROOT ", "").lstrip("%"), dims,
                    op.group(1), line))
    return comps


def _o_m_outside_kernel(hlo, m):
    """Instructions with a result axis of >= m rows that are neither the
    fit_sketch kernel, an in-place update of the state, nor a view."""
    comps = _computations(hlo)

    def root(comp):
        return next(i for i in reversed(comps[comp]) if "ROOT" in i[3])

    bad = []
    for insts in comps.values():
        for name, dims, op, line in insts:
            if max(dims, default=0) < m or op in _IN_PLACE:
                continue
            if op == "custom-call" and "tpu_custom_call" in line:
                continue
            called = re.search(r"calls=%?([\w.\-]+)", line)
            if (op == "fusion" and called
                    and root(called.group(1))[2] == "dynamic-update-slice"):
                continue
            bad.append(f"{name}: {op} {dims}")
    return bad


def _block_update_on(one_chip, p, m, rp, b):
    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
              for shape, dtype in [*_block_shapes(p, m, rp),
                                   ((), jnp.int32)]]
    X, W, rn, rows_k, q = shapes
    return _fused_block_update.lower(X, W, rn, rows_k, None, q, b=b,
                                     kind="rbf", gamma=GAMMA, degree=2,
                                     interpret=False)


def test_a_block_update_touches_no_O_m_rows_outside_the_kernel(
        one_chip, no_compile_cache):
    """The per-block program at m = 700, b = 128, as lowered for a v5e:
    every array of 700 or more rows is the kernel's, the state updated in
    place (the O(b) new rows written at q) or a view; the state is
    donated and aliased to the outputs."""
    lowered = _block_update_on(one_chip, CP, 700, CR, 128)
    hlo = lowered.as_text(dialect="hlo")
    assert _o_m_outside_kernel(hlo, 700) == []
    assert ("input_output_alias={ {0}: (1, {}, may-alias), "
            "{1}: (2, {}, may-alias) }") in hlo
    assert "output_to_operand_aliasing={{1}: (5, {}), {2}: (6, {})}" in hlo


def test_the_compiled_block_update_at_covtype_full_adds_no_copy(
        one_chip, no_compile_cache):
    """Compiled for a v5e at the published Covertype shape (581,012 rows,
    b = 512), XLA adds no copy, pad or transpose of the O(m) arrays
    around the kernel, and the donated state stays aliased."""
    compiled = _block_update_on(one_chip, CP, CN, CR, 512).compile()
    hlo = compiled.as_text()
    assert _o_m_outside_kernel(hlo, CN) == []
    assert ("input_output_alias={ {0}: (1, {}, may-alias), "
            "{1}: (2, {}, may-alias) }") in hlo
    assert re.search(r"%fit_sketch_inplace[\w.]* = .* custom-call\(", hlo)
