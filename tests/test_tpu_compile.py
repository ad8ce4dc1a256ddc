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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.extend_embed.ops import extend_embed_pallas
from repro.kernels.fit_sketch.ops import fit_sketch_pallas
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


def _fit_block_update(p, m, rp, b, n_pad):
    """One fit block of width b over m columns, SRHT padded to n_pad."""
    return (lambda X, W, rn, signs, rows, q: _fused_block_update(
                X, W, rn, signs, rows, q, b=b, n_pad=n_pad, kind="rbf",
                gamma=GAMMA, degree=2, interpret=False),
            [_f32(p, m), _f32(m, rp), _f32(m), _f32(n_pad),
             ((rp,), jnp.int32), ((), jnp.int32)])


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
    "fit_block_update": _fit_block_update(P, N, 20, 512, 1 << 17),
    "fit_sketch_border_covtype_full": _fit_sketch_border(CP, CN, CR),
    "fit_block_update_covtype_full_b512": _fit_block_update(
        CP, CN, CR, 512, 1 << 20),
    "fit_block_update_covtype_full_b404": _fit_block_update(
        CP, CN, CR, CN % 512, 1 << 20),
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
