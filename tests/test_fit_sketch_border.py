"""fit_sketch bounded to a fit block's border [0, q+b): the kernel visits
only the row tiles that hold it, and every output a caller keeps is bit
for bit what the full sweep over all m rows gives."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.fit_sketch.ops as fit_ops
from repro.analysis.contracts import capture_pallas_calls, derive_call
from repro.core.sketch import make_gaussian, make_srht
from repro.kernels.fit_sketch.fit_sketch import fit_sketch_call
from repro.kernels.fit_sketch.ops import (border_tiles, fit_sketch_pallas,
                                          memory_contract)
from repro.stream.accumulate import _fused_block_update

pytestmark = pytest.mark.kernels    # CI kernel-parity job runs -m kernels

P, RP = 5, 12
KINDS = {"rbf": {"kind": "rbf", "gamma": 0.3},
         "polynomial": {"kind": "polynomial", "gamma": 1.0, "degree": 3},
         "linear": {"kind": "linear"}}


def _block_inputs(m, q, b, seed=0):
    """A fit block's kernel operands as _fused_block_update builds them:
    Omega rows and the validity mask zero from row q+b on."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    X = jax.random.normal(k1, (P, m), jnp.float32)
    valid = jnp.arange(m) < q + b
    Omega = jnp.where(valid[:, None],
                      jax.random.normal(k2, (m, RP), jnp.float32), 0.0)
    C = X[:, q:q + b]
    cross = jax.random.normal(k3, (b, RP), jnp.float32)
    V = jnp.zeros((8, m), jnp.float32).at[0].set(valid.astype(jnp.float32))
    return X, Omega, C, cross, V


# (m, q, b): m a multiple of the 256-row tile (512) and not one (700);
# the first block, middle blocks, the last full block, a ragged tail.
BLOCKS = [(512, 0, 128), (512, 128, 128), (512, 384, 128),
          (700, 0, 128), (700, 128, 100), (700, 256, 128),
          (700, 512, 128), (700, 640, 60)]


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("m,q,b", BLOCKS)
def test_border_call_matches_full_sweep(m, q, b, kind):
    args = _block_inputs(m, q, b)
    kw = dict(KINDS[kind], interpret=True)
    full = fit_sketch_pallas(*args, **kw)
    bounded = fit_sketch_pallas(*args, border=jnp.int32(q + b), **kw)
    new_rows, delta, rn_rows, rn_cols = map(np.asarray, bounded)
    want_new, want_delta, want_rnr, want_rnc = map(np.asarray, full)
    np.testing.assert_array_equal(new_rows, want_new)
    np.testing.assert_array_equal(rn_cols, want_rnc)
    # The caller keeps delta and rn_rows below q; rows up to the border
    # are computed too.
    np.testing.assert_array_equal(delta[:q + b], want_delta[:q + b])
    np.testing.assert_array_equal(rn_rows[:q + b], want_rnr[:q + b])


def _fit(X, sketch, b, monkeypatch=None):
    """Every block of a one-pass fit of X through _fused_block_update,
    the ragged tail last, as SketchAccumulator applies them."""
    m = X.shape[1]
    if hasattr(sketch, "signs"):
        aux, rows, n_pad = sketch.signs, sketch.rows, sketch.n_pad
    else:
        aux, rows, n_pad = sketch.omega, None, 0
    W = jnp.zeros((m, RP), jnp.float32)
    rn = jnp.zeros((m,), jnp.float32)
    for q in range(0, m, b):
        W, rn = _fused_block_update(
            X, W, rn, aux, rows, jnp.int32(q), b=min(b, m - q),
            n_pad=n_pad, kind="rbf", gamma=0.3, degree=2, interpret=True)
    return np.asarray(W), np.asarray(rn)


@pytest.mark.parametrize("sketch_type", ["srht", "gaussian"])
@pytest.mark.parametrize("m", [512, 700])
def test_block_updates_over_a_fit_are_bit_identical(m, sketch_type,
                                                    monkeypatch):
    X = jax.random.normal(jax.random.PRNGKey(4), (P, m), jnp.float32)
    make = make_srht if sketch_type == "srht" else make_gaussian
    sketch = make(jax.random.PRNGKey(5), m, RP)
    bounded = _fit(X, sketch, 128)
    orig = fit_ops.fit_sketch_pallas

    def full_sweep(*a, border=None, **k):
        return orig(*a, **k)
    monkeypatch.setattr(fit_ops, "fit_sketch_pallas", full_sweep)
    _fused_block_update.clear_cache()
    try:
        full = _fit(X, sketch, 128)
    finally:
        _fused_block_update.clear_cache()
    for got, want in zip(bounded, full):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("nt", [1, 2, 3])
def test_a_bounded_call_moves_only_its_tiles(nt):
    m, b, rt = 768, 128, 256
    X, Omega, C, cross, V = _block_inputs(m, 0, b)
    Op = jnp.pad(Omega, ((0, 0), (0, 128 - RP)))
    Ocr = jnp.pad(cross, ((0, 0), (0, 128 - RP)))
    cap, = capture_pallas_calls(lambda: fit_sketch_call(
        np.full((1,), nt, np.int32), X, Op, C, Ocr, V, "rbf", 0.3, 2, b,
        rt, True))
    report = derive_call(cap)
    assert report.grid == (m // rt,)
    moved = {o.name: o.distinct_blocks for o in report.operands}
    # X, Omega, V, delta, rn_row stream nt tiles; C, Ocross and the two
    # accumulators stay resident.
    assert moved == {"in0": nt, "in1": nt, "in2": 1, "in3": 1, "in4": nt,
                     "out0": 1, "out1": nt, "out2": nt, "out3": 1}
    if nt == m // rt:
        assert report.hbm_bytes == memory_contract(P, m, b, RP)["hbm_bytes"]


@pytest.mark.parametrize("m,border,want", [
    (512, 1, (1, 2)), (512, 256, (1, 2)), (512, 257, (2, 2)),
    (700, 640, (3, 3)), (700, 700, (3, 3)), (100, 12, (1, 1)),
    (70_000, 512, (2, 274)), (70_000, 70_000, (274, 274))])
def test_border_tiles(m, border, want):
    assert border_tiles(m, border) == want


@pytest.mark.parametrize("n,visited,total", [(70_000, 18_906, 37_538),
                                             (131_072, 65_792, 131_072)])
def test_a_fits_tile_share_at_the_cells_shapes(n, visited, total):
    """Over a whole fit in 512-column blocks (the ragged tail included)
    the kernel visits about half the tiles of a full sweep."""
    counts = [border_tiles(n, min(q + 512, n)) for q in range(0, n, 512)]
    assert sum(v for v, _ in counts) == visited
    assert sum(t for _, t in counts) == total
