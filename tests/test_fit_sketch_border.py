"""fit_sketch bounded to a fit block's border [0, q+b): the kernel visits
only the row tiles that hold it, and every output a caller keeps is bit
for bit what the full sweep over all m rows gives. The in-place entry a
fit runs (fit_sketch_inplace, through SketchAccumulator) gives bit for
bit what the functional kernel gave with the per-block O(m) masks and
updates around it."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.stream.accumulate as acc_mod
from repro.analysis.contracts import capture_pallas_calls, derive_call
from repro.api import KernelKMeans
from repro.core.kernels_fn import make_kernel
from repro.core.sketch import srht_rows_at
from repro.kernels.fit_sketch.fit_sketch import fit_sketch_call
from repro.kernels.fit_sketch.ops import (border_tiles, fit_sketch_pallas,
                                          memory_contract)
from repro.serve import ComputePolicy, VersionStore, load_model
from repro.stream.accumulate import SketchAccumulator

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


@functools.partial(jax.jit, static_argnames=("b", "n_pad", "kind",
                                             "gamma", "degree"))
def _reference_block(X, W, row_norms2, aux, rows, q, *, b, n_pad, kind,
                     gamma, degree):
    """One fit block as the functional kernel computes it: the Omega rows
    of all m columns built and masked past the border, the validity mask
    V, and the masked O(m) read-modify-write of W and the row norms
    around fit_sketch_pallas (the update the in-place kernel replaced)."""
    m = X.shape[1]
    gids = jnp.arange(m, dtype=jnp.int32)
    bids = q + jnp.arange(b, dtype=jnp.int32)
    valid = gids < q + b
    C = jax.lax.dynamic_slice_in_dim(X, q, b, axis=1)
    if rows is None:
        Omega = aux[:m]
        cross = jax.lax.dynamic_slice_in_dim(aux, q, b, axis=0)
    else:
        Omega = srht_rows_at(gids, aux[:m], rows, n_pad)
        cross = srht_rows_at(bids, jax.lax.dynamic_slice(aux, (q,), (b,)),
                             rows, n_pad)
    Omega = jnp.where(valid[:, None], Omega, 0.0)
    V = jnp.zeros((8, m), jnp.float32).at[0].set(valid.astype(jnp.float32))
    new_rows, delta, rn_rows, rn_cols = fit_sketch_pallas(
        X, Omega, C, cross, V, kind=kind, gamma=gamma, degree=degree,
        interpret=True, border=q + b)
    applied = gids < q
    Wm = jnp.where(applied[:, None], W[:m] + delta, W[:m])
    rnm = jnp.where(applied, row_norms2[:m] + rn_rows, row_norms2[:m])
    W = jax.lax.dynamic_update_slice(W, Wm, (0, 0))
    row_norms2 = jax.lax.dynamic_update_slice(row_norms2, rnm, (0,))
    W = jax.lax.dynamic_update_slice(W, new_rows, (q, 0))
    row_norms2 = jax.lax.dynamic_update_slice(row_norms2, rn_cols, (q,))
    return W, row_norms2


def _reference_pass(X, sketch, b, kind):
    """(W, row norms) after the whole blocks of a pass, and after its
    ragged tail too, through _reference_block."""
    m = X.shape[1]
    if hasattr(sketch, "signs"):
        aux, rows, n_pad = sketch.signs, sketch.rows, sketch.n_pad
    else:
        aux, rows, n_pad = sketch.omega, None, 0
    name, gamma, degree = _statics(kind)
    W = jnp.zeros((m, RP), jnp.float32)
    rn = jnp.zeros((m,), jnp.float32)
    states = []
    for q in range(0, m, b):
        if q + b > m:
            states.append((W, rn))
        W, rn = _reference_block(X, W, rn, aux, rows, jnp.int32(q),
                                 b=min(b, m - q), n_pad=n_pad, kind=name,
                                 gamma=gamma, degree=degree)
    states.append((W, rn))
    return [tuple(map(np.asarray, st)) for st in states]


def _statics(kind):
    kw = KINDS[kind]
    return kw["kind"], kw.get("gamma", 0.0), kw.get("degree", 2)


def _accumulator(m, sketch_type, kind, b):
    name, gamma, degree = _statics(kind)
    params = {k: v for k, v in KINDS[kind].items() if k != "kind"}
    return SketchAccumulator(
        jax.random.PRNGKey(5), make_kernel(name, **params), m, 2,
        oversampling=RP - 2, block=b, sketch_type=sketch_type,
        policy=ComputePolicy(fit_fused=True, interpret=True),
        kernel_statics=_statics(kind))


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("sketch_type", ["srht", "gaussian"])
@pytest.mark.parametrize("m", [512, 700])
def test_block_updates_over_a_fit_are_bit_identical(m, sketch_type, kind):
    """A whole pass through the in-place kernel, the Omega rows prepared
    once, gives W and the row norms bit for bit as the per-block
    functional update did: after the whole blocks and after the ragged
    tail (96-column blocks leave one at both m)."""
    X = jax.random.normal(jax.random.PRNGKey(4), (P, m), jnp.float32)
    acc = _accumulator(m, sketch_type, kind, 96).add(X)
    whole, tail = _reference_pass(X, acc.sketch, 96, kind)
    st = acc.state_arrays()
    W, rn, n_eff = acc._effective_state()
    assert n_eff == m
    for got, want in zip((st["stream_w"], st["stream_row_norms2"], W, rn),
                         (*whole, *tail)):
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("sketch_type", ["srht", "gaussian"])
def test_uneven_chunks_give_the_one_shot_fit(sketch_type):
    """add() in uneven chunks, some shorter than a block, one spanning
    several, gives exactly the one-shot state, before and after the
    tail; the sketch rows are prepared again for each pass that grows."""
    m, b = 700, 128
    X = jax.random.normal(jax.random.PRNGKey(6), (P, m), jnp.float32)
    one = _accumulator(m, sketch_type, "rbf", b).add(X)
    chunked = _accumulator(m, sketch_type, "rbf", b)
    for lo, hi in ((0, 50), (50, 301), (301, 302), (302, 700)):
        chunked.add(X[:, lo:hi])
    assert chunked.n_applied == one.n_applied == 640
    for a, c in ((one.state_arrays(), chunked.state_arrays()),
                 (one._effective_state()[:2], chunked._effective_state()[:2])
                 ):
        for got, want in zip(jax.tree_util.tree_leaves(c),
                             jax.tree_util.tree_leaves(a)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_published_models_state_survives_a_later_partial_fit(tmp_path):
    """The model's stream_w is never the buffer a later block update
    donates: it reads back, unchanged, from the model and from its
    published artifact after partial_fit has gone on, both on the
    estimator that made it and on one resumed from it."""
    n, b = 700, 128
    X = np.asarray(jax.random.normal(jax.random.PRNGKey(7), (P, n)),
                   np.float32)
    est = KernelKMeans(k=2, r=2, kernel="rbf", kernel_params={"gamma": 0.3},
                       block=b, policy=ComputePolicy(fit_fused=True,
                                                     interpret=True))
    est.partial_fit(X[:, :300], key=1, capacity=n)
    model = est.model_
    want = np.asarray(model.stream_w)
    store = VersionStore(str(tmp_path / "store"))
    version = store.publish(model)
    est.partial_fit(X[:, 300:520])
    resumed = KernelKMeans(k=2, r=2, kernel="rbf",
                           kernel_params={"gamma": 0.3}, block=b,
                           policy=ComputePolicy(fit_fused=True,
                                                interpret=True))
    resumed.model_ = model
    resumed.partial_fit(X[:, 300:520], key=2)
    np.testing.assert_array_equal(np.asarray(model.stream_w), want)
    np.testing.assert_array_equal(
        np.asarray(load_model(store.path(version)).stream_w), want)
    # Both went on from the same state with the same columns.
    np.testing.assert_array_equal(np.asarray(est.model_.stream_w),
                                  np.asarray(resumed.model_.stream_w))


def _prepares(monkeypatch):
    """The fit.prepare spans the accumulator opens, with their args."""
    opened = []
    real = acc_mod.span

    def recording(name, **args):
        if name == "fit.prepare":
            opened.append(args)
        return real(name, **args)
    monkeypatch.setattr(acc_mod, "span", recording)
    return opened


def test_the_sketch_rows_are_prepared_once_per_one_shot_fit(monkeypatch):
    opened = _prepares(monkeypatch)
    X = jax.random.normal(jax.random.PRNGKey(8), (P, 700), jnp.float32)
    acc = _accumulator(700, "srht", "rbf", 128).add(X)
    acc.eig()                               # the ragged tail, same pass
    assert opened == [{"m": 700, "m_pad": 768}]


def test_the_sketch_rows_are_prepared_once_per_growing_add(monkeypatch):
    opened = _prepares(monkeypatch)
    X = jax.random.normal(jax.random.PRNGKey(9), (P, 700), jnp.float32)
    acc = _accumulator(700, "gaussian", "rbf", 128)
    for lo, hi in ((0, 200), (200, 520), (520, 700)):
        acc.add(X[:, lo:hi])
    acc.eig()
    assert opened == [{"m": 200, "m_pad": 256}, {"m": 520, "m_pad": 768},
                      {"m": 700, "m_pad": 768}]


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
