"""Shape/dtype sweep: fused assignment Pallas kernel vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import assign_pallas
from repro.kernels.kmeans_assign.ref import assign_ref

pytestmark = pytest.mark.kernels    # CI kernel-parity job runs -m kernels


@pytest.mark.parametrize("n,r,k", [(50, 2, 2), (1000, 2, 7), (513, 16, 100),
                                   (2048, 128, 8), (31, 5, 3)])
def test_assign_matches_ref(n, r, k):
    k1, k2 = jax.random.split(jax.random.PRNGKey(n + r + k))
    Y = jax.random.normal(k1, (n, r), jnp.float32)
    C = jax.random.normal(k2, (k, r), jnp.float32)
    labels, d2 = assign_pallas(Y, C, interpret=True)
    labels_ref, d2_ref = assign_ref(Y, C)
    # Distances must match tightly; labels can differ only on exact ties.
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d2_ref),
                               rtol=1e-4, atol=1e-4)
    mism = np.asarray(labels) != np.asarray(labels_ref)
    assert mism.mean() < 0.01


def test_assign_padded_centroids_never_win():
    """k not a multiple of the pad: padded (zero) centroids are masked."""
    Y = jnp.ones((64, 4)) * 100.0   # far from origin
    C = jnp.ones((3, 4)) * 100.0    # 3 real centroids, 5 padded zeros
    labels, d2 = assign_pallas(Y, C, interpret=True)
    assert int(labels.max()) < 3
    np.testing.assert_allclose(np.asarray(d2), 0.0, atol=1e-5)


def test_assign_row_tiles():
    """Multi-tile grids (3, 2 and 1 steps) agree with the oracle; a tile
    that splits the rows must be a multiple of 1024, the 1-D output
    layout the TPU compiler demands."""
    Y = jax.random.normal(jax.random.PRNGKey(1), (2500, 9))
    C = jax.random.normal(jax.random.PRNGKey(2), (11, 9))
    want = assign_ref(Y, C)
    for rt in (1024, 2048, 4096):
        labels, d2 = assign_pallas(Y, C, row_tile=rt, interpret=True)
        np.testing.assert_allclose(np.asarray(d2), np.asarray(want[1]),
                                   rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError, match="multiple of 1024"):
        assign_pallas(Y, C, row_tile=256, interpret=True)
