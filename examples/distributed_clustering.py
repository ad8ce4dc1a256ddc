"""The paper's pipeline distributed over a mesh (8 simulated devices).

Data columns sharded, kernel stripes computed shard-locally, SRHT
preconditioning via the ppermute-butterfly distributed FWHT, Cholesky-QR,
distributed Lloyd. See DESIGN.md §5 / distributed/cluster.py.

Run: PYTHONPATH=src python examples/distributed_clustering.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import polynomial_kernel, clustering_accuracy
from repro.data import blob_ring
from repro.distributed.cluster import distributed_one_pass_kernel_kmeans
from repro.serve import data_mesh

mesh = data_mesh()
n = 4096                                   # power of two (pre-padded)
X, labels = blob_ring(jax.random.PRNGKey(0), n=n)
X = jax.device_put(X, NamedSharding(mesh, P(None, "data")))

res = distributed_one_pass_kernel_kmeans(
    jax.random.PRNGKey(1), polynomial_kernel(degree=2), X, k=2, r=2,
    mesh=mesh, oversampling=10, block=512)

acc = clustering_accuracy(labels, np.asarray(res.labels), 2)
print(f"devices={jax.device_count()} n={n} accuracy={acc:.3f} "
      f"eigvals={np.asarray(res.eigvals).round(1)}")
assert acc > 0.95
