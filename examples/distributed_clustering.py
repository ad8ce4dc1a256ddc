"""The paper's pipeline on a mesh (8 simulated devices).

The estimator's one-pass fit with `ComputePolicy(mesh=...)`: each device
owns an n/d row slab of the sketch and builds its kernel stripes
locally, the SRHT's FWHT runs as a local transform plus the
ppermute-butterfly stages, and each block costs one psum of the r' x b
sampled rows (distributed/fit.py, ShardedFitEngine). The eig and
K-means run on the gathered (n, r') sketch.

Run: PYTHONPATH=src python examples/distributed_clustering.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import numpy as np

from repro.api import KernelKMeans
from repro.core import clustering_accuracy
from repro.data import blob_ring
from repro.serve import ComputePolicy, data_mesh

n = 4096
X, labels = blob_ring(jax.random.PRNGKey(0), n=n)

est = KernelKMeans(k=2, r=2, kernel="polynomial",
                   kernel_params={"gamma": 0.0, "degree": 2},
                   backend="onepass-srht",
                   backend_params={"oversampling": 10}, block=512,
                   policy=ComputePolicy(mesh=data_mesh()))
est.fit(X, key=jax.random.PRNGKey(1))

acc = clustering_accuracy(labels, np.asarray(est.labels_), 2)
print(f"devices={jax.device_count()} n={n} accuracy={acc:.3f} "
      f"eigvals={np.asarray(est.model_.eigvals).round(1)}")
assert acc > 0.95
