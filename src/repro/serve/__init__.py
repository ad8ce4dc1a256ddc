"""repro.serve: fit once, assign millions — out-of-sample inference.

The fitting side (repro.api.KernelKMeans over the pluggable approximation
backends — one-pass SRHT/Gaussian, Nystrom, exact) produces a compact
rank-r linearization of the kernel matrix; this package turns that fit —
WHICHEVER backend produced it — into a deployable service:

  artifact.py   FittedModel pytree + atomic save/load (ClusteringSpec
                sidecar, arrays via repro.distributed.checkpoint,
                optional bf16 storage); backend-specific extension state
                (sketch state, Nystrom landmarks) rides along
  extend.py     streaming Nystrom-style out-of-sample extension
                y(x) = Sigma^{-1/2} U^T kappa(ref, x) — ref being the
                training set or the Nystrom landmarks — and cluster
                assignment; Extender runs each stripe either through the
                fused gram->projection Pallas kernel
                (kernels/extend_embed, the off-CPU default — the
                (n, block) block never leaves VMEM) or the two-pass
                gram+projection executables, plus the jnp / fused Pallas
                kmeans_assign argmin; ShardedExtender shards the
                extension matmul over a mesh
  policy.py     ComputePolicy: the one frozen object carrying every
                compute-path knob (embed_fused / assign_fused /
                fit_fused / interpret / mesh / mesh_axis), accepted
                uniformly by the serving front doors AND the one-pass
                fit; absorbs resolve_pallas_path
  batcher.py    micro-batching with power-of-two shape buckets so variable
                query traffic never retraces; coalescing request queue
  scheduler.py  AsyncBatcher: futures per request, deadline-driven flush
                (max_wait_ms or full bucket), SLO-accounted; stop()
                retires it (post-stop submits raise, never strand)
  latency.py    streaming latency histogram: p50/p95/p99, SLO violations
  registry.py   multi-model registry + lifecycle: one process, many
                fitted models; warm hot-swap (swap() pre-warms the new
                row's executables, flips atomically, drains the old
                scheduler — SwapReport measures the flip)
  versions.py   versioned artifact store: <root>/v_<N>/ on the atomic
                checkpoint commit; publish / pinned loads / keep-last-K GC

Docs: docs/SERVING.md (serving semantics), docs/ARCHITECTURE.md (layers).
"""
from repro.serve.artifact import (ClusteringSpec, FittedModel, ModelSpec,
                                  fit_model, load_model, save_model)
from repro.serve.batcher import MicroBatcher, bucket_size
from repro.serve.extend import (Extender, ShardedExtender, assign, embed,
                                embed_sharded)
from repro.serve.policy import (ComputePolicy, data_mesh,
                                merge_legacy_kwargs, resolve_pallas_path)
from repro.serve.latency import LatencyStats
from repro.serve.registry import (DEFAULT_REGISTRY, ModelRegistry,
                                  SwapReport)
from repro.serve.scheduler import AsyncBatcher
from repro.serve.versions import (VersionStore, gc_versions,
                                  latest_version, load_version,
                                  publish_version)

__all__ = [
    "ClusteringSpec", "FittedModel", "ModelSpec", "fit_model",
    "load_model", "save_model",
    "MicroBatcher", "bucket_size",
    "Extender", "ShardedExtender", "assign", "embed", "embed_sharded",
    "ComputePolicy", "data_mesh", "merge_legacy_kwargs",
    "resolve_pallas_path",
    "LatencyStats",
    "DEFAULT_REGISTRY", "ModelRegistry", "SwapReport",
    "AsyncBatcher",
    "VersionStore", "gc_versions", "latest_version", "load_version",
    "publish_version",
]
