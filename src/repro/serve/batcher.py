"""Micro-batching for the query path: pow-2 shape buckets, no retracing.

jit specializes on shapes, so serving raw variable-size batches would
compile a fresh executable per distinct batch size — unbounded compile
cache, latency cliffs on first-seen sizes. Policy here:

  - a batch of b queries is zero-padded up to bucket(b), the next power of
    two clamped to [min_bucket, max_bucket]; results for the padded columns
    are computed and discarded (columns are independent, so real queries
    are bit-identical to an unpadded run at the same padded width);
  - batches wider than max_bucket are chunked into full max_bucket pieces
    (the steady-state shape) plus one bucketed remainder;
  - at most log2(max_bucket / min_bucket) + 1 executables ever exist per
    model, all tracked in `stats` so tests can assert the no-retrace
    property.

`MicroBatcher` also provides a coalescing request queue: `submit()` enqueues
any number of independent requests, `drain()` runs them as ONE concatenated
bucketed batch and scatters labels back per request — the standard
GPU/TPU-serving micro-batch pattern, deterministic and thread-free so the
behaviour is exactly testable.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.sketch import next_pow2
from repro.serve import extend
from repro.serve.artifact import FittedModel
from repro.serve.policy import ComputePolicy, merge_legacy_kwargs
from repro.spans import span


def bucket_size(b: int, min_bucket: int = 8, max_bucket: int = 1024) -> int:
    """Next power of two >= b, clamped to [min_bucket, max_bucket]."""
    if b < 1:
        raise ValueError(f"batch size must be positive, got {b}")
    return max(min_bucket, min(next_pow2(b), max_bucket))


class MicroBatcher:
    """Bucketed assignment front-end for one FittedModel.

    policy: ComputePolicy selecting the compute paths — assign_fused is
    the Pallas kmeans_assign argmin (None = off-CPU default), embed_fused
    the fused extend_embed stripe engine (same default), interpret the
    Pallas interpret-mode override for both (the knob CI uses to force
    the Pallas serving path on CPU; see serve/policy.py for the conflict
    rules), and mesh/mesh_axis the mesh-sharded extension. The old
    fused=/embed_fused=/interpret=/mesh= kwargs are the deprecated
    spelling of the same fields.
    """

    def __init__(self, model: FittedModel, block: Optional[int] = None,
                 min_bucket: int = 8, max_bucket: int = 1024,
                 fused: Optional[bool] = None,
                 embed_fused: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 mesh=None, mesh_axis: str = "data",
                 policy: Optional[ComputePolicy] = None):
        policy = merge_legacy_kwargs(
            policy, {"assign_fused": fused, "embed_fused": embed_fused,
                     "interpret": interpret, "mesh": mesh,
                     "mesh_axis": mesh_axis}, "MicroBatcher")
        self.model = model
        self.policy = policy
        self.block = block or model.spec.block
        self.min_bucket = min_bucket
        self.max_bucket = max_bucket
        self.fused = policy.assign_fused
        # policy.mesh != None routes every bucketed assignment through the
        # mesh-sharded extension (same bucketing policy, sharded matmul);
        # otherwise one Extender owns the stripe engine + executables.
        self.sharded = policy.mesh is not None
        self.extender = (
            extend.ShardedExtender(model, block=self.block, policy=policy)
            if self.sharded else
            extend.Extender(model, self.block, policy=policy))
        self._pending: List[np.ndarray] = []
        self.stats: Dict = {}
        self.reset_stats()

    def reset_stats(self, preserve_buckets: bool = False) -> None:
        """Zero the traffic counters.

        preserve_buckets=False also drops bucket_hits — and with it the
        `executables` view that a warm hot-swap (registry.swap) replays
        into the incoming row. Periodic stats sampling (e.g. the drift
        monitor's sample_serving_stats) must pass preserve_buckets=True:
        the hit COUNTS reset to zero but every bucket key survives, so a
        sample between swaps can never cold-start the next swap. Neither
        form touches the jit cache itself."""
        hits = ({b: 0 for b in self.stats.get("bucket_hits", {})}
                if preserve_buckets else {})
        self.stats = {"queries": 0, "padded_queries": 0,
                      "batches": 0, "bucket_hits": hits}

    # -- bucketed one-shot path ------------------------------------------

    def assign_batch(self, Xq: jnp.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Bucketed assignment of Xq (p, b) -> (labels (b,), d2 (b,))."""
        b = Xq.shape[1]
        if b == 0:
            return (np.zeros((0,), np.int32), np.zeros((0,), np.float32))
        labels, d2 = [], []
        for start in range(0, b, self.max_bucket):
            chunk = Xq[:, start:start + self.max_bucket]
            lab, dd = self._assign_bucketed(chunk)
            labels.append(lab)
            d2.append(dd)
        return np.concatenate(labels), np.concatenate(d2)

    def _assign_bucketed(self, chunk: jnp.ndarray
                         ) -> Tuple[np.ndarray, np.ndarray]:
        w = chunk.shape[1]
        bsz = bucket_size(w, self.min_bucket, self.max_bucket)
        with span("serve.dispatch", bucket=bsz):
            padded = (chunk if w == bsz
                      else jnp.pad(chunk, ((0, 0), (0, bsz - w))))
            if self.sharded:
                # Sharded path: stripe width is baked into the one
                # compiled sharded executable at ShardedExtender
                # construction.
                lab, d2 = self.extender.assign(padded)
            else:
                # Narrow the gram stripe to the bucket: a bucket-8
                # request must not pay an n x block (e.g. 512-wide)
                # kernel stripe. bsz is already pow-2-clamped, so stripe
                # widths — and hence compiled executables — stay bounded
                # by the bucket count.
                lab, d2 = self.extender.assign(padded,
                                               block=min(self.block, bsz))
        self.stats["queries"] += w
        self.stats["padded_queries"] += bsz - w
        self.stats["batches"] += 1
        self.stats["bucket_hits"][bsz] = \
            self.stats["bucket_hits"].get(bsz, 0) + 1
        # Waits for the device, copies back (and compiles the slices of
        # a width not seen before).
        with span("serve.fetch"):
            return np.asarray(lab[:w]), np.asarray(d2[:w])

    def warm(self, buckets) -> List[int]:
        """Compile the executables for the given bucket widths now.

        Runs one zero batch per distinct pow-2-clamped bucket through the
        real bucketed path, so the compile cost is paid here — off the
        serving path — and the buckets land in stats["bucket_hits"]
        exactly like traffic would put them there. This is how a warm
        hot-swap (registry.swap) replays the outgoing row's bucket
        history into the incoming row before the flip. Returns the
        bucket sizes warmed, ascending.
        """
        warmed = []
        for b in sorted({bucket_size(int(b), self.min_bucket,
                                     self.max_bucket) for b in buckets}):
            self.assign_batch(np.zeros((self.model.spec.p, b), np.float32))
            warmed.append(b)
        return warmed

    # -- coalescing request queue ----------------------------------------

    def validate_request(self, Xq) -> np.ndarray:
        """Shape-check one request; returns it as float32 numpy.

        Shared with AsyncBatcher (serve/scheduler.py) so both front doors
        reject malformed requests identically, at submit time."""
        Xq = np.asarray(Xq, np.float32)
        if Xq.ndim != 2 or Xq.shape[0] != self.model.spec.p \
                or Xq.shape[1] < 1:
            raise ValueError(f"request must be (p={self.model.spec.p}, "
                             f"b>=1), got {Xq.shape}")
        return Xq

    def submit(self, Xq: jnp.ndarray) -> int:
        """Enqueue one request of queries (p, b_i); returns its ticket."""
        self._pending.append(self.validate_request(Xq))
        return len(self._pending) - 1

    def drain(self) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Run all pending requests as one coalesced bucketed batch.

        Returns [(labels_i, d2_i)] aligned with submission order.
        """
        if not self._pending:
            return []
        widths = [x.shape[1] for x in self._pending]
        with span("serve.coalesce", width=sum(widths)):
            big = jnp.asarray(np.concatenate(self._pending, axis=1))
        self._pending = []
        labels, d2 = self.assign_batch(big)
        out, off = [], 0
        for w in widths:
            out.append((labels[off:off + w], d2[off:off + w]))
            off += w
        return out

    @property
    def executables(self) -> List[int]:
        """Bucket sizes compiled so far (sorted) — the retrace budget."""
        return sorted(self.stats["bucket_hits"])
