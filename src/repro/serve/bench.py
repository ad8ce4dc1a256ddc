"""Serving benchmarks: sync/async/fused/swap/backends, 1-device or sharded.

Eight modes, all landing in BENCH_serve.json:

  sync     `benchmark_assign` — bucketed assignments/sec per batch size
           through MicroBatcher (one warmup call per size pays compile);
  async    `benchmark_async` — request traffic through AsyncBatcher with
           deadline-driven flushing; reports the LatencyStats summary
           (p50/p95/p99, queue wait, SLO violations) plus throughput;
  fused    `benchmark_fused` — the extension stripe through the fused
           gram->projection Pallas kernel vs the two-pass gram+projection
           executables, plus the per-stripe HBM-traffic delta (two-pass
           measured by launch/hlo_analysis, fused from the kernel's
           static memory contract);
  swap     `benchmark_swap` — async traffic with a warm hot-swap
           (registry.swap) in the middle: measured flip duration plus
           p95 before/after from the surviving LatencyStats, so swap
           downtime is a number, not a claim;
  backends `benchmark_backends` — the paper's comparison as a gated
           number: every registered approximation backend (onepass-srht /
           onepass-gaussian / nystrom / exact) fitted through the
           unified KernelKMeans front door on the same data; accuracy,
           streaming kernel-approx error, fit wall/memory, artifact
           bytes, and bucketed serving throughput per backend;
  stream   `benchmark_stream` — the streaming-fit path (repro.stream):
           partial_fit accumulation throughput (chunks/sec, cols/sec),
           the re-eig cadence cost, and the detection-to-swap latency of
           one full drift rollout (trigger -> refit -> publish -> warm
           swap) against a real VersionStore + ModelRegistry;
  fit_scaling `benchmark_fit_scaling` — the mesh-sharded one-pass fit
           (distributed/fit.ShardedFitEngine) vs the single-host
           accumulator on an n sweep: partial_fit cols/sec each, plus a
           per-block bytes-moved model (canonical executables measured
           by launch/hlo_analysis, fused fit_sketch from its static
           memory contract) with roofline flops/byte coverage;
  fleet    `repro.fleet.benchmark_fleet` — the multi-worker soak: q/s +
           merged p99 per worker count (pump threads running), an
           overload flood asserting shed-rate > 0 with admitted p99
           within the SLO, and a canary-then-promote rollout plus a
           probe-breached rollback (zero stranded futures asserted);
  sharded  sync/async with mesh= set — the extension matmul runs through
           serve.extend.ShardedExtender on the given mesh.

Schema (write_bench):

    {"model": {...spec...}, "backend": "cpu",
     "batch_sizes": [...],
     "results": [{"batch_size": b, "bucket": B, "calls": c, "wall_s": t,
                  "assignments_per_sec": qps}, ...],
     "bucket_executables": [...],
     "sharded": false | {"shards": s, "axis": "data"},
     "async": {"max_wait_ms": ..., "wall_s": ..., "queries_per_sec": ...,
               "latency": <LatencyStats.summary()>},       # async mode only
     "fused": {"fused": {...}, "two_pass": {...}, "speedup": ...,
               "hbm": {"two_pass_bytes": ..., "fused_bytes": ...,
                       "saved_bytes": ..., "saved_ratio": ...}},
     "swap": {"flip_ms": ..., "warm_s": ..., "drain_s": ...,
              "buckets_warmed": [...], "drained_requests": ...,
              "p95_before_ms": ..., "p95_after_ms": ...,
              "stranded_futures": 0},
     "backends": {"per_backend": {"onepass-srht": {"accuracy": ...,
                  "kernel_approx_error": ..., "fit_s": ...,
                  "fit_memory_bytes": ..., "artifact_bytes": ...,
                  "n_ref": ..., "assignments_per_sec": ...}, ...}},
     "stream": {"partial_fit_chunks_per_sec": ...,
                "partial_fit_cols_per_sec": ..., "reeig_s": ...,
                "rollout": {"detect_to_swap_s": ..., "refit_s": ...,
                            "publish_s": ..., "swap_s": ...,
                            "stranded_futures": 0, "retrains": 1}},
     "fit_scaling": {"shards": s, "rows": [{"n": ...,
                     "single_cols_per_sec": ..., "sharded_cols_per_sec":
                     ..., "bytes": {"two_pass_bytes": ..., "fused_bytes":
                     ..., "flops": ..., ...}}, ...]}}
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.artifact import FittedModel
from repro.serve.batcher import MicroBatcher, bucket_size
from repro.serve.extend import Extender
from repro.serve.registry import ModelRegistry
from repro.serve.scheduler import AsyncBatcher


def _min_call_time(fn, repeats: int, min_total_s: float = 0.25,
                   max_calls: int = 1000):
    """(best per-call seconds, calls made, total wall seconds).

    Throughput from the BEST of an auto-calibrated number of calls
    (timeit's estimator): serving calls here finish in ~ms, where a
    mean over a fixed handful of calls is dominated by scheduler/GC
    outliers and flaps the CI regression gate by ±30%. `repeats` is the
    floor; the count is raised until ~min_total_s of samples back the
    minimum. The caller must have warmed up / compiled `fn` already.
    """
    t0 = time.perf_counter()
    fn()
    est = time.perf_counter() - t0
    calls = max(int(repeats),
                min(max_calls, int(min_total_s / max(est, 1e-9)) + 1))
    times = [est]
    for _ in range(calls - 1):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times), calls, sum(times)


def benchmark_assign(model: FittedModel,
                     batch_sizes: Sequence[int] = (64, 512),
                     repeats: int = 5,
                     key: Optional[jax.Array] = None,
                     block: Optional[int] = None,
                     fused: Optional[bool] = None,
                     embed_fused: Optional[bool] = None,
                     interpret: Optional[bool] = None,
                     max_bucket: int = 1024,
                     mesh=None, mesh_axis: str = "data") -> Dict:
    """Drive synthetic query load through a MicroBatcher; returns the dict
    documented in the module docstring. mesh != None measures the
    mesh-sharded extension path on the same bucketing policy;
    embed_fused/interpret pick the extension stripe engine."""
    key = key if key is not None else jax.random.PRNGKey(0)
    batcher = MicroBatcher(model, block=block, fused=fused,
                           embed_fused=embed_fused, interpret=interpret,
                           max_bucket=max_bucket, mesh=mesh,
                           mesh_axis=mesh_axis)
    results = []
    for b in batch_sizes:
        Xq = jax.random.normal(key, (model.spec.p, b), jnp.float32)
        batcher.assign_batch(Xq)                    # warmup / compile
        # assign_batch returns host numpy arrays, so the timed calls
        # include device sync — honest throughput.
        best, calls, wall = _min_call_time(
            lambda: batcher.assign_batch(Xq), repeats)
        results.append({
            "batch_size": int(b),
            "bucket": bucket_size(b, batcher.min_bucket, batcher.max_bucket),
            "calls": int(calls),
            "wall_s": wall,
            "assignments_per_sec": b / best,
        })
    return {
        "model": dataclasses.asdict(model.spec),
        "backend": jax.default_backend(),
        "batch_sizes": [int(b) for b in batch_sizes],
        "results": results,
        "bucket_executables": batcher.executables,
        "sharded": ({"shards": batcher.extender.shards, "axis": mesh_axis}
                    if mesh is not None else False),
    }


def benchmark_async(model: FittedModel,
                    n_requests: int = 256,
                    width_range: Sequence[int] = (1, 64),
                    max_wait_ms: float = 2.0,
                    slo_ms: float = 250.0,
                    key: Optional[jax.Array] = None,
                    block: Optional[int] = None,
                    fused: Optional[bool] = None,
                    embed_fused: Optional[bool] = None,
                    interpret: Optional[bool] = None,
                    max_bucket: int = 1024,
                    mesh=None, mesh_axis: str = "data") -> Dict:
    """Request traffic through AsyncBatcher; returns latency percentiles.

    Submits n_requests of uniformly random widths in width_range, polling
    the deadline between submits (cooperative mode — the bench IS the
    event loop, so numbers are not polluted by pump-thread jitter), then
    flushes the tail. Every pow-2 bucket the traffic can hit is compiled
    during a warmup pass first: steady-state percentiles, not compile
    spikes, which on CPU would otherwise dominate p99 by ~3 orders of
    magnitude.
    """
    key = key if key is not None else jax.random.PRNGKey(0)
    rng = np.random.RandomState(
        int(jax.random.randint(key, (), 0, 2**31 - 1)))
    lo, hi = int(width_range[0]), int(width_range[1])
    widths = rng.randint(lo, hi + 1, size=n_requests)
    queries = rng.randn(model.spec.p, int(widths.sum())).astype(np.float32)

    async_batcher = AsyncBatcher(model, max_wait_ms=max_wait_ms,
                                 slo_ms=slo_ms, block=block, fused=fused,
                                 embed_fused=embed_fused,
                                 interpret=interpret,
                                 max_bucket=max_bucket, mesh=mesh,
                                 mesh_axis=mesh_axis)
    # Warmup: compile every bucket in [min_bucket, max_bucket] once.
    bsz = async_batcher.batcher.min_bucket
    while bsz <= max_bucket:
        async_batcher.batcher.assign_batch(
            jnp.zeros((model.spec.p, bsz), jnp.float32))
        bsz *= 2
    async_batcher.batcher.reset_stats()

    futures = []
    off = 0
    t0 = time.perf_counter()
    for w in widths:
        futures.append(async_batcher.submit(queries[:, off:off + w]))
        off += w
        async_batcher.poll()
    async_batcher.flush()
    for fut in futures:
        fut.result()                              # all resolved by flush
    wall = time.perf_counter() - t0
    total_q = int(widths.sum())
    return {
        "mode": "async",
        "n_requests": int(n_requests),
        "width_range": [lo, hi],
        "max_wait_ms": float(max_wait_ms),
        "wall_s": wall,
        "queries_per_sec": total_q / wall,
        "latency": async_batcher.latency.summary(),
        "bucket_executables": async_batcher.batcher.executables,
        "sharded": ({"shards": async_batcher.batcher.extender.shards,
                     "axis": mesh_axis} if mesh is not None else False),
    }


def benchmark_swap(model: FittedModel,
                   new_model: Optional[FittedModel] = None,
                   n_requests: int = 128,
                   width_range: Sequence[int] = (1, 64),
                   max_wait_ms: float = 2.0,
                   slo_ms: float = 250.0,
                   key: Optional[jax.Array] = None,
                   block: Optional[int] = None,
                   fused: Optional[bool] = None,
                   embed_fused: Optional[bool] = None,
                   interpret: Optional[bool] = None,
                   max_bucket: int = 1024) -> Dict:
    """Async traffic with a warm hot-swap in the middle; measures the flip.

    Half the requests run against the original model, registry.swap()
    flips to `new_model` (default: a re-wrap of the same fit — the
    same-spec refresh case every real redeploy hits), the other half run
    against the swapped-in row. All timing comes from the surviving
    LatencyStats, so p95_before/p95_after are directly comparable — the
    after number includes the before samples (cumulative histogram): a
    swap that stalled traffic shows up as p95_after >> p95_before.
    Every future is checked resolved; `stranded_futures` must be 0.
    """
    key = key if key is not None else jax.random.PRNGKey(0)
    rng = np.random.RandomState(
        int(jax.random.randint(key, (), 0, 2**31 - 1)))
    lo, hi = int(width_range[0]), int(width_range[1])
    widths = rng.randint(lo, hi + 1, size=n_requests)
    queries = rng.randn(model.spec.p, int(widths.sum())).astype(np.float32)

    reg = ModelRegistry()
    reg.register("swap-bench", model, version=1)
    sched = reg.scheduler("swap-bench", max_wait_ms=max_wait_ms,
                          slo_ms=slo_ms, block=block, fused=fused,
                          embed_fused=embed_fused, interpret=interpret,
                          max_bucket=max_bucket)
    # Warmup as in benchmark_async: compile every reachable bucket so the
    # percentiles measure steady-state serving (and the swap's warm phase
    # has a full bucket history to replay).
    bsz = sched.batcher.min_bucket
    while bsz <= max_bucket:
        sched.batcher.assign_batch(
            jnp.zeros((model.spec.p, bsz), jnp.float32))
        bsz *= 2

    half = n_requests // 2
    pend_n = min(4, half)
    futures = []
    off = 0

    def drive(target, lo_i, hi_i, flush=True):
        nonlocal off
        for w in widths[lo_i:hi_i]:
            futures.append(target.submit(queries[:, off:off + w]))
            off += w
            if flush:
                target.poll()
        if flush:
            target.flush()

    t0 = time.perf_counter()
    drive(sched, 0, half - pend_n)
    # The last pre-swap requests stay PENDING at flip time: the swap's
    # drain — not a client flush — must resolve them through the old
    # model, so drained_requests measures the real pending-at-flip path.
    drive(sched, half - pend_n, half, flush=False)
    report = reg.swap("swap-bench",
                      new_model if new_model is not None
                      else model._replace(), version=2)
    sched2 = reg.scheduler("swap-bench")
    drive(sched2, half, n_requests)
    wall = time.perf_counter() - t0
    report.p95_after_ms = sched2.latency.total.percentile(95.0)
    stranded = sum(not f.done() for f in futures)
    out = {"mode": "swap", "n_requests": int(n_requests),
           "width_range": [lo, hi], "max_wait_ms": float(max_wait_ms),
           "wall_s": wall, "stranded_futures": int(stranded)}
    out.update({k: v for k, v in report.to_dict().items()
                if k not in ("name", "old_version", "new_version")})
    return out


def _stripe_hbm_traffic(model: FittedModel, width: int) -> Dict:
    """Per-stripe HBM traffic: two-pass measured vs fused kernel contract.

    Two-pass is the sum of `launch.hlo_analysis.analyze` over the two real
    executables (gram stripe, projection matmul) — the (n, width) stripe
    is written by the first and re-read by the second. The fused Pallas
    kernel is a custom call, opaque to HLO analysis, but its memory
    contract is static and exact: each operand tile crosses HBM once and
    the (r, width) output is written once (the accumulator is revisited in
    VMEM), so its bytes are computed from the padded operand shapes.
    """
    from repro.launch.hlo_analysis import analyze

    spec = model.spec
    # n here is the extension height: the landmark count for Nystrom
    # fits, the training count otherwise.
    p, n, r = spec.p, model.n_ref, spec.r
    kern = model.kernel_fn()
    f32 = jnp.float32
    gram_txt = jax.jit(lambda X, xb: kern(X, xb)).lower(
        jax.ShapeDtypeStruct((p, n), f32),
        jax.ShapeDtypeStruct((p, width), f32)).compile().as_text()
    proj_txt = jax.jit(lambda pr, s: pr @ s).lower(
        jax.ShapeDtypeStruct((r, n), f32),
        jax.ShapeDtypeStruct((n, width), f32)).compile().as_text()
    two_pass = (analyze(gram_txt)["traffic_bytes"] +
                analyze(proj_txt)["traffic_bytes"])
    # Single source of truth: the kernel package's own declared model,
    # which repro.analysis cross-checks against the BlockSpecs (C001).
    from repro.kernels.extend_embed.ops import memory_contract
    fused = memory_contract(p, n, r, width)["hbm_bytes"]
    return {
        "two_pass_bytes": float(two_pass),
        "two_pass_source": "launch.hlo_analysis over gram + projection "
                           "executables",
        "fused_bytes": float(fused),
        "fused_source": "extend_embed kernel memory contract (Pallas "
                        "custom call is opaque to HLO analysis)",
        "stripe_roundtrip_bytes": float(2 * 4 * n * width),
        "saved_bytes": float(two_pass - fused),
        "saved_ratio": float((two_pass - fused) / two_pass)
        if two_pass else 0.0,
    }


def benchmark_fused(model: FittedModel, width: int = 512, repeats: int = 5,
                    key: Optional[jax.Array] = None,
                    block: Optional[int] = None,
                    interpret: Optional[bool] = None) -> Dict:
    """Fused extend_embed stripe vs two-pass gram+projection, same load.

    Embeds a (p, width) query batch through both engines (warmup paid
    outside the timed loop; np.asarray forces device sync) and reports
    throughput each plus the per-stripe HBM delta. On CPU the fused
    engine runs the Pallas kernel in interpret mode — throughput there
    measures the interpreter, not the TPU lowering, but the parity and
    the HBM model are backend-independent.
    """
    key = key if key is not None else jax.random.PRNGKey(0)
    block_w = min(block or model.spec.block, width)
    cpu = jax.default_backend() == "cpu"
    interp = interpret if interpret is not None else (True if cpu else None)
    engines = {
        "fused": Extender(model, block_w, fused=True, interpret=interp),
        "two_pass": Extender(model, block_w, fused=False),
    }
    Xq = jax.random.normal(key, (model.spec.p, width), jnp.float32)
    out: Dict = {"mode": "fused", "width": int(width),
                 "block": int(block_w), "repeats": int(repeats),
                 "backend": jax.default_backend(),
                 "interpret": bool(engines["fused"]._interpret)}
    for name, ext in engines.items():
        np.asarray(ext.embed(Xq))                   # warmup / compile
        best, calls, wall = _min_call_time(
            lambda: np.asarray(ext.embed(Xq)), repeats)
        out[name] = {"wall_s": wall, "calls": int(calls),
                     "queries_per_sec": width / best}
    out["speedup"] = (out["fused"]["queries_per_sec"] /
                      out["two_pass"]["queries_per_sec"])
    out["hbm"] = _stripe_hbm_traffic(model, block_w)
    return out


def benchmark_backends(X, labels, k: int, r: int,
                       backends: Optional[Sequence[str]] = None,
                       kernel: str = "polynomial",
                       kernel_params: Optional[Dict] = None,
                       block: int = 512, batch_size: int = 256,
                       repeats: int = 3,
                       key: Optional[jax.Array] = None,
                       interpret: Optional[bool] = None,
                       max_n: int = 4000) -> Dict:
    """The paper's comparison as a bench section: fit every registered
    approximation backend through the unified `KernelKMeans` front door
    on the SAME data and report, per backend:

      accuracy            best-permutation clustering accuracy vs labels
      kernel_approx_error streaming ||K - Y^T Y||_F / ||K||_F
      fit_s               fit wall time (backend + K-means)
      fit_memory_bytes    the backend's dominant fit working set (the
                          paper's memory axis: O(r'n) one-pass vs O(mn)
                          Nystrom vs O(n^2) exact)
      artifact_bytes      persisted FittedModel array payload
      n_ref               serving extension height (m for Nystrom, n else)
      assignments_per_sec bucketed serving throughput at `batch_size`
                          through MicroBatcher (compile paid in warmup)

    This is the section that makes "a Nystrom-fitted model serves through
    the full stack" a gated number rather than a claim.

    Note on accuracy: K-means on a rank-r linearization can have several
    basins (on blob+ring at r=2 the best-objective split is not always
    the class split), so per-backend accuracy reflects the (key,
    n_restarts) basin — deterministic run to run, which is what the CI
    gate needs (it tracks per-backend drift, not the cross-backend
    ranking; a genuinely broken backend craters to ~1/k).

    Fits are cached per (data fingerprint, config) within the process —
    one sweep at a time: every per-backend number except the serve
    throughput is deterministic for a fixed key, so the K median passes
    of serve_cluster --smoke refit nothing (no K exact
    eigendecompositions) and only re-time the serving loop — the one
    pass-varying gated metric.

    The sweep includes the exact backend — a full (n, n) gram + dense
    eigh — so X is truncated to its first `max_n` columns (a uniform
    subsample for the pre-shuffled synthetic sets) before fitting; the
    dict records `subsampled_from` when that happened. A sync/async
    throughput bench at huge --n must not hide minutes of O(n^3)
    eigendecomposition behind it.
    """
    from repro.api import KernelKMeans, available_backends, fit_memory_bytes
    from repro.core.metrics import (clustering_accuracy,
                                    kernel_approx_error_streaming)

    key = key if key is not None else jax.random.PRNGKey(0)
    backends = list(backends) if backends else available_backends()
    full_n = int(X.shape[1])
    if full_n > max_n:
        X = X[:, :max_n]
        labels = np.asarray(labels)[:max_n]
    n = int(X.shape[1])
    per_backend: Dict[str, Dict] = {}
    data_print = (tuple(np.asarray(X).shape), float(jnp.sum(X)),
                  float(jnp.sum(jnp.square(X))))
    cfg = (data_print, n, int(k), int(r), kernel,
           tuple(sorted((kernel_params or {}).items())), int(block),
           _key_bits(key))
    if _BACKEND_FIT_CACHE.get("cfg") != cfg:
        _BACKEND_FIT_CACHE.clear()
        _BACKEND_FIT_CACHE["cfg"] = cfg
    for name in backends:
        cached = _BACKEND_FIT_CACHE.get((cfg, name))
        if cached is None:
            est = KernelKMeans(k=k, r=r, kernel=kernel,
                               kernel_params=kernel_params, backend=name,
                               block=block)
            t0 = time.perf_counter()
            est.fit(X, key=key)
            jax.block_until_ready(est.centroids_)
            fit_s = time.perf_counter() - t0
            model = est.model_
            err = kernel_approx_error_streaming(model.kernel_fn(), X,
                                                est.embedding_, block=block)
            acc = clustering_accuracy(labels, est.labels_, k)
            from repro.serve.artifact import _array_state
            artifact_bytes = sum(int(np.asarray(v).nbytes)
                                 for v in _array_state(model).values())
            cached = {
                "model": model,
                "row": {
                    "accuracy": float(acc),
                    "kernel_approx_error": float(err),
                    "fit_s": float(fit_s),
                    "fit_memory_bytes": int(
                        fit_memory_bytes(name, n, r, **est.backend_params)),
                    "artifact_bytes": artifact_bytes,
                    "n_ref": model.n_ref,
                },
            }
            _BACKEND_FIT_CACHE[(cfg, name)] = cached
        model = cached["model"]
        batcher = MicroBatcher(model, interpret=interpret)
        Xq = jax.random.normal(key, (model.spec.p, batch_size), jnp.float32)
        batcher.assign_batch(Xq)                     # warmup / compile
        best, calls, wall = _min_call_time(
            lambda: batcher.assign_batch(Xq), repeats)
        per_backend[name] = dict(cached["row"],
                                 assignments_per_sec=batch_size / best,
                                 calls=int(calls), wall_s=wall)
    out = {"mode": "backends", "n": n, "k": int(k), "r": int(r),
           "batch_size": int(batch_size), "per_backend": per_backend}
    if full_n > n:
        out["subsampled_from"] = full_n
    return out


# benchmark_backends fit cache; see its docstring. Keyed by a cheap data
# fingerprint (shape + first two moments) plus the full fit config and
# key bits — everything the deterministic fit depends on. Bounded to ONE
# sweep: a new (data, config) evicts the previous sweep's fitted models,
# so a long-lived process sweeping many datasets never accumulates them.
_BACKEND_FIT_CACHE: Dict = {}


def _key_bits(key) -> tuple:
    """Hashable bit content of a PRNG key, raw uint32 or typed."""
    try:
        arr = jax.random.key_data(key)      # typed keys
    except (TypeError, ValueError, AttributeError):
        arr = key                           # raw uint32 keys
    return tuple(np.asarray(arr).ravel().tolist())


def benchmark_stream(model: FittedModel, n_chunks: int = 8,
                     chunk_cols: int = 128, repeats: int = 3,
                     key: Optional[jax.Array] = None,
                     block: Optional[int] = None,
                     max_wait_ms: float = 2.0) -> Dict:
    """The streaming-fit path (repro.stream) as bench numbers.

    Three read-outs:

      partial_fit_*_per_sec  accumulation throughput: chunks folded with
                             reeig=False (the steady-state ingest path) —
                             best pass of `repeats`, each on a fresh
                             accumulator so every pass pays the same
                             per-block kernel-stripe work;
      reeig_s                re-eig cadence cost at full capacity
                             (one_pass_core + full K-means re-cluster),
                             best of `repeats` after a warmup call;
      rollout                detection-to-swap latency of one REAL drift
                             rollout — drifted async traffic observed by
                             a DriftMonitor, RetrainWorker.step() doing
                             refit -> VersionStore.publish -> warm
                             registry.swap — with the zero-stranded-
                             futures invariant re-checked. Wall numbers
                             here include a full refit, so the gate
                             treats detect_to_swap_s as info-only.

    The accumulation/re-eig section streams random data through the
    passed model's spec (coerced to a one-pass backend — streaming needs
    sketch state); the rollout is a self-contained 1-d drift demo, so the
    numbers are comparable across --backend choices.
    """
    import tempfile

    from repro.api import KernelKMeans
    from repro.serve.versions import VersionStore
    from repro.stream import DriftMonitor, RetrainWorker

    key = key if key is not None else jax.random.PRNGKey(0)
    spec = model.spec
    backend = (spec.backend if spec.backend.startswith("onepass-")
               else "onepass-srht")
    blk = min(block or spec.block, chunk_cols)
    capacity = int(n_chunks) * int(chunk_cols)
    X = jax.random.normal(key, (spec.p, capacity), jnp.float32)

    def one_pass():
        est = KernelKMeans(k=spec.k, r=spec.r, kernel=spec.kernel,
                           kernel_params=spec.kernel_params,
                           backend=backend, block=blk)
        est.partial_fit(X[:, :chunk_cols], key=key, capacity=capacity,
                        reeig=False)               # warmup chunk
        t0 = time.perf_counter()
        for i in range(1, n_chunks):
            est.partial_fit(X[:, i * chunk_cols:(i + 1) * chunk_cols],
                            reeig=False)
        jax.block_until_ready(est._acc.W)
        return time.perf_counter() - t0, est

    walls = []
    for _ in range(max(int(repeats), 1)):
        wall, est = one_pass()
        walls.append(wall)
    accum_best = min(walls)

    est.reeig_now()                                # compile / warmup
    reeig_times = []
    for _ in range(max(int(repeats), 1)):
        t0 = time.perf_counter()
        est.reeig_now()
        jax.block_until_ready(est.centroids_)
        reeig_times.append(time.perf_counter() - t0)

    # One full drift rollout against a real store + registry.
    rng = np.random.RandomState(0)

    def blobs(xs, n_per=80):
        cols = []
        for x0 in xs:
            c = np.zeros((2, n_per), np.float32)
            c[0] = x0 + 0.25 * rng.randn(n_per)
            c[1] = 0.25 * rng.randn(n_per)
            cols.append(c)
        return np.concatenate(cols, axis=1)

    X0, Xd = blobs((-2.0, 2.0)), blobs((3.0, 8.0))
    demo = KernelKMeans(k=2, r=2, kernel="linear",
                        backend="onepass-srht", block=64)
    demo.partial_fit(X0, key=key, capacity=X0.shape[1] + Xd.shape[1])
    with tempfile.TemporaryDirectory() as tmp:
        store = VersionStore(tmp, keep=2)
        reg = ModelRegistry()
        reg.register("stream-bench", demo.model_,
                     version=store.publish(demo.model_))
        sched = reg.scheduler("stream-bench", max_wait_ms=max_wait_ms)
        mon = DriftMonitor(demo.model_, ref_labels=demo.labels_,
                           min_queries=64)
        worker = RetrainWorker("stream-bench", reg, store, mon,
                               lambda rep: demo.partial_fit(Xd).model_)
        chunks = [Xd[:, i * 20:(i + 1) * 20] for i in range(8)]
        futures = [sched.submit(ch) for ch in chunks]
        sched.flush()
        for ch, fut in zip(chunks, futures):
            mon.observe(ch, fut.result()[0])
        pending = sched.submit(Xd[:, :8])          # drained by the swap
        rollout = worker.step()
        assert rollout is not None, "drift rollout did not fire"
        stranded = sum(not f.done() for f in futures + [pending])
        reg.unregister("stream-bench")             # retire the new pump

    return {
        "mode": "stream",
        "stream_backend": backend,
        "chunk_cols": int(chunk_cols),
        "n_chunks": int(n_chunks),
        "capacity": capacity,
        "block": int(blk),
        "partial_fit_chunks_per_sec": (n_chunks - 1) / accum_best,
        "partial_fit_cols_per_sec":
            (n_chunks - 1) * chunk_cols / accum_best,
        "reeig_s": min(reeig_times),
        "rollout": {
            "detect_to_swap_s": float(rollout.detect_to_swap_s),
            "refit_s": float(rollout.refit_s),
            "publish_s": float(rollout.publish_s),
            "swap_s": float(rollout.swap_s),
            "drift_chi2": float(rollout.drift.chi2),
            "drained_requests": int(rollout.swap.drained_requests),
            "stranded_futures": int(stranded),
            "retrains": int(worker.retrains),
        },
    }


def _fit_block_traffic(model: FittedModel, n: int, block: int) -> Dict:
    """Per-block HBM bytes of the one-pass fit update at capacity n.

    Canonical path measured over its three real executables (gram
    stripe, normalized FWHT of the zero-padded stripe, cross-term
    matmul) via `launch.hlo_analysis.analyze`; the fused fit_sketch
    Pallas kernel is a custom call opaque to HLO analysis, so its bytes
    come from the static memory contract (every padded operand and
    output crosses HBM once, the accumulator is revisited in VMEM).
    Flops are the analyzer's dot-op count (the FWHT's adds are not dots;
    the roofline ratio is therefore a floor for the canonical path).
    """
    from repro.core.sketch import fwht
    from repro.kernels.fit_sketch.ops import memory_contract
    from repro.launch.hlo_analysis import analyze

    spec = model.spec
    p, rp = spec.p, spec.r + spec.oversampling
    b = min(block, n)
    n_pad = 1 if n <= 1 else 1 << (n - 1).bit_length()
    kern = model.kernel_fn()
    f32 = jnp.float32
    texts = [
        jax.jit(lambda X, c: kern(X, c)).lower(
            jax.ShapeDtypeStruct((p, n), f32),
            jax.ShapeDtypeStruct((p, b), f32)).compile().as_text(),
        jax.jit(lambda M: fwht(M)).lower(
            jax.ShapeDtypeStruct((n_pad, b), f32)).compile().as_text(),
        jax.jit(lambda K, c: K @ c).lower(
            jax.ShapeDtypeStruct((n, b), f32),
            jax.ShapeDtypeStruct((b, rp), f32)).compile().as_text(),
    ]
    parts = [analyze(t) for t in texts]
    two_pass = sum(a["traffic_bytes"] for a in parts)
    flops = sum(a["flops"] for a in parts)
    # Single source of truth: the kernel package's own declared model,
    # which repro.analysis cross-checks against the BlockSpecs (C001).
    fused = memory_contract(p, n, b, rp)["hbm_bytes"]
    return {
        "two_pass_bytes": float(two_pass),
        "two_pass_source": "launch.hlo_analysis over gram + fwht + "
                           "cross executables",
        "fused_bytes": float(fused),
        "fused_source": "fit_sketch kernel memory contract (Pallas "
                        "custom call is opaque to HLO analysis)",
        "flops": float(flops),
        "flops_per_byte_two_pass": float(flops / two_pass)
        if two_pass else 0.0,
        "flops_per_byte_fused": float(flops / fused) if fused else 0.0,
        "saved_bytes": float(two_pass - fused),
    }


def benchmark_fit_scaling(model: FittedModel, ns: Sequence[int] = (128, 256,
                                                                   512),
                          repeats: int = 3,
                          key: Optional[jax.Array] = None,
                          block: Optional[int] = None,
                          policy=None) -> Dict:
    """Sharded one-pass fit vs single-host accumulator on an n sweep.

    For each n: stream n columns chunk-by-chunk through
    `KernelKMeans.partial_fit` with `reeig=False` (the steady-state
    ingest path) twice — once single-host, once with a mesh
    ComputePolicy over all local devices (distributed/fit engine) — and
    report cols/sec each (best pass of `repeats`, fresh estimator per
    pass; the warmup chunk pays compile outside the timed loop). On a
    1-process CPU run the mesh has one device, so "sharded" measures
    the engine's overhead over the canonical path at parity (the paths
    are bit-identical there); real scaling numbers come from
    multi-device runs (tests/fit_dist_checks.py, the CI 2-device
    smoke). Each row carries the `_fit_block_traffic` bytes-moved model,
    which is backend-independent.
    """
    from repro.api import KernelKMeans
    from repro.serve.policy import ComputePolicy, data_mesh

    key = key if key is not None else jax.random.PRNGKey(0)
    spec = model.spec
    backend = (spec.backend if spec.backend.startswith("onepass-")
               else "onepass-srht")
    chunk = min(block or spec.block, min(int(n) for n in ns))
    if policy is None:
        policy = ComputePolicy(mesh=data_mesh())

    def one_pass(n_chunks, capacity, X, pol):
        est = KernelKMeans(k=spec.k, r=spec.r, kernel=spec.kernel,
                           kernel_params=spec.kernel_params,
                           backend=backend, block=chunk, policy=pol)
        est.partial_fit(X[:, :chunk], key=key, capacity=capacity,
                        reeig=False)               # warmup chunk
        t0 = time.perf_counter()
        for i in range(1, n_chunks):
            est.partial_fit(X[:, i * chunk:(i + 1) * chunk], reeig=False)
        jax.block_until_ready(est._acc.W)
        return time.perf_counter() - t0

    rows = []
    seen = set()
    for n in ns:
        n_chunks = max(int(n) // chunk, 2)
        capacity = n_chunks * chunk
        if capacity in seen:    # small n collapse onto the same capacity
            continue            # when chunk > n/2; one row per capacity
        seen.add(capacity)
        X = jax.random.normal(key, (spec.p, capacity), jnp.float32)
        single = min(one_pass(n_chunks, capacity, X, None)
                     for _ in range(max(int(repeats), 1)))
        sharded = min(one_pass(n_chunks, capacity, X, policy)
                      for _ in range(max(int(repeats), 1)))
        cols = (n_chunks - 1) * chunk
        rows.append({
            "n": int(capacity), "chunk_cols": int(chunk),
            "single_cols_per_sec": cols / single,
            "sharded_cols_per_sec": cols / sharded,
            "sharded_over_single": single / sharded,
            "bytes": _fit_block_traffic(model, capacity, chunk),
        })
    return {"mode": "fit_scaling", "fit_backend": backend,
            "shards": int(policy.shards), "chunk_cols": int(chunk),
            "repeats": int(repeats), "rows": rows}


def machine_calibration() -> Dict:
    """Machine-speed probe: best-call time of a fixed jitted matmul.

    Stored in every BENCH_serve.json so the CI regression gate can
    normalize wall-clock metrics by relative machine speed before
    diffing — the committed baseline and the CI runner are different
    (and burstable-CPU) machines, so raw absolute numbers drift with
    hardware state even when the serving code is unchanged.
    """
    x = jnp.ones((512, 512), jnp.float32)
    f = jax.jit(lambda a: a @ a)
    np.asarray(f(x))                                # compile
    best, _, _ = _min_call_time(lambda: np.asarray(f(x)), 10,
                                min_total_s=0.2)
    return {"matmul512_ms": best * 1e3}


def run_benches(model: FittedModel, modes: Sequence[str] = ("sync", "async"),
                batch_sizes: Sequence[int] = (64, 512), repeats: int = 5,
                key: Optional[jax.Array] = None,
                block: Optional[int] = None, fused: Optional[bool] = None,
                embed_fused: Optional[bool] = None,
                interpret: Optional[bool] = None,
                max_bucket: int = 1024,
                mesh=None, mesh_axis: str = "data",
                n_requests: int = 256, max_wait_ms: float = 2.0,
                slo_ms: float = 250.0,
                data: Optional[Tuple] = None) -> Dict:
    """Run the requested bench modes into ONE BENCH_serve.json dict.

    The shared driver behind benchmarks/bench_serve.py and the
    serve_cluster CLI: only the modes asked for run (and land in the
    dict), so `modes=("async",)` pays no synchronous warmup/timing.

    `data=(X, labels)` enables the "backends" mode — the per-backend
    accuracy/memory/throughput sweep needs the raw training data and
    ground truth, not just a fitted model; without it the mode is skipped
    with a note in the dict.
    """
    bench: Dict = {
        "model": dataclasses.asdict(model.spec),
        "backend": jax.default_backend(),
        "calibration": machine_calibration(),
        "sharded": ({"shards": dict(mesh.shape)[mesh_axis],
                     "axis": mesh_axis} if mesh is not None else False),
    }
    if "sync" in modes:
        bench.update(benchmark_assign(
            model, batch_sizes=batch_sizes, repeats=repeats, key=key,
            block=block, fused=fused, embed_fused=embed_fused,
            interpret=interpret, max_bucket=max_bucket, mesh=mesh,
            mesh_axis=mesh_axis))
    if "async" in modes:
        bench["async"] = benchmark_async(
            model, n_requests=n_requests, max_wait_ms=max_wait_ms,
            slo_ms=slo_ms, key=key, block=block, fused=fused,
            embed_fused=embed_fused, interpret=interpret,
            max_bucket=max_bucket, mesh=mesh, mesh_axis=mesh_axis)
    if "fused" in modes:
        # The fused-vs-two-pass stripe section is single-device by
        # construction (the sharded engines are compared in dist_checks).
        bench["fused"] = benchmark_fused(
            model, repeats=repeats, key=key, block=block,
            interpret=interpret)
    if "swap" in modes:
        # Single-device: the swap path itself is mesh-agnostic (the new
        # row is rebuilt with the old row's kwargs, mesh included), and
        # the flip/drain numbers are what this section is for.
        bench["swap"] = benchmark_swap(
            model, n_requests=max(n_requests // 2, 32),
            max_wait_ms=max_wait_ms, slo_ms=slo_ms, key=key, block=block,
            fused=fused, embed_fused=embed_fused, interpret=interpret,
            max_bucket=max_bucket)
    if "stream" in modes:
        # Single-device by construction: the streaming accumulate/re-eig
        # path and the drift rollout are fit-side, not extension-side.
        bench["stream"] = benchmark_stream(
            model, repeats=repeats, key=key, block=block,
            max_wait_ms=max_wait_ms)
    if "fit_scaling" in modes:
        # The mesh here is every LOCAL device; multi-host meshes go
        # through the library API (pass policy= to benchmark_fit_scaling
        # directly) rather than the CLI driver.
        bench["fit_scaling"] = benchmark_fit_scaling(
            model, repeats=repeats, key=key, block=block)
    if "fleet" in modes:
        # Imported here, not at module top: repro.fleet composes the
        # serve layer, so a top-level import would be circular via
        # repro.serve.__init__.
        from repro.fleet import benchmark_fleet
        bench["fleet"] = benchmark_fleet(
            model, max_wait_ms=max_wait_ms, slo_ms=slo_ms, key=key,
            block=block, fused=fused, embed_fused=embed_fused,
            interpret=interpret)
    if "backends" in modes:
        if data is None:
            bench["backends"] = {"skipped": "no (X, labels) data passed"}
        else:
            X, labels = data
            spec = model.spec
            bench["backends"] = benchmark_backends(
                X, labels, k=spec.k, r=spec.r, kernel=spec.kernel,
                kernel_params=spec.kernel_params,
                block=block or spec.block, repeats=repeats, key=key,
                interpret=interpret)
    return bench


def median_benches(benches: Sequence[Dict]) -> Dict:
    """Per-leaf median across K same-shape run_benches dicts.

    The CI regression gate diffs absolute wall-clock numbers; a single
    bench pass's async latency section moves ±50% with transient machine
    state even after min-of-N per-call timing, so serve_cluster --smoke
    runs the benches K times (warm jit caches after pass 1) and commits
    the element-wise median. Non-numeric leaves (and bools/strings) take
    the first pass's value.
    """
    import statistics

    def merge(vals):
        v0 = vals[0]
        if isinstance(v0, dict):
            # Timing-dependent sections (the async per-bucket breakdown)
            # can legitimately differ in keys across passes — a request
            # that coalesced into bucket 512 on pass 1 may land in 1024
            # on pass 2. Median over the passes that saw the key.
            return {k: merge([v[k] for v in vals
                              if isinstance(v, dict) and k in v])
                    for k in v0}
        if isinstance(v0, list):
            return [merge([v[i] for v in vals]) for i in range(len(v0))]
        if isinstance(v0, bool) or not isinstance(v0, (int, float)):
            return v0
        med = statistics.median(vals)
        # Even pass counts give float midpoints; round (not truncate)
        # integer leaves like calls / slo_violations.
        return round(med) if isinstance(v0, int) else float(med)

    benches = list(benches)
    return benches[0] if len(benches) == 1 else merge(benches)


def format_bench(bench: Dict) -> str:
    """Human-readable lines for a run_benches dict (CLI output)."""
    lines = []
    for row in bench.get("results", []):
        lines.append(f"batch {row['batch_size']:>6d} "
                     f"(bucket {row['bucket']:>5d}): "
                     f"{row['assignments_per_sec']:>12.0f} assignments/sec")
    if "async" in bench:
        a = bench["async"]
        lat = a["latency"]["latency_ms"]
        lines.append(f"async: {a['queries_per_sec']:>12.0f} queries/sec  "
                     f"p50 {lat['p50']:.2f} ms  p95 {lat['p95']:.2f} ms  "
                     f"p99 {lat['p99']:.2f} ms  SLO violations "
                     f"{a['latency']['slo_violations']}")
    if "swap" in bench:
        s = bench["swap"]
        after = (f"{s['p95_after_ms']:.2f}"
                 if s.get("p95_after_ms") is not None else "—")
        lines.append(
            f"swap: flip {s['flip_ms']:.3f} ms  warm {s['warm_s']:.3f} s "
            f"(buckets {s['buckets_warmed']})  p95 {s['p95_before_ms']:.2f}"
            f" -> {after} ms  stranded futures {s['stranded_futures']}")
    if "backends" in bench and "per_backend" in bench["backends"]:
        for name, row in sorted(bench["backends"]["per_backend"].items()):
            lines.append(
                f"backend {name:>16s}: acc {row['accuracy']:.3f}  "
                f"err {row['kernel_approx_error']:.3f}  "
                f"fit {row['fit_s']:6.2f} s / "
                f"{row['fit_memory_bytes'] / 1e6:8.2f} MB  "
                f"serve {row['assignments_per_sec']:>10.0f} q/s "
                f"(n_ref {row['n_ref']})")
    if "stream" in bench:
        st = bench["stream"]
        ro = st["rollout"]
        lines.append(
            f"stream: partial_fit {st['partial_fit_cols_per_sec']:>10.0f} "
            f"cols/sec ({st['partial_fit_chunks_per_sec']:.1f} chunks/sec "
            f"@ {st['chunk_cols']} cols)  re-eig {st['reeig_s'] * 1e3:.1f}"
            f" ms @ n={st['capacity']}")
        lines.append(
            f"  drift rollout: detect->swap {ro['detect_to_swap_s']:.3f} s"
            f" (refit {ro['refit_s']:.3f} s, publish {ro['publish_s']:.3f}"
            f" s, swap {ro['swap_s']:.3f} s)  stranded futures "
            f"{ro['stranded_futures']}")
    if "fleet" in bench:
        fl = bench["fleet"]
        for row in fl["sweep"]:
            lines.append(
                f"fleet {row['workers']} worker"
                f"{'s' if row['workers'] != 1 else ''}: "
                f"{row['queries_per_sec']:>10.0f} q/s  "
                f"p50 {row['p50_ms']:.2f} ms  p95 {row['p95_ms']:.2f} ms  "
                f"p99 {row['p99_ms']:.2f} ms")
        ov = fl["overload"]
        lines.append(
            f"  overload (depth {ov['max_queue_depth']}): shed "
            f"{ov['shed']}/{ov['offered']} ({ov['shed_rate']:.0%})  "
            f"admitted p99 {ov['admitted_p99_ms']:.2f} ms "
            f"{'<=' if ov['within_slo'] else '>'} SLO {ov['slo_ms']:.0f} ms")
        ro = fl["rollout"]
        lines.append(
            f"  rollout: promote v{ro['promote']['version']} in "
            f"{ro['promote']['wall_s']:.3f} s (canary p95 "
            f"{ro['promote']['canary_p95_ms']:.2f} ms)  rollback "
            f"v{ro['rollback']['version']} -> {ro['rollback']['state']}  "
            f"stranded futures {ro['stranded_futures']}")
    if "fit_scaling" in bench:
        fs = bench["fit_scaling"]
        for row in fs["rows"]:
            by = row["bytes"]
            lines.append(
                f"fit n={row['n']:>6d} ({fs['shards']} shard"
                f"{'s' if fs['shards'] != 1 else ''}): single "
                f"{row['single_cols_per_sec']:>9.0f} cols/sec  sharded "
                f"{row['sharded_cols_per_sec']:>9.0f} cols/sec  "
                f"block HBM {by['two_pass_bytes'] / 1e6:.2f} MB -> fused "
                f"{by['fused_bytes'] / 1e6:.2f} MB "
                f"({by['flops_per_byte_fused']:.1f} flops/B)")
    if "fused" in bench:
        f = bench["fused"]
        hbm = f["hbm"]
        interp = " (interpret)" if f["interpret"] else ""
        lines.append(
            f"fused stripe{interp}: "
            f"{f['fused']['queries_per_sec']:>10.0f} q/s  vs two-pass "
            f"{f['two_pass']['queries_per_sec']:>10.0f} q/s  "
            f"(speedup {f['speedup']:.2f}x)")
        lines.append(
            f"  stripe HBM: two-pass {hbm['two_pass_bytes'] / 1e6:.2f} MB"
            f" -> fused {hbm['fused_bytes'] / 1e6:.2f} MB  "
            f"(saves {hbm['saved_ratio']:.0%})")
    return "\n".join(lines)


def write_bench(path: str, bench: Dict) -> str:
    with open(path, "w") as f:
        json.dump(bench, f, indent=1, sort_keys=True)
        f.write("\n")
    return path
