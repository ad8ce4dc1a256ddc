"""Serving launcher: fit -> persist artifact -> load -> drive query load.

End-to-end demo/check of the estimator API + repro.serve on synthetic
data, for ANY approximation backend (--backend onepass-srht |
onepass-gaussian | nystrom | exact):

  1. fit a kernel clustering through `repro.api.KernelKMeans` on
     blob+ring data,
  2. save the FittedModel artifact and load it back through the registry,
  3. verify the artifact serves correctly:
       - out-of-sample embeddings of the TRAINING points reproduce the
         fitted linearization Y (the extension identity; rel err <= 1e-4
         — gated for low-rank kernels on the training-set backends and
         for the Nystrom backend on EVERY kernel, where the identity
         holds by construction),
       - bucketed/batched assignment == unbatched assignment exactly,
  4. drive synthetic query load and write BENCH_serve.json: synchronous
     assignments/sec per batch size (--bench sync), async latency
     percentiles p50/p95/p99 + SLO accounting through AsyncBatcher
     (--bench async), the per-backend accuracy/memory/throughput sweep
     (--bench backends), or everything (--bench all, the default),
  5. verify the async path resolves futures bit-identically to a
     synchronous drain of the same requests,
  6. with --swap, exercise the model lifecycle: publish versions to a
     VersionStore (retention via --gc-keep), then warm hot-swap the live
     registry row to a pinned version while async requests are pending —
     every future resolves, post-swap labels come from the new version,
     and the SwapReport's measured flip/warm numbers are printed,
  7. with --sharded, run the extension matmul mesh-sharded over all local
     devices (set XLA_FLAGS=--xla_force_host_platform_device_count=8 to
     fake a CPU mesh) and verify it matches the single-device path,
  8. with --stream, run the streaming drift loop (repro.stream):
     partial_fit on an initial distribution, drifted synthetic traffic
     through AsyncBatcher trips the DriftMonitor (--drift-* thresholds),
     RetrainWorker refits from the accumulated sketch, publishes and
     warm-swaps — asserted: exactly one rollout, zero stranded futures,
     post-swap accuracy on the drifted distribution beats the stale
     model. `--bench stream` (in `all`) adds the partial_fit/re-eig/
     detection-to-swap numbers to BENCH_serve.json,
  9. with --fleet, run the multi-worker tier (repro.fleet):
     --fleet-workers replicas over one shared VersionStore behind the
     routed/admission-controlled front door — asserted: fleet-routed
     labels match direct assignment bit-identically, GC cannot delete a
     version the workers pin, a canary-then-promote rollout lands every
     worker on the new version with zero stranded futures, a rollout
     whose canary probe breaches the budget rolls back to the prior
     version, and a flood past a tiny admission cap sheds (typed
     ShedError) with shed_rate > 0. `--bench fleet` (in `all`) adds the
     q/s-vs-worker-count/overload/rollout soak numbers.

Usage:
  PYTHONPATH=src python -m repro.launch.serve_cluster --smoke --swap
  PYTHONPATH=src python -m repro.launch.serve_cluster --smoke --stream
  PYTHONPATH=src python -m repro.launch.serve_cluster --smoke --fleet \
      --fleet-workers 2 --bench fleet
  PYTHONPATH=src python -m repro.launch.serve_cluster --smoke \
      --backend nystrom            # full stack on a Nystrom fit
  PYTHONPATH=src python -m repro.launch.serve_cluster --n 8000 --r 2 \
      --batch-sizes 64,512,4096 --queries 8192 --bench all --slo-ms 250
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes + full round-trip verification")
    ap.add_argument("--n", type=int, default=4000, help="training points")
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--r", type=int, default=2)
    ap.add_argument("--l", type=int, default=10, help="oversampling")
    ap.add_argument("--kernel", default="polynomial")
    ap.add_argument("--degree", type=int, default=2)
    ap.add_argument("--gamma", type=float, default=None,
                    help="kernel gamma; defaults to 0.0 for polynomial, "
                         "1.0 for rbf")
    ap.add_argument("--block", type=int, default=512)
    ap.add_argument("--backend", default=None,
                    choices=["onepass-srht", "onepass-gaussian", "nystrom",
                             "exact"],
                    help="approximation backend (default: onepass-<sketch>)")
    ap.add_argument("--nystrom-m", type=int, default=None,
                    help="landmark count for --backend nystrom "
                         "(default: repro.api default, 16r floored at 64)")
    ap.add_argument("--sketch", default="srht",
                    choices=["srht", "gaussian"],
                    help="one-pass sketch type (legacy spelling of "
                         "--backend onepass-<sketch>)")
    ap.add_argument("--artifact-dir", default="serve_artifacts/demo")
    ap.add_argument("--batch-sizes", default="64,512")
    ap.add_argument("--queries", type=int, default=2048,
                    help="synthetic queries for the equality check")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--bench", default="all",
                    choices=["sync", "async", "fused", "swap", "backends",
                             "stream", "fit_scaling", "fleet", "all"],
                    help="which benchmark modes land in BENCH_serve.json")
    ap.add_argument("--swap", action="store_true",
                    help="exercise the model lifecycle: publish versions, "
                         "warm hot-swap under pending async traffic, GC")
    ap.add_argument("--fleet", action="store_true",
                    help="run the multi-worker fleet tier checks: routing "
                         "parity, gc-under-pin, canary-then-promote "
                         "rollout + probe-breached rollback, overload "
                         "shedding (all asserted)")
    ap.add_argument("--fleet-workers", type=int, default=2,
                    help="replica count for --fleet")
    ap.add_argument("--stream", action="store_true",
                    help="run the streaming drift loop demo: partial_fit "
                         "on an initial distribution, drifted async "
                         "traffic trips the DriftMonitor, RetrainWorker "
                         "refits from accumulated state, publishes and "
                         "warm-swaps — exactly one rollout, zero "
                         "stranded futures (asserted)")
    ap.add_argument("--drift-chi2", type=float, default=30.0,
                    help="assignment-shift chi-square trigger threshold")
    ap.add_argument("--drift-frac-delta", type=float, default=0.25,
                    help="max cluster-population fraction delta trigger")
    ap.add_argument("--drift-min-queries", type=int, default=64,
                    help="assignment trigger stays quiet below this "
                         "window size")
    ap.add_argument("--drift-approx-threshold", type=float, default=None,
                    help="p95 kernel-approximation-error trigger "
                         "(default: disabled — exact-rank kernels keep "
                         "residuals ~0 under any shift)")
    ap.add_argument("--gc-keep", type=int, default=None,
                    help="VersionStore retention for --swap: keep the "
                         "last K published versions")
    ap.add_argument("--fused-embed", default="auto",
                    choices=["auto", "on", "off"],
                    help="extension stripe engine for the benches: fused "
                         "Pallas (on), two-pass (off), backend default "
                         "(auto)")
    ap.add_argument("--interpret", action="store_true",
                    help="run Pallas kernels in interpret mode (forces "
                         "the Pallas path on CPU — the CI hook)")
    ap.add_argument("--async-requests", type=int, default=256,
                    help="request count for the async latency bench")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="AsyncBatcher flush deadline")
    ap.add_argument("--slo-ms", type=float, default=250.0,
                    help="latency SLO for violation accounting")
    ap.add_argument("--sharded", action="store_true",
                    help="shard the extension matmul over all local "
                         "devices (needs >= 2)")
    ap.add_argument("--bench-passes", type=int, default=None,
                    help="bench repetitions; BENCH_serve.json gets the "
                         "per-metric median. Default: 1, or 3 under "
                         "--smoke (so the CI regression gate diffs "
                         "stable numbers); an explicit value is always "
                         "honoured")
    ap.add_argument("--bench-out", default="BENCH_serve.json")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.smoke:
        args.n = min(args.n, 2000)
        args.queries = min(args.queries, 1024)
    if args.bench_passes is None:
        args.bench_passes = 3 if args.smoke else 1
    backend = args.backend or f"onepass-{args.sketch}"

    from repro.api import KernelKMeans
    from repro.data import blob_ring
    from repro.serve import (DEFAULT_REGISTRY, ComputePolicy,
                             ShardedExtender, assign, data_mesh, embed,
                             write_bench)
    from repro.serve.bench import format_bench, run_benches
    from repro.serve.extend import _projection

    key = jax.random.PRNGKey(args.seed)
    k_fit, k_query = jax.random.split(key)
    X, labels = blob_ring(key, n=args.n)
    # gamma=0.0 is the right homogeneous-polynomial default but makes rbf a
    # degenerate constant kernel — pick the per-kernel default when unset.
    gamma = args.gamma if args.gamma is not None else \
        (0.0 if args.kernel == "polynomial" else 1.0)
    params = ({"gamma": gamma, "degree": args.degree}
              if args.kernel == "polynomial" else
              {"gamma": gamma} if args.kernel == "rbf" else {})
    backend_params = {}
    if backend.startswith("onepass-"):
        backend_params["oversampling"] = args.l
    elif backend == "nystrom" and args.nystrom_m is not None:
        backend_params["m"] = args.nystrom_m

    t0 = time.time()
    est = KernelKMeans(k=args.k, r=args.r, kernel=args.kernel,
                       kernel_params=params, backend=backend,
                       backend_params=backend_params, block=args.block)
    est.fit(X, key=k_fit)
    model = est.model_
    t_fit = time.time() - t0
    print(f"fit: n={args.n} r={args.r} backend={backend} "
          f"kernel={args.kernel} ({est!r}) in {t_fit:.2f} s")

    path = est.save(args.artifact_dir)
    served = DEFAULT_REGISTRY.load("demo", path)
    print(f"artifact saved + loaded: {path}")

    # Check 1: the extension reproduces the fitted linearization Y on the
    # training points. For the training-set backends (one-pass / exact)
    # the identity y(x_j) = Y e_j is exact only when the kernel matrix is
    # numerically rank <= r' (polynomial/linear); a full-rank kernel
    # (rbf) keeps the irreducible rank-r truncation residual, so there
    # the number is reported but not gated. The Nystrom backend's fitted
    # Y IS the landmark extension evaluated on the training columns, so
    # the identity is exact for EVERY kernel and always gated.
    Y_ext = embed(served, np.asarray(X, np.float32))
    Y_fit = est.embedding_
    rel = (float(jnp.linalg.norm(Y_ext - Y_fit)) /
           float(jnp.linalg.norm(Y_fit)))
    print(f"train-point round-trip rel err: {rel:.2e}")
    if backend == "nystrom" or args.kernel in ("polynomial", "linear"):
        assert rel <= 1e-4, f"extension inconsistent with fit: {rel:.2e}"
    else:
        print("  (full-rank kernel: residual is the rank-r truncation "
              "error, not gated)")

    # Check 2: bucketed/batched == unbatched, bit-identical labels.
    Xq = jax.random.normal(k_query, (X.shape[0], args.queries), jnp.float32)
    labels_direct, _ = assign(served, Xq)
    batcher = DEFAULT_REGISTRY.batcher("demo")
    labels_bucketed, _ = batcher.assign_batch(Xq)
    # Also through the coalescing queue, as ragged concurrent requests.
    rng = np.random.RandomState(args.seed)
    splits = np.sort(rng.choice(np.arange(1, args.queries),
                                size=min(7, args.queries - 1),
                                replace=False))
    tickets = [batcher.submit(part)
               for part in np.split(np.asarray(Xq), splits, axis=1)]
    drained = batcher.drain()
    labels_queued = np.concatenate([drained[t][0] for t in tickets])
    assert np.array_equal(np.asarray(labels_direct), labels_bucketed), \
        "bucketed assignment != unbatched assignment"
    assert np.array_equal(labels_bucketed, labels_queued), \
        "queued micro-batching changed assignments"
    print(f"bucketed == unbatched == queued on {args.queries} queries "
          f"(buckets compiled: {batcher.executables})")

    # Check 3: async futures resolve bit-identically to a sync drain.
    sched = DEFAULT_REGISTRY.scheduler("demo", max_wait_ms=args.max_wait_ms,
                                       slo_ms=args.slo_ms)
    futs = [sched.submit(part)
            for part in np.split(np.asarray(Xq), splits, axis=1)]
    sched.flush()
    labels_async = np.concatenate([f.result()[0] for f in futs])
    assert np.array_equal(labels_bucketed, labels_async), \
        "async scheduling changed assignments"
    buckets_seen = sorted(sched.latency.by_bucket)
    print(f"async == sync on {args.queries} queries "
          f"({sched.latency.requests} requests recorded; per-bucket "
          f"breakdown over buckets {buckets_seen})")

    # Check 4: the mesh-sharded one-pass fit (ComputePolicy(mesh=...))
    # is bit-identical to the single-host fit — the distributed engine's
    # core contract, checked here on a 1-device mesh (CI's distributed
    # smoke runs the multi-device variant under XLA_FLAGS).
    if backend.startswith("onepass-"):
        pol = ComputePolicy(mesh=data_mesh(jax.devices()[:1]))
        est_sh = KernelKMeans(k=args.k, r=args.r, kernel=args.kernel,
                              kernel_params=params, backend=backend,
                              backend_params=backend_params,
                              block=args.block, policy=pol)
        est_sh.fit(X, key=k_fit)
        assert np.array_equal(np.asarray(est.labels_),
                              np.asarray(est_sh.labels_)), \
            "sharded fit changed training labels"
        for leaf in ("U", "eigvals", "centroids"):
            assert np.array_equal(
                np.asarray(getattr(model, leaf)),
                np.asarray(getattr(est_sh.model_, leaf))), \
                f"sharded fit changed model.{leaf}"
        print(f"sharded fit ({pol.shards} shard) bit-identical to "
              f"single-host fit")

    # Check 5 (--swap): model lifecycle — publish versions, GC, warm
    # hot-swap the live row while async requests are pending.
    if args.swap:
        from repro.serve import VersionStore
        if args.gc_keep is not None and args.gc_keep < 1:
            ap.error("--gc-keep must be >= 1")
        store = VersionStore(args.artifact_dir + "_versions",
                             keep=args.gc_keep)
        v1 = store.publish(model)
        v2 = store.publish(model)
        # A distinguishable refresh, published LAST so it survives any
        # --gc-keep >= 1: flipping the centroid rows permutes the labels,
        # so post-swap labels prove which version served.
        model_b = model._replace(centroids=model.centroids[::-1])
        v3 = store.publish(model_b)
        print(f"published v{v1}, v{v2}, v{v3} -> {store.versions()}"
              + (f" (keep={args.gc_keep})" if args.gc_keep else ""))
        if args.gc_keep:
            assert len(store.versions()) <= args.gc_keep, \
                f"GC kept {store.versions()}, wanted <= {args.gc_keep}"
        served_b = store.load(v3)                 # pinned-version read
        w = min(args.queries, 64)
        swap_splits = [w // 3, 2 * w // 3] if w >= 3 else []
        parts = np.split(np.asarray(Xq[:, :w]), swap_splits, axis=1)
        pending = [sched.submit(part) for part in parts]
        report = DEFAULT_REGISTRY.swap("demo", served_b, version=v3)
        assert all(f.done() for f in pending), \
            "swap stranded pending futures"
        old_labels = np.concatenate([f.result()[0] for f in pending])
        assert np.array_equal(old_labels,
                              np.asarray(labels_bucketed[:w])), \
            "pre-swap requests must resolve against the old version"
        sched2 = DEFAULT_REGISTRY.scheduler("demo")
        futs = [sched2.submit(part) for part in parts]
        sched2.flush()
        new_labels = np.concatenate([f.result()[0] for f in futs])
        want_new, _ = assign(served_b, Xq[:, :w])
        assert np.array_equal(new_labels, np.asarray(want_new)), \
            "post-swap requests must resolve against the new version"
        print(f"warm swap v{report.old_version} -> v{report.new_version}: "
              f"flip {report.flip_ms:.3f} ms, warm {report.warm_s:.3f} s "
              f"(buckets {report.buckets_warmed}), drained "
              f"{report.drained_requests} pending requests into the old "
              f"model; p95 before {report.p95_before_ms:.2f} ms")

    # Check 6 (--stream): the living-service loop — partial_fit on an
    # initial distribution, drifted async traffic trips the DriftMonitor,
    # RetrainWorker refits from the accumulated sketch, publishes to the
    # VersionStore and warm-swaps the registry row. Gated: exactly one
    # rollout, zero stranded futures, post-swap accuracy on the drifted
    # distribution beats the stale model.
    if args.stream:
        from repro.core.metrics import clustering_accuracy
        from repro.serve import VersionStore
        from repro.stream import DriftMonitor, RetrainWorker

        rng_s = np.random.RandomState(args.seed)

        def blobs_1d(xs, n_per=100):
            cols, labs = [], []
            for i, x0 in enumerate(xs):
                c = np.zeros((2, n_per), np.float32)
                c[0] = x0 + 0.25 * rng_s.randn(n_per)
                c[1] = 0.25 * rng_s.randn(n_per)
                cols.append(c)
                labs.append(np.full(n_per, i))
            return np.concatenate(cols, axis=1), np.concatenate(labs)

        X0, _ = blobs_1d((-2.0, 2.0))              # initial distribution
        Xd, yd = blobs_1d((3.0, 8.0))              # drifted distribution
        stream_backend = (backend if backend.startswith("onepass-")
                          else "onepass-srht")
        s_est = KernelKMeans(k=2, r=2, kernel="linear",
                             backend=stream_backend, block=64)
        s_est.partial_fit(X0, key=jax.random.fold_in(key, 7),
                          capacity=X0.shape[1] + Xd.shape[1])
        stale_acc = clustering_accuracy(yd, s_est.predict(Xd), 2)
        s_store = VersionStore(args.artifact_dir + "_stream_versions",
                               keep=args.gc_keep or 4)
        DEFAULT_REGISTRY.register("stream-demo", s_est.model_,
                                  overwrite=True,
                                  version=s_store.publish(s_est.model_))
        s_sched = DEFAULT_REGISTRY.scheduler(
            "stream-demo", max_wait_ms=args.max_wait_ms)
        mon = DriftMonitor(
            s_est.model_, ref_labels=s_est.labels_,
            approx_err_threshold=args.drift_approx_threshold,
            chi2_threshold=args.drift_chi2,
            frac_delta_threshold=args.drift_frac_delta,
            min_queries=args.drift_min_queries)
        worker = RetrainWorker(
            "stream-demo", DEFAULT_REGISTRY, s_store, mon,
            lambda rep: s_est.partial_fit(Xd).model_)

        # Healthy (shuffled) traffic first: the monitor must stay quiet.
        Xh = X0[:, rng_s.permutation(X0.shape[1])]
        chunks = [Xh[:, lo:lo + 20] for lo in range(0, 100, 20)]
        futs = [s_sched.submit(ch) for ch in chunks]
        s_sched.flush()
        for ch, f in zip(chunks, futs):
            mon.observe(ch, f.result()[0])
        assert worker.step() is None, \
            "drift monitor fired on in-distribution traffic"

        # Drifted traffic through the async front door; one request left
        # pending so the swap's drain path is exercised.
        chunks = [Xd[:, lo:lo + 20] for lo in range(0, Xd.shape[1], 20)]
        futs = [s_sched.submit(ch) for ch in chunks]
        s_sched.flush()
        for ch, f in zip(chunks, futs):
            mon.observe(ch, f.result()[0])
        pending = s_sched.submit(Xd[:, :8])
        rollout = worker.step()
        assert rollout is not None, "injected drift did not trigger"
        assert worker.step() is None and worker.retrains == 1, \
            "drift must trigger exactly one refit+swap"
        stranded = sum(not f.done() for f in futs + [pending])
        assert stranded == 0, f"{stranded} futures stranded by the swap"
        new_acc = clustering_accuracy(
            yd, KernelKMeans.from_model(
                DEFAULT_REGISTRY.get("stream-demo")).predict(Xd), 2)
        assert new_acc > stale_acc, \
            f"refit did not beat the stale model ({new_acc} vs {stale_acc})"
        print(f"stream: drift {rollout.drift.reason}; refit v"
              f"{rollout.version} detect->swap "
              f"{rollout.detect_to_swap_s:.3f} s (refit "
              f"{rollout.refit_s:.3f} s), drained "
              f"{rollout.swap.drained_requests} pending, stranded 0; "
              f"drifted-set accuracy {stale_acc:.2f} -> {new_acc:.2f}")

    # Check 7 (--fleet): the multi-worker tier — N replicas over ONE
    # shared VersionStore behind the routed/admission-controlled front
    # door. Gated: fleet labels == direct assignment, gc-under-pin,
    # canary-then-promote with zero stranded futures, probe-breached
    # rollback restoring the prior version, overload shedding.
    if args.fleet:
        from repro.fleet import Fleet, ShedError
        from repro.serve import VersionStore
        if args.fleet_workers < 1:
            ap.error("--fleet-workers must be >= 1")
        f_store = VersionStore(args.artifact_dir + "_fleet_versions")
        fv1 = f_store.publish(model)
        # rollout_budget_ms is generous on purpose: the 7c canary probe
        # pays first-flush compile spikes (cold workers, by design), and
        # this check is about the PROMOTE path; the breach path is
        # forced explicitly in 7d, machine speed must not pick for us.
        fleet = Fleet(f_store, n_workers=args.fleet_workers,
                      slo_ms=args.slo_ms, max_wait_ms=args.max_wait_ms,
                      rollout_budget_ms=60_000.0, block=args.block)
        # 7a: routing only picks the replica; results must be
        # bit-identical to direct assignment regardless of placement.
        w = min(args.queries, 64)
        f_splits = [w // 4, w // 2, 3 * w // 4] if w >= 4 else []
        parts = np.split(np.asarray(Xq[:, :w]), f_splits, axis=1)
        futs = [fleet.submit(part) for part in parts]
        fleet.flush()
        fleet_labels = np.concatenate([f.result()[0] for f in futs])
        assert np.array_equal(fleet_labels,
                              np.asarray(labels_bucketed[:w])), \
            "fleet-routed labels != direct assignment"
        assert {wk.version for wk in fleet.workers} == {fv1}
        print(f"fleet: {args.fleet_workers} workers pinned to v{fv1} "
              f"(pins: {f_store.pins(fv1)}), routed labels match "
              f"direct assignment on {w} queries")
        # 7b: GC with keep=1 would delete v1 — but every worker pins it,
        # so it must survive (the pin-refcount guard).
        model_b = model._replace(centroids=model.centroids[::-1])
        fv2 = f_store.publish(model_b)
        f_store.gc(keep=1)
        assert fv1 in f_store.versions(), \
            f"GC deleted pinned v{fv1} out from under the fleet"
        print(f"gc(keep=1) preserved pinned v{fv1} "
              f"(pins: {f_store.pins(fv1)})")
        # 7c: canary-then-promote to v2 with requests pending — every
        # worker lands on v2, the pending futures resolve (old model).
        pending = [fleet.submit(part) for part in parts]
        rollout = fleet.rollout(fv2)
        fleet.flush()
        assert rollout is not None and rollout.promoted, \
            f"canary-then-promote failed: {rollout}"
        assert all(wk.version == fv2 for wk in fleet.workers), \
            "promote left a worker on the old version"
        stranded = sum(not f.done() for f in pending)
        assert stranded == 0, f"rollout stranded {stranded} futures"
        old_roll_labels = np.concatenate([f.result()[0] for f in pending])
        assert np.array_equal(old_roll_labels,
                              np.asarray(labels_bucketed[:w])), \
            "pre-rollout requests must resolve against the old version"
        futs = [fleet.submit(part) for part in parts]
        fleet.flush()
        new_roll_labels = np.concatenate([f.result()[0] for f in futs])
        want_new, _ = assign(f_store.load(fv2), Xq[:, :w])
        assert np.array_equal(new_roll_labels, np.asarray(want_new)), \
            "post-rollout requests must resolve against the new version"
        print(f"canary-then-promote v{fv1} -> v{fv2}: "
              f"{rollout.state} in {rollout.wall_s:.3f} s "
              f"(canary {rollout.canary_id} p95 "
              f"{rollout.canary_p95_ms:.2f} ms <= budget "
              f"{rollout.budget_ms:.0f} ms), 0 stranded futures")
        # 7d: a rollout whose canary probe breaches the budget must roll
        # back — fleet stays on v2, v3 stays in the store untouched.
        fv3 = f_store.publish(model)
        bad = fleet.rollout(fv3, probe=lambda wk: float("inf"))
        assert bad is not None and bad.state == "rolled-back" \
            and not bad.promoted, f"breached canary did not roll back: {bad}"
        assert all(wk.version == fv2 for wk in fleet.workers), \
            "rollback did not restore the prior version"
        assert fv3 in f_store.versions(), "rollback deleted the target"
        print(f"breached canary rolled back: fleet stays on v{fv2}, "
              f"v{fv3} intact for a retry")
        fleet.stop()
        # 7e: overload — a flood past a tiny admission cap must shed
        # (typed ShedError), and the counters must say so.
        tiny = Fleet(f_store, n_workers=args.fleet_workers, version=fv2,
                     slo_ms=args.slo_ms, max_wait_ms=args.max_wait_ms,
                     max_queue_depth=8, block=args.block)
        shed = 0
        for i in range(32):
            try:
                tiny.submit(np.asarray(Xq[:, :4]))
            except ShedError as e:
                assert e.reason == "queue-full", e.reason
                shed += 1
        tiny.flush()
        rate = tiny.admission.shed_rate
        tiny.stop()
        assert shed > 0 and rate > 0.0, \
            f"flood past depth 8 shed nothing (shed={shed}, rate={rate})"
        print(f"overload: shed {shed}/32 requests past depth-8 caps "
              f"(shed_rate {rate:.0%}, typed ShedError)")

    # Optional: the mesh-sharded extension path against the local mesh.
    mesh = None
    if args.sharded:
        n_dev = len(jax.devices())
        if n_dev < 2:
            ap.error(f"--sharded needs >= 2 devices, have {n_dev} (set "
                     "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        mesh = data_mesh()
        ext = ShardedExtender(served, mesh)
        Y_sh = ext.embed(Xq[:, :256])
        Y_1d = embed(served, Xq[:, :256])
        rel_sh = (float(jnp.linalg.norm(Y_sh - Y_1d)) /
                  max(float(jnp.linalg.norm(Y_1d)), 1e-30))
        assert rel_sh <= 1e-5, f"sharded embed != single-device: {rel_sh:.2e}"
        print(f"sharded extension matches single-device over {n_dev} "
              f"devices (rel err {rel_sh:.2e})")

    # Benchmarks -> BENCH_serve.json (only the modes asked for run).
    batch_sizes = [int(b) for b in args.batch_sizes.split(",") if b.strip()]
    if not batch_sizes:
        ap.error(f"--batch-sizes {args.batch_sizes!r} parses to nothing")
    modes = (("sync", "async", "fused", "swap", "backends", "stream",
              "fit_scaling", "fleet")
             if args.bench == "all" else (args.bench,))
    embed_fused = {"auto": None, "on": True, "off": False}[args.fused_embed]
    from repro.serve import median_benches
    bench = median_benches([
        run_benches(served, modes=modes, batch_sizes=batch_sizes,
                    repeats=args.repeats, key=k_query, mesh=mesh,
                    embed_fused=embed_fused,
                    interpret=True if args.interpret else None,
                    n_requests=args.async_requests,
                    max_wait_ms=args.max_wait_ms, slo_ms=args.slo_ms,
                    data=(X, labels))
        for _ in range(max(args.bench_passes, 1))])
    write_bench(args.bench_out, bench)
    print(format_bench(bench))
    print(f"wrote {args.bench_out}")

    # Smoke also forces both Pallas serving paths for agreement with the
    # jnp / two-pass paths: the fused kmeans_assign argmin and the fused
    # gram->projection extend_embed stripe. Interpret mode only on the
    # CPU, where Pallas cannot compile; on a chip the compiled kernels
    # are compared.
    if args.smoke:
        interp = True if jax.default_backend() == "cpu" else None
        small = Xq[:, :256]
        lab_jnp, _ = assign(served, small,
                            policy=ComputePolicy(assign_fused=False))
        lab_pallas, _ = assign(served, small,
                               policy=ComputePolicy(assign_fused=True,
                                                    interpret=interp))
        assert np.array_equal(np.asarray(lab_jnp), np.asarray(lab_pallas)), \
            "fused Pallas assignment disagrees with jnp path"
        print("fused Pallas assignment path agrees (256 queries)")
        Y_two = embed(served, small,
                      policy=ComputePolicy(embed_fused=False))
        Y_fused = embed(served, small,
                        policy=ComputePolicy(embed_fused=True,
                                             interpret=interp))
        rel_f = (float(jnp.linalg.norm(Y_fused - Y_two)) /
                 max(float(jnp.linalg.norm(Y_two)), 1e-30))
        assert rel_f <= 1e-5, \
            f"fused extend_embed stripe != two-pass: {rel_f:.2e}"
        print(f"fused extend_embed stripe agrees (rel err {rel_f:.2e})")
        # Backend-specific ground truth: the served assignment must match
        # a direct evaluation of the backend's own extension formula
        # y(x) = Sigma^{-1/2} U^T kappa(ref, x) — for --backend nystrom
        # this is the "assign parity with a direct Nystrom embedding"
        # acceptance check.
        P = _projection(served)
        Y_direct = P @ served.kernel_fn()(served.extension_ref, small)
        d2 = (jnp.sum(Y_direct.T ** 2, 1)[:, None]
              + jnp.sum(served.centroids ** 2, 1)[None, :]
              - 2.0 * Y_direct.T @ served.centroids.T)
        lab_direct = np.asarray(jnp.argmin(d2, axis=1), np.int32)
        assert np.array_equal(lab_direct, np.asarray(lab_jnp)), \
            f"served assignment != direct {backend} embedding assignment"
        print(f"served stack agrees with the direct {backend} extension "
              f"(256 queries)")
    print("serve_cluster: OK")


if __name__ == "__main__":
    main()
