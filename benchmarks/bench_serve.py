"""Serving benchmark -> BENCH_serve.json: sync, async, and sharded modes.

Fits a model on synthetic blob+ring data through `repro.api.KernelKMeans`
(--backend picks the approximation backend), then measures:

  --mode sync     bucketed assignments/sec per batch size (MicroBatcher)
  --mode backends accuracy + fit memory + serving throughput for every
                  registered approximation backend (onepass-srht,
                  onepass-gaussian, nystrom, exact) fitted through the
                  unified KernelKMeans front door on the same data
  --mode async    request latency p50/p95/p99 + SLO accounting through
                  the deadline-driven AsyncBatcher
  --mode fused    fused gram->projection Pallas stripe vs the two-pass
                  gram+projection executables, plus the per-stripe HBM
                  delta from launch/hlo_analysis
  --mode swap     async traffic across a warm hot-swap: measured flip
                  duration + p95 before/after from the surviving
                  LatencyStats
  --mode all      all of the above (default)

--fused-embed on --interpret forces the Pallas stripe engine for the
sync/async modes even on CPU (interpret mode) — the CI hook.

Add --sharded to run the extension matmul mesh-sharded over all local
devices (set XLA_FLAGS=--xla_force_host_platform_device_count=8 to fake a
CPU mesh).

  PYTHONPATH=src python benchmarks/bench_serve.py
  PYTHONPATH=src python benchmarks/bench_serve.py --n 8000 \
      --batch-sizes 64,512,4096 --mode all --slo-ms 100 --out BENCH_serve.json
"""
from __future__ import annotations

import argparse

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--r", type=int, default=2)
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--l", type=int, default=10)
    ap.add_argument("--block", type=int, default=512)
    ap.add_argument("--backend", default="onepass-srht",
                    choices=["onepass-srht", "onepass-gaussian", "nystrom",
                             "exact"],
                    help="approximation backend the served model is "
                         "fitted with")
    ap.add_argument("--nystrom-m", type=int, default=None,
                    help="landmark count for --backend nystrom")
    ap.add_argument("--batch-sizes", default="64,512")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--mode", default="all",
                    choices=["sync", "async", "fused", "swap", "backends",
                             "all"])
    ap.add_argument("--fused-embed", default="auto",
                    choices=["auto", "on", "off"],
                    help="extension stripe engine for sync/async modes: "
                         "fused Pallas (on), two-pass (off), or the "
                         "backend default (auto)")
    ap.add_argument("--interpret", action="store_true",
                    help="run Pallas kernels in interpret mode (forces "
                         "the Pallas path on CPU)")
    ap.add_argument("--async-requests", type=int, default=256)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--slo-ms", type=float, default=250.0)
    ap.add_argument("--sharded", action="store_true",
                    help="mesh-shard the extension over all local devices")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.api import KernelKMeans
    from repro.data import blob_ring
    from repro.serve import data_mesh, write_bench
    from repro.serve.bench import format_bench, run_benches

    key = jax.random.PRNGKey(args.seed)
    X, labels = blob_ring(key, n=args.n)
    backend_params = ({"oversampling": args.l}
                      if args.backend.startswith("onepass-") else
                      {"m": args.nystrom_m}
                      if args.backend == "nystrom"
                      and args.nystrom_m is not None else {})
    est = KernelKMeans(k=args.k, r=args.r, backend=args.backend,
                       backend_params=backend_params, block=args.block)
    model = est.fit(X, key=jax.random.PRNGKey(args.seed + 1)).model_
    mesh = None
    if args.sharded:
        n_dev = len(jax.devices())
        if n_dev < 2:
            ap.error(f"--sharded needs >= 2 devices, have {n_dev}")
        mesh = data_mesh()

    modes = (("sync", "async", "fused", "swap", "backends")
             if args.mode == "all" else (args.mode,))
    embed_fused = {"auto": None, "on": True, "off": False}[args.fused_embed]
    bench = run_benches(
        model, modes=modes,
        batch_sizes=[int(b) for b in args.batch_sizes.split(",")],
        repeats=args.repeats, key=jax.random.PRNGKey(args.seed + 2),
        embed_fused=embed_fused,
        interpret=True if args.interpret else None,
        mesh=mesh, n_requests=args.async_requests,
        max_wait_ms=args.max_wait_ms, slo_ms=args.slo_ms,
        data=(X, labels))
    write_bench(args.out, bench)
    print(format_bench(bench))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
