#!/usr/bin/env python3
"""Knee sweep of a serving cell: one set-up, then one window per rate.

For each offered rate, the cell's traffic mix at that rate for
--seconds: requests sent, requests completed inside the window, the
latency median and 99th percentile, and how late the generator ran in
the first and the last quarter of the window (a backlog that grows
through the window shows as a later last quarter). The knee is the
highest rate whose completions keep up and whose lateness does not grow.

Usage, from the root of a checkout, on the chip:

  python3 bench/sweep.py --workload mnist-serve-256 --seed 5 \\
      --seconds 15 --rates 250 300 350 400
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402

# libtpu writes its logs to a fixed /tmp path unless told otherwise; a
# run writes only inside its checkout and its TMPDIR.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench.lib import device, harness, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    try:
        devices = device.require_tpu(cell.chips)
    except device.NoChip as exc:
        print(f"sweep: {exc}", file=sys.stderr)
        return 2
    device.configure(cell.config["matmul_precision"])
    drv = cell.driver
    with tempfile.TemporaryDirectory(prefix="bench_sweep_") as tmp:
        ctx = harness.Context(cell=cell, seed=args.seed,
                              seconds=args.seconds, devices=devices,
                              interpret=False, tmp=tmp,
                              clock=device.CompileClock())
        st = drv.setup(ctx)
        for rate in args.rates:
            drv.prepare(st, ctx, dict(cell.traffic, rate_per_s=rate))
            c0 = ctx.clock.count
            rec = drv.window(st, ctx)
            lat = 1e3 * rec.latency_s
            in_window = int(np.sum(st.due + rec.latency_s <= args.seconds))
            q = max(1, len(rec.late_s) // 4)
            print(json.dumps({
                "rate_per_s": rate, "sent": len(lat),
                "completed_in_window": in_window,
                "failed": rec.failed,
                "p50_ms": float(np.percentile(lat, 50)),
                "p99_ms": float(np.percentile(lat, 99)),
                "late_first_quarter_ms": 1e3 * float(np.mean(
                    rec.late_s[:q])),
                "late_last_quarter_ms": 1e3 * float(np.mean(
                    rec.late_s[-q:])),
                "compiles_in_window": ctx.clock.count - c0}), flush=True)
        drv.free(st)
    return 0


if __name__ == "__main__":
    sys.exit(main())
