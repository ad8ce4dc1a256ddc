#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

For every seed, in one process: the cell's set-up and a short window at
its own load, then the numbers the cell compares, first for what the
program produced and then (for --control-seeds) with the plain reference
at the precision below the configuration's standing in for the program
(float32 carried in three bfloat16 passes, XLA's `high`). Prints one
JSON line per reading and, last, the largest program reading and the
smallest control reading of each number.

Usage, from the root of a checkout, on the chip:

  python3 bench/control.py --workload mnist-fit --seconds 1 \\
      --seeds 1 2 3 4 5 6 7 8 9 10 11 12 --control-seeds 1 2 3
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

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench.lib import device, harness, spec  # noqa: E402


def readings(cell, seeds, control_seeds, seconds, devices,
             interpret=False):
    """Yield (seed, "program" | "control", {name: (value, limit)})."""
    clock = device.CompileClock()
    for seed in seeds:
        with tempfile.TemporaryDirectory(prefix="bench_control_") as tmp:
            ctx = harness.Context(cell=cell, seed=seed, seconds=seconds,
                                  devices=devices, interpret=interpret,
                                  tmp=tmp, clock=clock)
            state = cell.driver.setup(ctx)
            record = cell.driver.window(state, ctx)
            cell.driver.free(state)
            kinds = [False] + ([True] if seed in control_seeds else [])
            for control in kinds:
                checks = cell.driver.check(state, record, ctx,
                                           control=control)
                yield (seed, "control" if control else "program",
                       {name: (v, lim) for name, v, lim in checks})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    try:
        devices = device.require_tpu(cell.chips)
    except device.NoChip as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 2
    device.configure(cell.config["matmul_precision"])
    worst, least = {}, {}
    for seed, kind, values in readings(cell, args.seeds, args.control_seeds,
                                       args.seconds, devices):
        print(json.dumps({"seed": seed, "kind": kind,
                          "values": {k: v for k, (v, _) in values.items()}}),
              flush=True)
        for name, (v, _) in values.items():
            if kind == "program":
                worst[name] = max(worst.get(name, v), v)
            else:
                least[name] = min(least.get(name, v), v)
    print(json.dumps({"workload": cell.name, "program_max": worst,
                      "control_min": least,
                      "seconds": time.perf_counter() - T_START}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
