#!/usr/bin/env python3
"""Chip benchmark: one run of one cell of BENCHMARK.json.

Usage, from the root of a checkout:

  python3 bench/run.py --workload mnist-fit --seed 7 --seconds 30 --trace 0

Sets the cell up (data made on the device from --seed, every shape its
traffic uses warmed), measures for --seconds, checks what the timed path
produced against the plain reference, and prints one JSON object as the
last line of standard output. With --trace 1 the window is traced by the
JAX profiler and the line carries the cell's per-layer metrics instead
of its end-to-end ones. Exits nonzero, printing no result, unless JAX
finds a TPU with as many chips as the cell asks for.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402

# libtpu writes its logs to a fixed /tmp path unless told otherwise; a
# run writes only inside its checkout and its TMPDIR.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench.lib import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a cell name from BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw profiler trace in this directory")
    return ap.parse_args(argv)


if __name__ == "__main__":
    sys.exit(harness.main(parse(), T_START))
