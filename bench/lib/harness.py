"""One run of one cell: set up, measure, check, print the result line.

A driver module (bench/drivers/<kind>.py) supplies the cell's work:

    setup(ctx) -> state            data, program objects, every shape warmed
    window(state, ctx) -> record   the measured window, ctx.seconds long
    end_to_end(state, record)      {metric: value} of the cell's own metrics
    counters(state, record)        what per-layer readers may read
    free(state)                    drop the program's state, keep the data
    check(state, record, ctx)      [(name, value, limit)], after free()
    attempted(record), failed(record)

Per-layer readers (bench/metrics/<name>.py) expose read(run) -> float or
None, where run has .trace (the reduced profile, or None), .counters,
.cell, .device_kind and .chips. None means nothing to read, and the
metric is left out of the line.
"""
from __future__ import annotations

import dataclasses
import json
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

from bench.lib import device, spec
from bench.lib import trace as tr


@dataclasses.dataclass
class Context:
    cell: spec.Cell
    seed: int
    seconds: float
    devices: List
    interpret: bool
    tmp: str
    clock: Optional[device.CompileClock] = None

    def log(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Run:
    cell: spec.Cell
    trace: Optional[tr.Trace]
    counters: Dict
    device_kind: str
    chips: int


def _fmt(v) -> str:
    return repr(float(v)) if isinstance(v, (int, float)) else str(v)


def run(args, t_start: float, root=spec.ROOT, require_chip: bool = True,
        interpret: bool = False) -> Optional[Dict]:
    """Run one cell; returns the result dict (None when there is no chip,
    after saying why on stderr)."""
    cell = spec.resolve(args.workload, root)
    import jax

    if require_chip:
        try:
            devices = device.require_tpu(cell.chips)
        except device.NoChip as exc:
            print(f"bench: {exc}", file=sys.stderr)
            return None
    else:
        devices = jax.devices()[:cell.chips]
    cache = device.configure(cell.config["matmul_precision"])
    clock = device.CompileClock()
    with tempfile.TemporaryDirectory(prefix="bench_") as tmp:
        ctx = Context(cell=cell, seed=args.seed, seconds=float(args.seconds),
                      devices=devices, interpret=interpret, tmp=tmp,
                      clock=clock)
        ctx.log(f"bench: cell {cell.name} on {device.info(devices)} at "
                f"{time.perf_counter() - t_start:.3f} s; compile cache "
                f"{cache}")
        state = cell.driver.setup(ctx)
        setup_s = time.perf_counter() - t_start
        ctx.log(f"bench: setup {setup_s:.3f} s, {clock.count} compiles "
                f"({clock.secs:.3f} s), {clock.cache_hits} cache hits; "
                f"{device.written_bytes()} bytes written")
        c0, h0 = clock.count, clock.cache_hits
        trace_dir = None
        if args.trace:
            trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="trace_",
                                                           dir=tmp)
            with tr.record(trace_dir):
                record = cell.driver.window(state, ctx)
        else:
            record = cell.driver.window(state, ctx)
        ctx.log(f"bench: window compiled {clock.count - c0} programs "
                f"({clock.cache_hits - h0} from the cache); "
                f"{device.written_bytes()} bytes written")
        peak = device.memory_peak_bytes(devices)
        dev = dict(device.info(devices), memory_peak_bytes=peak)
        if args.trace:
            t_read = time.perf_counter()
            trace = tr.load(trace_dir)
            if not args.trace_dir:
                shutil.rmtree(trace_dir, ignore_errors=True)
            r = Run(cell=cell, trace=trace,
                    counters=cell.driver.counters(state, record),
                    device_kind=dev["kind"], chips=len(devices))
            metrics = {}
            for m in cell.per_layer:
                value = cell.readers[m["name"]].read(r)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
            dev["busy_s"] = trace.busy_mean_s()
            dev["window_s"] = trace.window_s
            breakdown = {"device_ops": [[n, s] for n, s in trace.top_ops()],
                         "idle_gaps": sorted(
                             ([n, s] for n, s in
                              trace.idle_by_span().items()),
                             key=lambda t: -t[1])[:10]}
            ctx.log(f"bench: trace read in "
                    f"{time.perf_counter() - t_read:.3f} s; busy "
                    f"{dev['busy_s']!r} of {dev['window_s']!r} s")
        else:
            values = dict(cell.driver.end_to_end(state, record),
                          setup_s=setup_s)
            metrics = {m["name"]: {"value": float(values[m["name"]]),
                                   "unit": m["unit"]}
                       for m in cell.end_to_end}
            breakdown = None
        cell.driver.free(state)
        checks = cell.driver.check(state, record, ctx)
    correct = (cell.driver.failed(record) == 0 and bool(checks)
               and all(v <= lim for _, v, lim in checks))
    result = {"correct": correct,
              "attempted": cell.driver.attempted(record),
              "failed": cell.driver.failed(record),
              "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": float(v), "limit": float(lim)}
                        for name, v, lim in checks}
    for name, v, lim in checks:
        print(f"check {name}: {_fmt(v)} (limit {_fmt(lim)}) "
              f"{'ok' if v <= lim else 'FAIL'}", file=sys.stderr,
              flush=True)
    return result


def main(args, t_start: float) -> int:
    result = run(args, t_start)
    if result is None:
        return 2
    print(json.dumps(result), flush=True)
    return 0
