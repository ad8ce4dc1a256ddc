#!/usr/bin/env python3
"""Where the host held the chip, by the program's own spans.

Reads a profiler trace kept by a traced run (`bench/run.py ... --trace 1
--trace-dir <dir>`) and prints one JSON object: the window, busy and
idle seconds; idle per innermost open span (a compile, else the program
span that started last, else the benchmark's span, as
bench/lib/program_spans.py sets out) beside the benchmark's own
attribution; compiles per innermost span; the median split of a flush, a
publish and a fit into their parts; the longest spans of each, split the
same way; and flush_host_ms, store_publish_ms and fit_host_idle_ms.

Usage, from the root of a checkout (no chip needed to read a trace):

  python3 bench/run.py --workload mnist-serve-256 --seed 7 --seconds 13 \\
      --trace 1 --trace-dir $TMPDIR/trace_serve
  python3 bench/spans.py $TMPDIR/trace_serve
"""
import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench.lib import program_spans as ps  # noqa: E402

SPLIT = ("serve.flush", "store.publish", "fit")
LONGEST = 5


def _by_value(d):
    return dict(sorted(d.items(), key=lambda kv: -kv[1]))


def longest(p: ps.Program, s: ps.HostSpan) -> dict:
    """One long span: where it started in the window, its args, and how
    its time splits into its parts."""
    return {"start_s": s.start - p.trace.window[0], "ms": 1e3 * s.seconds,
            **dict(s.args),
            "parts_ms": {k: 1e3 * v for k, v in ps.parts(p, s).items()}}


def summary(p: ps.Program) -> dict:
    t = p.trace
    busy = t.busy_mean_s()
    counts = {}
    for s in p.spans:
        counts[s.name] = counts.get(s.name, 0) + 1
    return {
        "window_s": t.window_s, "busy_s": busy, "idle_s": t.window_s - busy,
        "idle_by_innermost_s": _by_value(ps.idle_by_innermost(p)),
        "idle_by_benchmark_span_s": _by_value(t.idle_by_span()),
        "compiles_by_span": _by_value(ps.compiles_by_span(p)),
        "span_counts": dict(sorted(counts.items())),
        "split_ms": {name: ps.split_ms(p, name) for name in SPLIT},
        "longest": {name: [longest(p, s) for s in
                           sorted(p.named(name), key=lambda s: -s.seconds)
                           [:LONGEST]]
                    for name in SPLIT},
        "flush_host_ms": ps.flush_host_ms(p),
        "store_publish_ms": ps.store_publish_ms(p),
        "fit_host_idle_ms": ps.fit_host_idle_ms(p),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", help="a directory holding an .xplane.pb")
    args = ap.parse_args(argv)
    print(json.dumps(summary(ps.load(args.trace_dir))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
