"""The program's own host spans in a profiler trace, and what they show.

The program writes a TraceAnnotation named "repro.<name>" at each layer
boundary of its fit, publish and serving paths (src/repro/spans.py).
They land in the same .xplane.pb as the benchmark's "bench.<name>" spans
and the device's ops, on the same clock, so the device's idle time can
be put under the program step that held the host at that moment.

`load(trace_dir)` (or `from_profile`) gives a `Program`: the benchmark's
own `Trace` (bench/lib/trace.py) and

  spans     every "repro.*" host event: name (prefix dropped), start,
            end, host line (one per thread), args from event.stats
  compiles  every "backend_compile_and_load" host event (an XLA compile)

The rest is arithmetic on those intervals: a span's self time, idle per
innermost open span, compiles per innermost span, the split of a span
into its children, and three per-layer numbers: flush_host_ms,
store_publish_ms and fit_host_idle_ms. Each returns None when the trace
holds no span to read (a program without these spans).
`bench/spans.py` prints all of it for a kept trace.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import statistics
from typing import Dict, List, Optional, Tuple

import jax

from bench.lib import trace as tr

PREFIX = "repro."
COMPILE = "backend_compile_and_load"


@dataclasses.dataclass(frozen=True)
class HostSpan:
    name: str
    start: float
    end: float
    line: Tuple[int, int] = (0, 0)      # (plane, line): one per thread
    args: Tuple[Tuple[str, object], ...] = ()

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Program:
    trace: tr.Trace
    spans: List[HostSpan]
    compiles: List[HostSpan]

    def __post_init__(self):
        self.spans = sorted(self.spans, key=lambda s: (s.start, -s.end))
        self.compiles = sorted(self.compiles, key=lambda s: s.start)
        self._by_line: Dict[Tuple[int, int], List[HostSpan]] = {}
        for s in self.spans:
            self._by_line.setdefault(s.line, []).append(s)
        self._starts = {line: [s.start for s in ss]
                        for line, ss in self._by_line.items()}

    def named(self, name: str) -> List[HostSpan]:
        """Spans of one name that start in the window."""
        w0, w1 = self.trace.window
        return [s for s in self.spans
                if s.name == name and w0 <= s.start < w1]

    def descendants(self, span: HostSpan) -> List[HostSpan]:
        """Spans on span's line that lie inside it."""
        line = self._by_line.get(span.line, [])
        i = bisect.bisect_left(self._starts[span.line], span.start)
        out = []
        for s in line[i:]:
            if s.start > span.end:
                break
            if s is not span and s.end <= span.end:
                out.append(s)
        return out

    def children(self, span: HostSpan) -> List[HostSpan]:
        """Descendants whose innermost enclosing span is span."""
        out, reach = [], span.start
        for s in self.descendants(span):
            if s.start >= reach:
                out.append(s)
                reach = s.end
        return out

    def self_seconds(self, span: HostSpan) -> float:
        """span's duration minus what its children on its line cover."""
        return span.seconds - sum(c.seconds for c in self.children(span))


def from_profile(profile) -> Program:
    spans, compiles = [], []
    for i, plane in enumerate(profile.planes):
        if plane.name.startswith("/device:"):
            continue
        for j, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append(HostSpan(
                        e.name[len(PREFIX):], e.start_ns * 1e-9,
                        e.end_ns * 1e-9, (i, j), tuple(e.stats)))
                elif e.name == COMPILE:
                    compiles.append(HostSpan("compile", e.start_ns * 1e-9,
                                             e.end_ns * 1e-9, (i, j)))
    return Program(trace=tr.from_profile(profile), spans=spans,
                   compiles=compiles)


def load(trace_dir: str) -> Program:
    """Read the newest .xplane.pb under trace_dir."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(jax.profiler.ProfileData.from_file(
        max(paths, key=os.path.getmtime)))


# -- idle and compiles by innermost span --------------------------------------

def _innermost_segments(p: Program) -> List[Tuple[float, float, str]]:
    """(start, end, owner) over the window wherever a compile or a
    program span is open: a compile owns its time; otherwise the open
    program span that started last (the innermost on its thread, and the
    latest across threads) does, named with its prefix."""
    w0, w1 = p.trace.window
    events = []
    for kind, items in ((0, p.compiles), (1, p.spans)):
        for s in items:
            a, b = max(s.start, w0), min(s.end, w1)
            if b > a:
                events += [(a, 1, kind, s), (b, -1, kind, s)]
    events.sort(key=lambda e: (e[0], e[1]))
    out: List[Tuple[float, float, str]] = []
    compiling, open_spans = 0, set()
    for (t, step, kind, s), nxt in zip(events, events[1:] + [None]):
        if kind == 0:
            compiling += step
        elif step > 0:
            open_spans.add(s)
        else:
            open_spans.discard(s)
        if nxt is None or nxt[0] <= t or not (compiling or open_spans):
            continue
        owner = ("compile" if compiling else
                 PREFIX + max(open_spans, key=lambda o: o.start).name)
        if out and out[-1][1] == t and out[-1][2] == owner:
            out[-1] = (out[-1][0], nxt[0], owner)
        else:
            out.append((t, nxt[0], owner))
    return out


def idle_by_innermost(p: Program) -> Dict[str, float]:
    """Idle seconds per innermost open span (mean over devices).

    Idle time under a compile is "compile"; under a program span, that
    span ("repro.<name>"). The rest goes to the benchmark's own spans as
    Trace.idle_by_span() puts it ("other" outside them), so a trace with
    no program span and no compile gives exactly idle_by_span()."""
    t = p.trace
    segments = _innermost_segments(p)
    out: Dict[str, float] = {}
    for d in t.devices:
        gaps = tr._gaps(tr._union(o for o in t.ops if o.device == d),
                        t.window)
        i = 0
        for a, b, owner in segments:
            while i < len(gaps) and gaps[i][1] <= a:
                i += 1
            k = i
            while k < len(gaps) and gaps[k][0] < b:
                ov = min(b, gaps[k][1]) - max(a, gaps[k][0])
                if ov > 0:
                    out[owner] = out.get(owner, 0.0) + ov
                k += 1
    n = max(len(t.devices), 1)
    out = {k: v / n for k, v in out.items()}
    # Time under program spans counts as covered for the benchmark's
    # spans: mark it busy and attribute the rest as before.
    covered = [tr.Op(d, "", a, b) for d in t.devices
               for a, b, _ in segments]
    rest = tr.Trace(ops=t.ops + covered, spans=t.spans, window=t.window,
                    devices=t.devices).idle_by_span()
    for k, v in rest.items():
        out[k] = out.get(k, 0.0) + v
    return out


def compiles_by_span(p: Program) -> Dict[str, int]:
    """Compiles that start in the window, counted per innermost span
    open at their start on their thread ("repro.<name>"), else per
    benchmark span, else "other"."""
    w0, w1 = p.trace.window
    benchmark = [s for s in p.trace.spans if s.name != "window"]
    out: Dict[str, int] = {}
    for c in p.compiles:
        if not w0 <= c.start < w1:
            continue
        inner = [s for s in p._by_line.get(c.line, [])
                 if s.start <= c.start < s.end]
        if inner:
            key = PREFIX + max(inner, key=lambda s: s.start).name
        else:
            key = next((s.name for s in benchmark
                        if s.start <= c.start < s.end), "other")
        out[key] = out.get(key, 0) + 1
    return out


# -- splits and per-layer numbers ----------------------------------------------

def parts(p: Program, span: HostSpan) -> Dict[str, float]:
    """Seconds of span's self time ("self") and of its children, summed
    per name."""
    out = {"self": p.self_seconds(span)}
    for c in p.children(span):
        out[c.name] = out.get(c.name, 0.0) + c.seconds
    return out


def split_ms(p: Program, name: str) -> Optional[Dict[str, float]]:
    """Median milliseconds, over the spans `name` that start in the
    window, of each of their parts; None without such spans."""
    rows = [parts(p, s) for s in p.named(name)]
    if not rows:
        return None
    keys = sorted({k for r in rows for k in r})
    return {k: 1e3 * statistics.median(r.get(k, 0.0) for r in rows)
            for k in keys}


def flush_host_ms(p: Program) -> Optional[float]:
    """Median over serve.flush spans of their duration minus their
    serve.fetch descendants: the host time a flush spends while nothing
    of its own runs on the chip."""
    flushes = p.named("serve.flush")
    if not flushes:
        return None
    return 1e3 * statistics.median(
        f.seconds - sum(d.seconds for d in p.descendants(f)
                        if d.name == "serve.fetch")
        for f in flushes)


def store_publish_ms(p: Program) -> Optional[float]:
    """Median milliseconds of the store.publish spans."""
    pubs = p.named("store.publish")
    if not pubs:
        return None
    return 1e3 * statistics.median(s.seconds for s in pubs)


def fit_host_idle_ms(p: Program) -> Optional[float]:
    """Device idle milliseconds inside program fit spans (mean over
    devices), per fit that starts in the window."""
    fits, t = p.named("fit"), p.trace
    if not fits or not t.devices:
        return None
    inside = tr._union(tr.Op(0, "", max(f.start, t.window[0]),
                             min(f.end, t.window[1])) for f in fits)
    idle = 0.0
    for d in t.devices:
        gaps = tr._gaps(tr._union(o for o in t.ops if o.device == d),
                        t.window)
        idle += sum(max(0.0, min(b, g1) - max(a, g0))
                    for a, b in inside for g0, g1 in gaps
                    if g0 < b and a < g1)
    return 1e3 * idle / max(len(t.devices), 1) / len(fits)
