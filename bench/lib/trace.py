"""Profiler trace of the window, reduced to device and host intervals.

`record(dir)` wraps the window in `jax.profiler.trace`; the benchmark's
own host spans (`span(name)`, written as TraceAnnotations named
"bench.<name>") land in the same trace on the same clock. `load()` reads
the `.xplane.pb` back with `jax.profiler.ProfileData` and keeps:

  ops    every event on a device plane's "XLA Ops" line, clipped to the
         window: device index, the op's HLO text, start, end (seconds)
  spans  the benchmark's host spans, "bench.window" bounding the window

Everything else here is arithmetic on those intervals: busy time is the
union of a device's op intervals, idle gaps are the window minus that
union, attributed to the host span they fall in.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
from typing import Callable, Dict, Iterable, List, Optional, Tuple

import jax

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
OP_LABEL = 96               # characters of an op's HLO text in a breakdown


@dataclasses.dataclass(frozen=True)
class Op:
    device: int
    name: str
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


@contextlib.contextmanager
def span(name: str):
    """A host span of the benchmark's own, visible in the trace."""
    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        yield


def _device_index(plane_name: str) -> Optional[int]:
    head = "/device:TPU:"
    if not plane_name.startswith(head):
        return None
    tail = plane_name[len(head):]
    return int(tail) if tail.isdigit() else None


def hlo_name(op: "Op") -> str:
    """The op's HLO instruction name: the text before " = "."""
    return op.name.split(" = ", 1)[0]


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Span]
    window: Tuple[float, float]
    devices: List[int]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    # -- busy and idle ----------------------------------------------------

    def busy_s(self, device: int,
               match: Optional[Callable[[Op], bool]] = None) -> float:
        return _length(_union(o for o in self.ops if o.device == device
                              and (match is None or match(o))))

    def busy_mean_s(self) -> float:
        return (sum(self.busy_s(d) for d in self.devices)
                / max(len(self.devices), 1))

    def op_seconds(self, match: Callable[[Op], bool]) -> float:
        """Summed durations of matching ops, averaged over devices."""
        return (sum(o.seconds for o in self.ops if match(o))
                / max(len(self.devices), 1))

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds per host span (mean over devices); idle time in
        no span of the benchmark's own is "other"."""
        spans = [s for s in self.spans if s.name != "window"]
        out: Dict[str, float] = {}
        for d in self.devices:
            busy = _union(o for o in self.ops if o.device == d)
            for g0, g1 in _gaps(busy, self.window):
                covered = 0.0
                for s in spans:
                    ov = min(g1, s.end) - max(g0, s.start)
                    if ov > 0:
                        out[s.name] = out.get(s.name, 0.0) + ov
                        covered += ov
                if g1 - g0 - covered > 0:
                    out["other"] = out.get("other", 0.0) + g1 - g0 - covered
        n = max(len(self.devices), 1)
        return {k: v / n for k, v in out.items()}

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        """The k ops that took most device time (mean over devices), each
        named by the head of its HLO text."""
        tot: Dict[str, float] = {}
        for o in self.ops:
            key = o.name[:OP_LABEL]
            tot[key] = tot.get(key, 0.0) + o.seconds
        n = max(len(self.devices), 1)
        return sorted(((name, s / n) for name, s in tot.items()),
                      key=lambda t: -t[1])[:k]


# -- interval arithmetic ------------------------------------------------------

def _union(ops: Iterable) -> List[Tuple[float, float]]:
    ivs = sorted((o.start, o.end) for o in ops)
    out: List[List[float]] = []
    for a, b in ivs:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _length(ivs) -> float:
    return sum(b - a for a, b in ivs)


def _gaps(busy, window) -> List[Tuple[float, float]]:
    out, t = [], window[0]
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if window[1] > t:
        out.append((t, window[1]))
    return out


# -- recording and loading ------------------------------------------------------

@contextlib.contextmanager
def record(trace_dir: str):
    """Trace the enclosed window into trace_dir."""
    jax.profiler.start_trace(trace_dir)
    try:
        with span("window"):
            yield
    finally:
        jax.profiler.stop_trace()


def load(trace_dir: str) -> Trace:
    """Read the newest .xplane.pb under trace_dir."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(jax.profiler.ProfileData.from_file(
        max(paths, key=os.path.getmtime)))


def from_profile(profile) -> Trace:
    raw_ops, spans = [], []
    for plane in profile.planes:
        dev = _device_index(plane.name)
        for line in plane.lines:
            if dev is not None and line.name == OPS_LINE:
                for e in line.events:
                    s = e.start_ns * 1e-9
                    raw_ops.append((dev, e.name, s,
                                    s + e.duration_ns * 1e-9))
            elif dev is None:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns * 1e-9
                        spans.append(Span(e.name[len(SPAN_PREFIX):], s,
                                          s + e.duration_ns * 1e-9))
    return from_events(raw_ops, spans)


def from_events(raw_ops, spans: List[Span]) -> Trace:
    """Build a Trace from (device, HLO text, start_s, end_s) tuples and
    host spans; ops are clipped to the "window" span."""
    windows = [s for s in spans if s.name == "window"]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    w = (windows[0].start, windows[0].end)
    ops = []
    for dev, name, a, b in raw_ops:
        a, b = max(a, w[0]), min(b, w[1])
        if b > a:
            ops.append(Op(int(dev), str(name), a, b))
    devices = sorted({int(r[0]) for r in raw_ops})
    return Trace(ops=ops, spans=sorted(spans, key=lambda s: s.start),
                 window=w, devices=devices)
