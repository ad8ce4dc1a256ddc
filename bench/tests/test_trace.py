"""The reduction from a profiler trace to busy, idle and kernel time, on
a recorded chip trace and on hand-made intervals."""
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from bench.lib import kernels
from bench.lib import trace as tr

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "trace_covtype_fit.json"


@pytest.fixture(scope="module")
def recorded():
    d = json.loads(FIXTURE.read_text())
    return d, tr.from_events([tuple(o) for o in d["ops"]],
                             [tr.Span(*s) for s in d["spans"]])


def test_recorded_trace_busy_and_kernel_time(recorded):
    d, t = recorded
    assert t.devices == [0] and t.window_s == pytest.approx(0.1)
    kernel = [o for o in d["ops"] if o[1].startswith("%fit_sketch_pallas")]
    assert len(kernel) == 36
    assert t.op_seconds(kernels.fit_sketch) == pytest.approx(
        sum(o[3] - o[2] for o in kernel))
    assert sum(1 for o in t.ops if kernels.fit_sketch(o)) == 36
    busy = t.busy_mean_s()
    assert t.op_seconds(kernels.fit_sketch) < busy < t.window_s
    idle = t.idle_by_span()
    assert sum(idle.values()) == pytest.approx(t.window_s - busy)
    assert max(idle, key=idle.get) == "fit"
    assert t.top_ops(1)[0][0].startswith("%fit_sketch_pallas.1")


def test_interval_arithmetic_two_devices():
    ops = [(0, "%fusion.1 = f32[8] fusion()", 0.0, 1.0),
           (0, "%fusion.2 = f32[8] fusion()", 0.5, 2.0),
           (0, "%all-reduce.3 = f32[8] all-reduce(%fusion.2)", 1.5, 3.0),
           (1, "%extend_embed_pallas.1 = f32[8] custom-call()", 0.0, 4.0),
           (1, "%all-gather.1 = f32[8] all-gather()", 3.5, 4.5),
           (1, "%late = f32[8] fusion()", 9.0, 12.0)]
    spans = [tr.Span("window", 0.0, 10.0), tr.Span("publish", 3.0, 5.0)]
    t = tr.from_events(ops, spans)
    assert t.devices == [0, 1]
    assert t.busy_s(0) == pytest.approx(3.0)
    assert t.busy_s(1) == pytest.approx(4.5 + 1.0)       # clipped at 10
    assert t.op_seconds(kernels.extend_embed) == pytest.approx(2.0)
    idle = t.idle_by_span()
    # device 0 idles [3, 10]: 2 s in publish, 5 s elsewhere; device 1
    # idles [4.5, 9]: 0.5 s in publish, 4 s elsewhere
    assert idle["publish"] == pytest.approx((2.0 + 0.5) / 2)
    assert idle["other"] == pytest.approx((5.0 + 4.0) / 2)


def test_ops_outside_the_window_are_dropped():
    t = tr.from_events([(0, "%a = f32[] fusion()", -2.0, -1.0)],
                       [tr.Span("window", 0.0, 1.0)])
    assert t.ops == [] and t.busy_mean_s() == 0.0


def test_a_trace_needs_its_window():
    with pytest.raises(ValueError):
        tr.from_events([], [tr.Span("fit", 0.0, 1.0)])


def test_recorded_profile_keeps_the_benchmark_spans(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with tr.record(str(tmp_path)):
        with tr.span("fit"):
            f(x).block_until_ready()
    t = tr.load(str(tmp_path))
    names = [s.name for s in t.spans]
    assert "window" in names and "fit" in names
    fit = next(s for s in t.spans if s.name == "fit")
    assert t.window[0] <= fit.start <= fit.end <= t.window[1]
