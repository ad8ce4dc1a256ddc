"""The program's spans in a trace: innermost attribution of idle time and
compiles, self time, the three per-layer numbers, and a trace without
program spans reading exactly as bench/lib/trace.py reads it."""
import argparse
import json
import pathlib
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from bench import spans as cli
from bench.lib import harness
from bench.lib import program_spans as ps
from bench.lib import trace as tr
from bench.tests.checkout import make_checkout
from repro.spans import span

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "trace_covtype_fit.json"
A, B, C = (0, 1), (0, 2), (0, 3)        # three host threads


def _serving_program():
    """Device 0 busy [0, 1] and [9, 10] of a 10 s window; the benchmark's
    generate span over [1, 8]; on thread A a submit holding a flush
    holding a fetch, with a compile inside the fetch; on thread B a
    later flush with its own fetch."""
    ops = [(0, "%a = f32[] fusion()", 0.0, 1.0),
           (0, "%b = f32[] fusion()", 9.0, 10.0)]
    bench = [tr.Span("window", 0.0, 10.0), tr.Span("generate", 1.0, 8.0)]
    spans = [ps.HostSpan("serve.submit", 2.0, 7.0, A, (("rid", 0),)),
             ps.HostSpan("serve.flush", 3.0, 6.0, A),
             ps.HostSpan("serve.fetch", 4.0, 5.0, A),
             ps.HostSpan("serve.flush", 5.5, 7.5, B),
             ps.HostSpan("serve.fetch", 6.0, 6.5, B)]
    compiles = [ps.HostSpan("compile", 4.5, 4.8, A),
                ps.HostSpan("compile", 1.5, 1.6, C),
                ps.HostSpan("compile", 8.5, 8.6, C),
                ps.HostSpan("compile", -1.0, -0.5, C)]
    return ps.Program(tr.from_events(ops, bench), spans, compiles)


def test_idle_goes_to_the_innermost_open_span():
    p = _serving_program()
    idle = ps.idle_by_innermost(p)
    assert idle == pytest.approx({
        "generate": 0.9 + 0.5,            # [1, 2] but its compile; [7.5, 8]
        "repro.serve.submit": 1.0,        # [2, 3]
        # A [3, 4] and [5, 5.5]; B from its start on, but its fetch
        "repro.serve.flush": 1.0 + 0.5 + 1.5,
        "repro.serve.fetch": 0.5 + 0.2 + 0.5,  # A around its compile; B
        "compile": 0.3 + 0.1 + 0.1,       # beats every span, any thread
        "other": 0.9})                    # [8, 9] but a compile
    assert sum(idle.values()) == pytest.approx(
        p.trace.window_s - p.trace.busy_mean_s())


def test_compiles_count_under_their_innermost_span():
    assert ps.compiles_by_span(_serving_program()) == {
        "repro.serve.fetch": 1, "generate": 1, "other": 1}


def test_self_time_and_children():
    p = _serving_program()
    submit, flush_a = p.spans[0], p.spans[1]
    assert [s.name for s in p.children(submit)] == ["serve.flush"]
    assert [s.name for s in p.descendants(submit)] == ["serve.flush",
                                                       "serve.fetch"]
    assert p.self_seconds(submit) == pytest.approx(2.0)
    assert p.self_seconds(flush_a) == pytest.approx(2.0)
    assert dict(submit.args) == {"rid": 0}
    assert ps.split_ms(p, "serve.flush") == pytest.approx(
        {"self": 1750.0, "serve.fetch": 750.0})


def test_flush_host_ms_leaves_out_the_fetch():
    # flush A: 3 s less its 1 s fetch; flush B: 2 s less 0.5 s
    assert ps.flush_host_ms(_serving_program()) == pytest.approx(1750.0)


def test_store_publish_ms_is_the_median_publish_in_the_window():
    spans = [ps.HostSpan("store.publish", s, s + d)
             for s, d in ((-1.0, 5.0), (1.0, 0.1), (2.0, 0.3), (3.0, 0.2))]
    spans.append(ps.HostSpan("store.fetch", 2.0, 2.1))
    p = ps.Program(tr.from_events([], [tr.Span("window", 0.0, 10.0)]),
                   spans, [])
    assert ps.store_publish_ms(p) == pytest.approx(200.0)


def test_fit_host_idle_ms_per_fit_mean_over_devices():
    ops = [(0, "%a = f32[] fusion()", 0.0, 2.0),
           (0, "%b = f32[] fusion()", 3.0, 4.0),
           (1, "%c = f32[] fusion()", 0.0, 10.0)]
    fits = [ps.HostSpan("fit", 1.0, 5.0), ps.HostSpan("fit", 6.0, 7.0),
            ps.HostSpan("fit.eig", 4.0, 5.0)]
    p = ps.Program(tr.from_events(ops, [tr.Span("window", 0.0, 10.0)]),
                   fits, [])
    # device 0 idles [2, 3], [4, 5] and [6, 7] inside fits, device 1
    # never: 1.5 s a device over two fits
    assert ps.fit_host_idle_ms(p) == pytest.approx(750.0)


def test_without_program_spans_nothing_is_read():
    p = ps.Program(tr.from_events([], [tr.Span("window", 0.0, 1.0)]),
                   [], [])
    assert ps.flush_host_ms(p) is None
    assert ps.store_publish_ms(p) is None
    assert ps.fit_host_idle_ms(p) is None
    assert ps.split_ms(p, "serve.flush") is None
    assert ps.compiles_by_span(p) == {}


def test_recorded_trace_reads_as_before():
    d = json.loads(FIXTURE.read_text())
    t = tr.from_events([tuple(o) for o in d["ops"]],
                       [tr.Span(*s) for s in d["spans"]])
    p = ps.Program(t, [], [])
    assert ps.idle_by_innermost(p) == t.idle_by_span()
    assert t.busy_mean_s() == pytest.approx(0.09085211399999997, rel=1e-12)
    assert t.idle_by_span() == pytest.approx(
        {"fit": 0.009121776000000031, "other": 2.610999999999998e-05},
        rel=1e-12)


def test_a_recorded_profile_keeps_program_spans_and_compiles(tmp_path):
    f = jax.jit(lambda x: jnp.cos(x) @ x)
    x = jnp.ones((32, 32))

    def other_thread():
        with span("fit.block", q=0, b=32):
            time.sleep(0.01)

    with tr.record(str(tmp_path)):
        with tr.span("generate"):
            with span("serve.flush", trigger="full", width=32):
                f(x).block_until_ready()         # compiles here
        worker = threading.Thread(target=other_thread)
        worker.start()
        worker.join()
    p = ps.load(str(tmp_path))
    flush, = p.named("serve.flush")
    block, = p.named("fit.block")
    assert dict(flush.args) == {"trigger": "full", "width": 32}
    assert dict(block.args) == {"q": 0, "b": 32}
    assert flush.line != block.line
    assert ps.compiles_by_span(p).get("repro.serve.flush", 0) >= 1
    assert any(c.start >= flush.start and c.end <= flush.end
               for c in p.compiles)


@pytest.mark.parametrize("cell,numbers,parent,names", [
    ("tiny-serve-fixed", ("flush_host_ms",), "serve.flush",
     ("serve.submit", "serve.flush", "serve.coalesce", "serve.dispatch",
      "serve.fetch", "serve.resolve")),
    ("tiny-fit", ("store_publish_ms",), "store.publish",
     ("fit", "fit.accumulate", "fit.block", "fit.eig", "fit.kmeans",
      "fit.package", "store.publish", "store.fetch", "store.write",
      "store.commit", "store.gc"))])
def test_a_kept_trace_of_a_cell_names_the_program_spans(
        tmp_path, cell, numbers, parent, names):
    root = make_checkout(tmp_path / "checkout")
    keep = tmp_path / "trace"
    args = argparse.Namespace(workload=cell, seed=2**33 + 5, seconds=1.0,
                              trace=1, trace_dir=str(keep))
    result = harness.run(args, time.perf_counter(), root=root,
                         require_chip=False, interpret=True)
    assert result["correct"], result["checks"]
    out = cli.summary(ps.load(str(keep)))
    # The CPU's trace has no device line, so nothing reads as idle.
    assert out["idle_by_innermost_s"] == {} and out["busy_s"] == 0.0
    for name in numbers:
        assert out[name] is not None and out[name] > 0, name
    assert set(out["span_counts"]) >= set(names)
    assert out["split_ms"][parent] is not None
