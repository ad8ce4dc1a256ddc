"""The program's host spans (repro.spans) as a jax.profiler trace shows
them: names, nesting by thread, args, and spans that close on errors."""
import glob
import pathlib

import jax
import numpy as np
import pytest

from repro import spans
from repro.api import KernelKMeans
from repro.data import blob_ring
from repro.serve import (AsyncBatcher, ComputePolicy, MicroBatcher,
                         VersionStore)

N, P, R, K, BLOCK = 250, 2, 2, 2, 64
ROOT = pathlib.Path(__file__).resolve().parents[1]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _estimator():
    return KernelKMeans(k=K, r=R, kernel="polynomial",
                        kernel_params={"gamma": 0.0, "degree": 2},
                        backend_params={"oversampling": 10}, block=BLOCK)


@pytest.fixture(scope="module")
def data():
    X, _ = blob_ring(jax.random.PRNGKey(0), n=N)
    return X


@pytest.fixture(scope="module")
def model(data):
    return _estimator().fit(data, key=jax.random.PRNGKey(1)).model_


def _requests(widths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randn(P, w).astype(np.float32) for w in widths]


def _traced(tmp_path, fn):
    """Run fn under a jax.profiler trace; returns its result and the
    program's spans as dicts (name without the prefix, start, end, line,
    args), in start order."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = []
    for i, plane in enumerate(jax.profiler.ProfileData.from_file(path)
                              .planes):
        for j, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(spans.PREFIX):
                    found.append({"name": e.name[len(spans.PREFIX):],
                                  "start": e.start_ns, "end": e.end_ns,
                                  "line": (i, j), "args": dict(e.stats)})
    return out, sorted(found, key=lambda s: (s["start"], -s["end"]))


def _inside(child, parent):
    return (child is not parent and child["line"] == parent["line"]
            and parent["start"] <= child["start"]
            and child["end"] <= parent["end"])


def _children(parent, found, name=None):
    """Spans whose innermost enclosing span on their line is parent."""
    out = []
    for s in found:
        if not _inside(s, parent) or (name and s["name"] != name):
            continue
        if not any(_inside(s, o) and _inside(o, parent) for o in found):
            out.append(s)
    return out


def _named(found, name):
    return [s for s in found if s["name"] == name]


def test_serving_spans_name_each_flush_and_nest_under_it(tmp_path, model):
    clock = FakeClock()
    ab = AsyncBatcher(model, max_wait_ms=5.0, clock=clock, max_bucket=64)
    reqs = _requests([30, 30, 10, 5])

    def serve():
        futs = [ab.submit(r) for r in reqs[:3]]    # 70 >= 64: inline flush
        futs.append(ab.submit(reqs[3]))
        clock.t += 5e-3
        assert ab.poll() == 1                      # the deadline flush
        return [f.result(timeout=0) for f in futs]

    results, found = _traced(tmp_path, serve)
    submits = _named(found, "serve.submit")
    assert [s["args"] for s in submits] == [{"rid": i} for i in range(4)]
    full, late = _named(found, "serve.flush")
    assert full["args"] == {"trigger": "full", "first_rid": 0,
                            "requests": 3, "width": 70, "bucket": 64}
    assert late["args"] == {"trigger": "deadline", "first_rid": 3,
                            "requests": 1, "width": 5, "bucket": 8}
    # The inline flush runs inside the submit that filled the bucket;
    # the deadline flush inside no submit.
    assert _children(submits[2], found) == [full]
    assert not any(_inside(late, s) for s in submits)
    for flush, buckets in ((full, [64, 8]), (late, [8])):
        kids = _children(flush, found)
        # 70 columns run as a full 64 chunk and a 6-column remainder.
        assert [s["name"] for s in kids] == (
            ["serve.coalesce"] + ["serve.dispatch", "serve.fetch"]
            * len(buckets) + ["serve.resolve"])
        assert kids[0]["args"] == {"width": flush["args"]["width"]}
        assert [s["args"]["bucket"] for s in kids
                if s["name"] == "serve.dispatch"] == buckets
    # The spans change no result: the same requests drained in one
    # synchronous batch give the same bits.
    mb = MicroBatcher(model, max_bucket=64)
    for r in reqs[:3]:
        mb.submit(r)
    for (lab, d2), (want_lab, want_d2) in zip(results, mb.drain()):
        np.testing.assert_array_equal(lab, want_lab)
        np.testing.assert_array_equal(d2, want_d2)


def test_a_flush_that_raises_still_closes_its_spans(tmp_path, model):
    ab = AsyncBatcher(model, clock=FakeClock(), max_bucket=512)

    def failing_flush():
        ab.batcher.submit(_requests([3])[0])   # foreign: bypasses futures
        fut = ab.submit(_requests([5])[0])
        with pytest.raises(RuntimeError, match="foreign"):
            ab.flush()
        ab.submit(_requests([4])[0])
        return fut

    fut, found = _traced(tmp_path, failing_flush)
    with pytest.raises(RuntimeError):
        fut.result(timeout=0)
    flush, = _named(found, "serve.flush")
    assert flush["args"]["trigger"] == "manual"
    assert [s["name"] for s in _children(flush, found)] == [
        "serve.coalesce", "serve.dispatch", "serve.fetch"]
    # The submit after the failure is a sibling, not a child, of the
    # failed flush: every span the flush opened was closed.
    after = _named(found, "serve.submit")[-1]
    assert after["args"] == {"rid": 1} and after["start"] >= flush["end"]


def test_fit_and_publish_spans(tmp_path, data):
    store = VersionStore(str(tmp_path / "store"), keep=1)

    def fit_and_publish():
        est = _estimator().fit(data, key=jax.random.PRNGKey(1))
        return [store.publish(est.model_) for _ in range(2)]

    versions, found = _traced(tmp_path / "trace", fit_and_publish)
    assert versions == [1, 2]
    fit, = _named(found, "fit")
    assert fit["args"] == {"n": N, "p": P, "backend": "onepass-srht"}
    kids = _children(fit, found)
    assert [s["name"] for s in kids] == [
        "fit.accumulate", "fit.eig", "fit.kmeans", "fit.package"]
    # 250 columns: three whole blocks while accumulating, the 58-column
    # tail applied on a copy inside eig.
    assert [s["args"] for s in _children(kids[0], found)] == [
        {"q": 0, "b": 64}, {"q": 64, "b": 64}, {"q": 128, "b": 64}]
    assert [s["args"] for s in _children(kids[1], found, "fit.block")] == [
        {"q": 192, "b": 58}]

    publishes = _named(found, "store.publish")
    assert [s["args"] for s in publishes] == [{"version": 1},
                                              {"version": 2}]
    removed = []
    for pub in publishes:
        kids = _children(pub, found)
        assert [s["name"] for s in kids] == [
            "store.fetch", "store.write", "store.write", "store.commit",
            "store.gc"]
        fetch, leaves, meta = kids[:3]
        assert fetch["args"]["bytes"] == leaves["args"]["bytes"] > 4 * N * P
        assert 0 < meta["args"]["bytes"] < leaves["args"]["bytes"]
        removed.append(kids[-1]["args"]["removed"])
    assert removed == [0, 1]                   # keep-last-1 drops v_1


def test_fused_fit_blocks_carry_their_tile_counts(tmp_path, data):
    """On the fit_sketch path each fit.block says how many of the
    kernel's row tiles it visits: those holding [0, q+b), of 1 here
    (250 columns fit one 256-row tile)."""
    est = KernelKMeans(k=K, r=R, kernel="rbf", kernel_params={"gamma": 0.5},
                       backend_params={"oversampling": 10}, block=BLOCK,
                       policy=ComputePolicy(fit_fused=True, interpret=True))
    _, found = _traced(tmp_path, lambda: est.fit(
        data, key=jax.random.PRNGKey(1)))
    assert [s["args"] for s in _named(found, "fit.block")] == [
        {"q": q, "b": b, "tiles": 1, "tiles_total": 1}
        for q, b in ((0, 64), (64, 64), (128, 64), (192, 58))]


def test_prefix_is_the_one_the_benchmark_reads(monkeypatch):
    monkeypatch.syspath_prepend(str(ROOT))
    from bench.lib import program_spans
    assert program_spans.PREFIX == spans.PREFIX == "repro."
