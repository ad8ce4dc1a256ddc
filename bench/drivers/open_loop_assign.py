"""Open-loop assignment against a published model.

Set-up: one fit of the configuration's data, VersionStore.publish,
ModelRegistry.load_version, the registry's AsyncBatcher with every
program the mix can run warmed (see prepare), and a pool of query
columns (seeded training points plus noise) on the host.

Window: requests are due on a fixed schedule and are sent as they fall
due, whether or not earlier ones have come back (an open loop). Every
seed gets the same set of gaps and widths, in its own order: the gaps
are the quantiles of an exponential at rate_per_s (Poisson arrivals),
the widths the quantiles of a log-uniform on [min_width, max_width].
Each request is a slice of the pool at a seeded offset. A request is
timed from when it was due to when its future resolved; one that fails
or has not resolved by the end of the grace counts as failed and as
missing the tail.

Traffic keys: rate_per_s, min_width, max_width, max_wait_ms, min_bucket,
max_bucket, pool_columns, noise, grace_s, requests_checked.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time
from typing import List, Optional

import jax
import numpy as np

from bench.lib import clustering, data, device
from bench.lib import trace as tr

MODEL = "model"
SERVED_FIT = 1 << 20        # job index of the served model's fit key


@dataclasses.dataclass
class State:
    n: int
    X: jax.Array
    gamma: float
    fit_key: jax.Array
    pool: np.ndarray
    registry: Optional[object]
    model: Optional[object]
    version: int
    policy: object
    block: int
    sched: Optional[object] = None
    due: Optional[np.ndarray] = None
    widths: Optional[np.ndarray] = None
    offsets: Optional[np.ndarray] = None


@dataclasses.dataclass
class Record:
    latency_s: np.ndarray
    late_s: np.ndarray
    results: List
    failed: int
    counters: dict


def schedule(traffic, seed: int, seconds: float):
    """(due offsets s, widths, pool offsets) of every request."""
    rate = float(traffic["rate_per_s"])
    count = max(1, int(round(rate * seconds)))
    u = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-u) / rate
    lo, hi = math.log(traffic["min_width"]), math.log(traffic["max_width"])
    widths = np.rint(np.exp(lo + (hi - lo) * u)).astype(np.int64)
    gaps = data.host_rng(seed, 2).permutation(gaps)
    widths = data.host_rng(seed, 3).permutation(widths)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    offsets = data.host_rng(seed, 4).integers(
        0, int(traffic["pool_columns"]) - widths + 1)
    return due, widths, offsets


def setup(ctx) -> State:
    device.use_program()
    from repro.serve import ModelRegistry, VersionStore

    cfg, tf = ctx.cell.config, ctx.cell.traffic
    n = int(cfg["n"])
    X, gamma, k_jobs = clustering.make_data(cfg, ctx.seed, n)
    pol = clustering.policy(cfg, ctx.interpret)
    fit_key = jax.random.fold_in(k_jobs, SERVED_FIT)
    t0 = time.perf_counter()
    est = clustering.estimator(cfg, gamma, pol).fit(X, key=fit_key)
    store = VersionStore(f"{ctx.tmp}/store")
    version = store.publish(est.model_)
    del est
    registry = ModelRegistry()
    model = registry.load_version(MODEL, str(store.root), version)
    pool = np.asarray(data.query_pool(
        X, data.root_key(ctx.seed, 5), float(tf["noise"]),
        total=int(tf["pool_columns"])))
    st = State(n=n, X=X, gamma=gamma, fit_key=fit_key, pool=pool,
               registry=registry, model=model, version=version, policy=pol,
               block=int(cfg["block"]))
    t1 = time.perf_counter()
    warmed = prepare(st, ctx, tf)
    ctx.log(f"open_loop_assign: n={n} p={cfg['p']} gamma={gamma!r}; fit + "
            f"publish + load {t1 - t0:.3f} s, warm widths {warmed} "
            f"{time.perf_counter() - t1:.3f} s; {len(st.due)} requests, "
            f"{int(st.widths.sum())} columns, widths "
            f"{int(st.widths.min())}..{int(st.widths.max())}")
    return st


def prepare(st: State, ctx, tf) -> List[int]:
    """A fresh AsyncBatcher on the published model with every program the
    mix can run warmed, and the schedule at tf's rate. Returns the widths
    warmed.

    Requests of one fixed width w coalesce into flushes of k x w columns,
    and the batcher compiles a few small programs for every new flush
    width, so each such width up to two buckets is run once here. A mix
    of widths warms the buckets alone."""
    from repro.serve.batcher import bucket_size

    st.registry.register(MODEL, st.model, overwrite=True,
                         version=st.version)
    st.sched = st.registry.scheduler(MODEL, policy=st.policy,
                                     max_wait_ms=float(tf["max_wait_ms"]),
                                     min_bucket=int(tf["min_bucket"]),
                                     max_bucket=int(tf["max_bucket"]))
    top = int(tf["max_bucket"])
    if tf["min_width"] == tf["max_width"]:
        w = int(tf["min_width"])
        warmed = list(range(w, 2 * top, w))
        for width in warmed:
            st.sched.batcher.assign_batch(
                jax.numpy.zeros((st.X.shape[0], width), jax.numpy.float32))
    else:
        lo = bucket_size(int(tf["min_width"]), int(tf["min_bucket"]), top)
        warmed = [b for b in (lo << i for i in range(32)) if b <= top]
        st.sched.batcher.warm(warmed)
    st.sched.batcher.reset_stats(preserve_buckets=True)
    st.due, st.widths, st.offsets = schedule(tf, ctx.seed, ctx.seconds)
    return warmed


def window(st: State, ctx) -> Record:
    tf = ctx.cell.traffic
    sched = st.sched
    count = len(st.due)
    done = np.full(count, np.nan)
    late = np.zeros(count)
    futures = []

    def on_done(i, due_at):
        def cb(_):
            done[i] = time.perf_counter() - due_at
        return cb

    sched.start()
    t0 = time.perf_counter()
    for i in range(count):
        due_at = t0 + st.due[i]
        with tr.span("wait"):
            pause = due_at - time.perf_counter()
            if pause > 0:
                time.sleep(pause)
        with tr.span("generate"):
            late[i] = time.perf_counter() - due_at
            o, w = int(st.offsets[i]), int(st.widths[i])
            fut = sched.submit(st.pool[:, o:o + w])
            fut.add_done_callback(on_done(i, due_at))
            futures.append(fut)
    close = t0 + ctx.seconds
    with tr.span("wait"):
        deadline = close + float(tf["grace_s"])
        for fut in futures:
            left = deadline - time.perf_counter()
            if left <= 0:
                break
            try:
                fut.exception(timeout=left)
            except TimeoutError:
                break
    sched.stop()
    results, failed = [], 0
    latency = np.empty(count)
    for i, fut in enumerate(futures):
        ok = fut.done() and fut.exception() is None
        results.append(fut.result() if ok else None)
        latency[i] = (done[i] if ok and not np.isnan(done[i])
                      else deadline - (t0 + st.due[i]))
        failed += 0 if ok else 1
    stats = sched.batcher.stats
    stripes = sum(hits * (b // min(st.block, b))
                  for b, hits in stats["bucket_hits"].items())
    counters = {"kind": "serve", "n": st.n, "queries": stats["queries"],
                "padded": stats["padded_queries"], "stripes": stripes,
                "requests_recorded": sched.latency.requests,
                "queue_wait_p99_ms": sched.latency.queue_wait.percentile(99)}
    ctx.log(f"open_loop_assign: generator lateness p50 "
            f"{1e3 * np.percentile(late, 50):.3f} ms, p99 "
            f"{1e3 * np.percentile(late, 99):.3f} ms, max "
            f"{1e3 * late.max():.3f} ms; {failed} of {count} failed; "
            f"batcher {stats['batches']} batches, buckets "
            f"{dict(sorted(stats['bucket_hits'].items()))}")
    return Record(latency_s=latency, late_s=late, results=results,
                  failed=failed, counters=counters)


def end_to_end(st: State, rec: Record) -> dict:
    ms = 1e3 * rec.latency_s
    return {"assign_p50_ms": float(np.percentile(ms, 50)),
            "assign_p99_ms": float(np.percentile(ms, 99))}


def counters(st: State, rec: Record) -> dict:
    return rec.counters


def attempted(rec: Record) -> int:
    return len(rec.latency_s)


def failed(rec: Record) -> int:
    return rec.failed


def free(st: State) -> None:
    st.registry.unregister(MODEL)
    st.registry = st.sched = st.model = None
    gc.collect()


def sample(st: State, rec: Record, seed: int, count: int) -> np.ndarray:
    """Indices of the requests compared: drawn from the seed, with the
    widest request among them."""
    rng = data.host_rng(seed, 6)
    picked = set(rng.choice(len(st.widths), min(count, len(st.widths)),
                            replace=False).tolist())
    picked.add(int(np.argmax(st.widths)))
    return np.array(sorted(picked))


def check(st: State, rec: Record, ctx, control: bool = False) -> list:
    """Labels and squared distances served for the sampled requests
    against the plain reference: its own fit under the served model's
    key, the out-of-sample extension, the nearest centroid. With control,
    the reference at the precision below the configuration's stands in
    for the program."""
    cfg, ref = ctx.cell.config, ctx.cell.reference
    limits = ctx.cell.limits()
    picked = [i for i in sample(st, rec, ctx.seed,
                                int(ctx.cell.traffic["requests_checked"]))
              if rec.results[i] is not None]
    if not picked:
        return []
    t0 = time.perf_counter()
    Xq = np.concatenate([st.pool[:, st.offsets[i]:st.offsets[i]
                                 + st.widths[i]] for i in picked], axis=1)

    def answers(precision):
        fit = ref.Fit(st.X, st.gamma, st.fit_key, cfg["r"],
                      cfg["r"] + cfg["oversampling"], cfg["k"],
                      seed=ctx.seed, precision=precision)
        return ref.nearest(ref.embed(st.X, fit.proj, Xq, st.gamma,
                                     precision), fit.centroids)

    labels, d2 = answers("highest")
    if control:
        got_labels, got_d2 = answers("high")
    else:
        got_labels = np.concatenate([rec.results[i][0] for i in picked])
        got_d2 = np.concatenate([rec.results[i][1] for i in picked])
    reading = {"d2_rel_err": ref.rel_err(got_d2, d2),
               "label_mismatches": float(ref.label_mismatches(
                   got_labels, labels, cfg["k"]))}
    ctx.log(f"open_loop_assign: {len(picked)} requests, {Xq.shape[1]} "
            f"columns, {'control' if control else 'program'} vs reference "
            f"({time.perf_counter() - t0:.3f} s): {reading}")
    return [(name, v, float(limits[name])) for name, v in reading.items()]
