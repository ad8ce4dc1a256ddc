"""Fit jobs back to back: the time to a published model.

One job is `KernelKMeans.fit` on the whole data set, then
`VersionStore.publish` of the fitted model (publish writes the artifact
with save_model into a fresh directory and renames it into place: the
save and the publish in one write). Every job gets a fresh key; the data
set is made once, on the device, from the seed.

The store keeps the latest version and the versions pinned for the
check (keep-last-1 GC inside publish, as a deployment runs it), so a run
leaves one or two artifacts on disk and most of what it writes is
deleted before it reaches the disk.

Metric: fit_cols_per_s = n x jobs completed / (end of the last job -
start of the window); jobs start while the window is open.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import List

import jax
import numpy as np

from bench.lib import clustering, data, device
from bench.lib import trace as tr

WARM_JOB = 1 << 20          # job index of the set-up job, never reused
PIN = "bench-check"         # pin owner of the versions the check reads


@dataclasses.dataclass
class Job:
    index: int
    version: int
    labels: np.ndarray
    fit_s: float
    publish_s: float


@dataclasses.dataclass
class State:
    n: int
    X: jax.Array
    gamma: float
    k_jobs: jax.Array
    policy: object
    store: object
    sampled: int = 0        # the seeded job the check compares


@dataclasses.dataclass
class Record:
    jobs: List[Job]
    failed: int
    t0: float
    t_end: float


def job_key(st: State, index: int) -> jax.Array:
    return jax.random.fold_in(st.k_jobs, index)


def run_job(st: State, cfg, index: int) -> Job:
    with tr.span("fit"):
        t0 = time.perf_counter()
        est = clustering.estimator(cfg, st.gamma, st.policy).fit(
            st.X, key=job_key(st, index))
        labels = np.asarray(est.labels_)
        t1 = time.perf_counter()
    with tr.span("publish"):
        version = st.store.publish(est.model_)
        t2 = time.perf_counter()
    return Job(index, version, labels, t1 - t0, t2 - t1)


def setup(ctx) -> State:
    device.use_program()
    from repro.serve import VersionStore

    cfg = ctx.cell.config
    n = int(cfg["n"])
    t0 = time.perf_counter()
    X, gamma, k_jobs = clustering.make_data(cfg, ctx.seed, n)
    st = State(n=n, X=X, gamma=gamma, k_jobs=k_jobs,
               policy=clustering.policy(cfg, ctx.interpret),
               store=VersionStore(f"{ctx.tmp}/store", keep=1))
    t1 = time.perf_counter()
    warm = run_job(st, cfg, WARM_JOB)
    # The check compares the last job and one job drawn from the seed
    # among those the window is expected to complete.
    expected = max(1, int(ctx.seconds // (warm.fit_s + warm.publish_s)))
    st.sampled = int(data.host_rng(ctx.seed, 1).integers(expected))
    ctx.log(f"fit_jobs: n={n} p={cfg['p']} gamma={gamma!r}; data "
            f"{t1 - t0:.3f} s; warm job fit {warm.fit_s:.3f} s, publish "
            f"{warm.publish_s:.3f} s; job {st.sampled} of about {expected} "
            f"sampled")
    return st


def window(st: State, ctx) -> Record:
    cfg = ctx.cell.config
    jobs: List[Job] = []
    failed = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < ctx.seconds:
        try:
            jobs.append(run_job(st, cfg, len(jobs)))
            if jobs[-1].index == st.sampled:
                st.store.pin(jobs[-1].version, PIN)
        except Exception as exc:  # a failed job is counted, not hidden
            ctx.log(f"fit_jobs: job {len(jobs)} failed: {exc!r}")
            failed += 1
            break
    t_end = time.perf_counter()
    for j in jobs:
        ctx.log(f"fit_jobs: job {j.index} fit {j.fit_s:.3f} s, publish "
                f"{j.publish_s:.3f} s")
    return Record(jobs=jobs, failed=failed, t0=t0, t_end=t_end)


def end_to_end(st: State, rec: Record) -> dict:
    return {"fit_cols_per_s": st.n * len(rec.jobs) / (rec.t_end - rec.t0)}


def counters(st: State, rec: Record) -> dict:
    return {"kind": "fit", "n": st.n, "jobs": len(rec.jobs),
            "publish_s": [j.publish_s for j in rec.jobs]}


def attempted(rec: Record) -> int:
    return len(rec.jobs) + rec.failed


def failed(rec: Record) -> int:
    return rec.failed


def free(st: State) -> None:
    gc.collect()


def _reference(st: State, ctx, index: int, precision: str):
    cfg = ctx.cell.config
    return ctx.cell.reference.Fit(
        st.X, st.gamma, job_key(st, index), cfg["r"],
        cfg["r"] + cfg["oversampling"], cfg["k"], seed=ctx.seed,
        precision=precision)


def check(st: State, rec: Record, ctx, control: bool = False) -> list:
    """Compare sampled jobs with the plain reference fit under the same
    key: the published artifact as loaded back (the sketch fit_sketch
    accumulated over the applied columns, the eigenvalues, the data it
    carries) and the job's training partition. With control, the
    reference at the precision below the configuration's stands in for
    the program."""
    from repro.serve import load_model

    cfg, ref = ctx.cell.config, ctx.cell.reference
    limits = ctx.cell.limits()
    if not rec.jobs:
        return []
    picked = sorted({min(st.sampled, len(rec.jobs) - 1), len(rec.jobs) - 1})
    X_host = np.asarray(st.X)
    worst = {"sketch_rel_err": 0.0, "eig_rel_gap": 0.0,
             "label_mismatches": 0.0, "artifact_data_mismatches": 0.0}
    for i in picked:
        job = rec.jobs[i]
        model = load_model(st.store.path(job.version))
        applied = int(model.stream_counts[0])
        t0 = time.perf_counter()
        want = _reference(st, ctx, job.index, "highest")
        if control:
            stand_in = _reference(st, ctx, job.index, "high")
            got_w = stand_in.applied_sketch(applied)
            got_ev, got_labels, got_x = (stand_in.eigvals, stand_in.labels,
                                         X_host)
        else:
            got_w = np.asarray(model.stream_w)[:st.n]
            got_ev = np.asarray(model.eigvals, np.float64)
            got_labels, got_x = job.labels, np.asarray(model.X_train)
        reading = {
            "sketch_rel_err": ref.rel_err(got_w, want.applied_sketch(applied)),
            "eig_rel_gap": float(np.max(np.abs(got_ev - want.eigvals)
                                        / want.eigvals)),
            "label_mismatches": float(ref.label_mismatches(
                got_labels, want.labels, cfg["k"])),
            "artifact_data_mismatches": float(np.count_nonzero(
                got_x != X_host)),
        }
        ctx.log(f"fit_jobs: job {job.index} v{job.version} "
                f"{'control' if control else 'program'} vs reference "
                f"({time.perf_counter() - t0:.3f} s, {applied} columns "
                f"applied): {reading}")
        for name, v in reading.items():
            worst[name] = max(worst[name], v)
    return [(name, worst[name], float(limits[name])) for name in worst]
