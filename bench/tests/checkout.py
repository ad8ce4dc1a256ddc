"""A scratch checkout of the benchmark with the test fixtures added as
new files and new BENCHMARK.json entries, editing no existing file; and
a way to run a cell in it on the CPU."""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import time

REPO = pathlib.Path(__file__).resolve().parents[2]
FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"

TINY_CELLS = [
    {"name": "tiny-fit", "config": "tiny-rbf-onepass", "traffic": "fit_jobs",
     "chips": 1, "why": "test fixture: fit jobs at a CPU size"},
    {"name": "tiny-serve", "config": "tiny-rbf-onepass",
     "traffic": "tiny_open_loop", "chips": 1,
     "why": "test fixture: open-loop requests at a CPU size"},
    {"name": "tiny-serve-fixed", "config": "tiny-rbf-onepass",
     "traffic": "tiny_fixed16", "chips": 1,
     "why": "test fixture: fixed-width requests the batcher coalesces"},
]


def make_checkout(dest: pathlib.Path) -> pathlib.Path:
    """Copy BENCHMARK.json and bench/ to dest, then add the fixtures'
    configuration, traffic mixes, metric reader and cell limits as new
    files with new entries."""
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for kind in ("configs", "traffic", "metrics", "limits"):
        for f in sorted((FIXTURES / kind).iterdir()):
            target = dest / "bench" / kind / f.name
            assert not target.exists(), f"fixture would edit {target}"
            shutil.copy(f, target)
    bench["configs"].append({
        "name": "tiny-rbf-onepass",
        "source": "test fixture",
        "file": "bench/configs/tiny-rbf-onepass.json",
        "reduced": ["n", "p", "k", "r"], "why": "test fixture"})
    bench["workloads"].extend(TINY_CELLS)
    bench["per_layer"].append({
        "name": "fixture_jobs_completed", "unit": "jobs", "better": "higher",
        "source": "program_counter", "layer": "fit path",
        "moves": "fit_cols_per_s", "workloads": ["tiny-fit"]})
    for m in bench["end_to_end"]:
        if m["name"] == "fit_cols_per_s":
            m["workloads"] += ["tiny-fit"]
        if m["name"] == "assign_p50_ms":
            m["workloads"] += ["tiny-serve", "tiny-serve-fixed"]
    for m in bench["per_layer"]:
        if m.get("layer") in ("fit path", "artifact store", "device") \
                and "fit" in m["name"] and m["name"] != \
                "fixture_jobs_completed":
            m["workloads"].append("tiny-fit")
    (dest / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return dest


def run_cell(root: pathlib.Path, workload: str, seed: int = 3,
             seconds: float = 2.0, trace: int = 0):
    """One run of a cell on the CPU, the Pallas kernels interpreted."""
    from bench.lib import harness
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace, trace_dir=None)
    return harness.run(args, time.perf_counter(), root=root,
                       require_chip=False, interpret=True)
