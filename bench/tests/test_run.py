"""bench/run.py refuses to run without a TPU, and outside a checkout."""
import json
import os
import shutil
import subprocess
import sys

from bench.tests.checkout import REPO

ARGS = ["--workload", "mnist-fit", "--seed", str(2**33 + 1), "--seconds",
        "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS],
                          capture_output=True, text=True, timeout=300,
                          cwd=str(cwd), env=env)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            continue
    return True


def test_refuses_the_cpu():
    r = _run(REPO)
    assert r.returncode != 0
    assert _no_result(r.stdout)
    assert "not a TPU" in r.stderr


def test_refuses_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert _no_result(r.stdout)
