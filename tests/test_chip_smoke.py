"""chip_smoke.py: refuses to run off the chip; its phases pass on the CPU
at a tiny size with the Pallas kernels in interpret mode."""
import os
import pathlib
import shutil
import subprocess
import sys

import jax

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _run_smoke(script: pathlib.Path, cwd: pathlib.Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], capture_output=True,
                          text=True, timeout=300, env=env, cwd=str(cwd))


def test_refuses_cpu():
    r = _run_smoke(ROOT / "chip_smoke.py", ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "not a TPU" in r.stderr


def test_refuses_outside_repo(tmp_path):
    script = tmp_path / "chip_smoke.py"
    shutil.copy(ROOT / "chip_smoke.py", script)
    r = _run_smoke(script, tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_fit_and_serve_phases_pass_in_interpret_mode():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke as cs
    finally:
        sys.path.remove(str(ROOT))
    from repro.data import gaussian_blobs
    from repro.serve import ComputePolicy

    clock, check = cs.CompileClock(), cs.Checks()
    policy = ComputePolicy(interpret=True)
    k_data, k_gamma, k_fit, k_serve = jax.random.split(
        jax.random.PRNGKey(0), 4)
    X, labels = gaussian_blobs(k_data, n=2048, p=32, k=cs.K)
    gamma = cs.rbf_gamma(X, k_gamma)
    est = cs.fit_phase(X, labels, gamma, k_fit, policy, check, clock)
    cs.serve_phase(est, X, k_serve, policy, check, clock)
    assert check.failed == []
