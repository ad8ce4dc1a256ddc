"""Where the entry points put JAX's persistent compilation cache."""
import os
import pathlib
import subprocess
import sys

import jax

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parent.parent


def test_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.use_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_cache_env_dir_wins_and_is_written(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, nothing is set in code and a
    compile lands in that directory."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import use_compile_cache\n"
        "before = jax.config.jax_compilation_cache_dir\n"
        "print(use_compile_cache())\n"
        "assert jax.config.jax_compilation_cache_dir == before\n"
        "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()\n")
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip().splitlines()[-1] == str(tmp_path)
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())
