"""The chip: refuse to run without it, configure JAX, read its counters."""
from __future__ import annotations

import pathlib
import sys
from typing import Dict, List

import jax

ROOT = pathlib.Path(__file__).resolve().parents[2]


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int) -> List:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"the default device is {devices[0].platform!r}, not "
                     f"a TPU; nothing was run")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{len(devices)}; nothing was run")
    return devices[:chips]


def use_program() -> None:
    """Put the checkout's src/ on the import path (the program)."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def configure(precision: str) -> str:
    """Compile cache in the checkout (every program, however quick to
    compile, so a second run compiles nothing) and the configuration's
    matmul precision for the program's own jnp contractions. Returns the
    cache directory."""
    use_program()
    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_default_matmul_precision", precision)
    return cache


class CompileClock:
    """Backend compilations and their seconds, from JAX's monitoring
    events (a copy of chip_smoke.py's clock, with a count)."""

    def __init__(self):
        self.secs = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.count += 1

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def info(devices: List) -> Dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def written_bytes() -> int:
    """Bytes this process has sent to storage, less those it deleted
    before they reached the disk (Linux /proc/self/io; 0 elsewhere)."""
    try:
        io = dict(line.split(": ") for line in
                  pathlib.Path("/proc/self/io").read_text().splitlines())
    except (OSError, ValueError):
        return 0
    return int(io["write_bytes"]) - int(io["cancelled_write_bytes"])


def memory_peak_bytes(devices: List) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0
