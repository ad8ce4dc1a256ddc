"""Published peaks per chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
A device missing from this table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def roofline_seconds(flops: float, hbm_bytes: float, device_kind: str,
                     chips: int = 1) -> Dict[str, float]:
    """Least time `chips` chips of this kind could take for the work:
    the larger of the compute and the memory bound, and which it is."""
    pk = peaks(device_kind)
    t_flops = flops / (pk["flops_per_s"] * chips)
    t_bytes = hbm_bytes / (pk["hbm_bytes_per_s"] * chips)
    return {"seconds": max(t_flops, t_bytes), "compute_s": t_flops,
            "memory_s": t_bytes,
            "bound": "compute" if t_flops >= t_bytes else "memory"}
