"""Which device ops of the trace belong to which Pallas kernel.

A TPU trace names each op by its HLO text ("%fit_sketch_pallas.1 = (...)
custom-call(...)"); a Pallas kernel is a tpu_custom_call whose
instruction takes the name of the jitted wrapper around it. Each matcher
takes a trace.Op.
"""
from __future__ import annotations

from bench.lib.trace import hlo_name


def _custom_call(op, wrapper: str) -> bool:
    return wrapper in hlo_name(op) and "custom-call" in op.name


def fit_sketch(op) -> bool:
    return _custom_call(op, "fit_sketch")


def extend_embed(op) -> bool:
    return _custom_call(op, "extend_embed")
