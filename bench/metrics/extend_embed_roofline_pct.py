"""extend_embed's share of its roofline over the traced window.

The least time the chip could take for the work the served stripes
need (bench/lib/costs.py: the real query columns only, each stripe
reading the reference set once), over the device time of the
extend_embed kernel's ops. Peaks: bench/lib/peaks.py.
"""
from bench.lib import costs, kernels, peaks


def read(run):
    c = run.counters
    if run.trace is None or c.get("kind") != "serve" or not c["queries"]:
        return None
    spent = run.trace.op_seconds(kernels.extend_embed)
    if spent <= 0:
        return None
    cfg = run.cell.config
    flops, hbm = costs.extend_embed_needed(cfg["p"], c["n"], cfg["r"],
                                           c["queries"], c["stripes"])
    bound = peaks.roofline_seconds(flops, hbm, run.device_kind, run.chips)
    return 100.0 * bound["seconds"] / spent
