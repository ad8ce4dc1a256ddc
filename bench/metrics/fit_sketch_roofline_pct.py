"""fit_sketch's share of its roofline over the traced window.

The least time the chips could take for the work every block of every
completed fit needs (bench/lib/costs.py: real widths, the q+b rows a
block reads), over the device time of the fit_sketch kernel's ops
(mean over the cell's chips). Peaks: bench/lib/peaks.py.
"""
from bench.lib import costs, kernels, peaks


def read(run):
    c = run.counters
    if run.trace is None or c.get("kind") != "fit" or not c["jobs"]:
        return None
    spent = run.trace.op_seconds(kernels.fit_sketch)
    if spent <= 0:
        return None
    cfg = run.cell.config
    flops, hbm, _ = costs.fit_sketch_fit_needed(
        c["n"], cfg["p"], cfg["r"] + cfg["oversampling"], cfg["block"])
    bound = peaks.roofline_seconds(flops * c["jobs"], hbm * c["jobs"],
                                   run.device_kind, run.chips)
    return 100.0 * bound["seconds"] / spent
