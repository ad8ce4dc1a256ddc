"""Device busy milliseconds per fit outside the fit_sketch kernel: the
Omega-row build, padding copies, the eigendecomposition and K-means
(mean over the cell's chips)."""
from bench.lib import kernels


def read(run):
    c = run.counters
    if run.trace is None or c.get("kind") != "fit" or not c["jobs"]:
        return None
    busy = run.trace.busy_mean_s()
    kernel = sum(run.trace.busy_s(d, kernels.fit_sketch)
                 for d in run.trace.devices) / max(len(run.trace.devices), 1)
    return 1e3 * (busy - kernel) / c["jobs"]
