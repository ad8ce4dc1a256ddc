"""Share of the traced window in which no op runs on the device (mean
over the cell's chips), in the serving cells."""


def read(run):
    if run.trace is None or run.counters.get("kind") != "serve":
        return None
    return 100.0 * (1.0 - run.trace.busy_mean_s() / run.trace.window_s)
