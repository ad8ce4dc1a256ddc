"""99th percentile of the time requests waited for a flush, from the
AsyncBatcher's LatencyStats.queue_wait over the window."""


def read(run):
    c = run.counters
    if c.get("kind") != "serve" or not c["requests_recorded"]:
        return None
    return c["queue_wait_p99_ms"]
