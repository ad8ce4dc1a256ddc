"""Median milliseconds of VersionStore.publish per job, from the
benchmark's own host span around it."""
import statistics


def read(run):
    c = run.counters
    if c.get("kind") != "fit" or not c["publish_s"]:
        return None
    return 1e3 * statistics.median(c["publish_s"])
