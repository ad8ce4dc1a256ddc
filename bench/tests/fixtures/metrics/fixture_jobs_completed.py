"""Test fixture: completed fit jobs in the window, from the driver's
counters."""


def read(run):
    if run.counters.get("kind") != "fit":
        return None
    return float(run.counters["jobs"])
