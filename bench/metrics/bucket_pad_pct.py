"""Share of the columns run through the serving buckets that were
padding: MicroBatcher.stats padded_queries / (queries + padded_queries)
over the window."""


def read(run):
    c = run.counters
    if c.get("kind") != "serve" or not c["queries"]:
        return None
    return 100.0 * c["padded"] / (c["queries"] + c["padded"])
