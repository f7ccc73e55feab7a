"""(key x SST) pairs the read path probed per point read in the window:
the difference over the window of the tree's ``filter_probes`` and
``gets`` counters."""


def read(ctx):
    c = ctx["counters"]
    if "filter_probes" not in c or not c.get("gets"):
        return None
    return c["filter_probes"] / c["gets"]
