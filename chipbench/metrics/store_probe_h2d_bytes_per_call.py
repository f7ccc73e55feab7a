"""Bytes one device probe call hands the device, padding included: the
difference over the window of the tree's ``probe_h2d_bytes`` counter over
that of ``probe_calls`` (``repro.lsm.filters.padded_bytes`` per call)."""


def read(ctx):
    c = ctx["counters"]
    if "probe_h2d_bytes" not in c or not c.get("probe_calls"):
        return None
    return c["probe_h2d_bytes"] / c["probe_calls"]
