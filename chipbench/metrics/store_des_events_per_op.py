"""DES entries scheduled per operation completed in the window: the
difference over the window of ``Sim.scheduled`` over the operations
completed."""


def read(ctx):
    c = ctx["counters"]
    if "scheduled" not in c or not c.get("ops_completed"):
        return None
    return c["scheduled"] / c["ops_completed"]
