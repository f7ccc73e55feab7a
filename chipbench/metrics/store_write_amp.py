"""Write amplification of the window: the bytes the WAL, flushes,
compactions and migrations wrote to both devices (the middleware's
per-stream counters, ``write_bytes.ssd`` plus ``write_bytes.hdd``), over
the acknowledged puts times the object size."""


def read(ctx):
    c = ctx.get("counters") or {}
    if "write_bytes" not in c or not c.get("puts_acked"):
        return None
    return c["write_bytes"] / (c["puts_acked"] * c["object_bytes"])
