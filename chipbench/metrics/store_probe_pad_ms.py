"""Mean wall time of padding one device probe call's pairs and filter
image to their buckets: the program's ``hhzs:probe.pad`` spans in the
traced window."""

SPAN = "hhzs:probe.pad"


def read(ctx):
    pad = ((ctx.get("trace") or {}).get("spans") or {}).get(SPAN)
    if not pad or not pad["count"]:
        return None
    return 1e3 * pad["total_s"] / pad["count"]
