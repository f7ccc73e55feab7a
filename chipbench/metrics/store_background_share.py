"""Share of the traced window the host spent in the HHZS middleware and
the background jobs, in percent: the program's spans for hint handling,
the migrator's pick, compaction and flush merges and SST builds (the
filter build nests inside the SST build and is not counted twice)."""

SPANS = ("hhzs:hint", "hhzs:migration.pick", "hhzs:compaction.merge",
         "hhzs:flush.merge", "hhzs:sst.build")


def read(ctx):
    trace = ctx.get("trace") or {}
    spans = trace.get("spans")
    if not spans or not trace.get("window_s"):
        return None
    busy = sum(spans[s]["total_s"] for s in SPANS if s in spans)
    return 100.0 * busy / trace["window_s"]
