"""Wall milliseconds the host spent in the window's flush and compaction
merges and SST and filter builds (the harness's span around each call of
the tree's ``_merge_memtables``, ``_merge_inputs`` and ``_make_sst``), per
1,000 acknowledged puts."""


def read(ctx):
    merge_s = ctx.get("merge_s")
    puts = (ctx.get("counters") or {}).get("puts_acked")
    if merge_s is None or not puts:
        return None
    return 1e3 * merge_s / (puts / 1e3)
