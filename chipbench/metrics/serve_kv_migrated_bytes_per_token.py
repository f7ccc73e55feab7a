"""KV bytes the tier manager moved between HBM and host per output token
in the window: the difference of ``mgr.stats["bytes_migrated"]`` over the
window, over the tokens completed in it."""


def read(ctx):
    c = ctx["counters"]
    if "bytes_migrated" not in c or not c.get("tokens_out"):
        return None
    return c["bytes_migrated"] / c["tokens_out"]
