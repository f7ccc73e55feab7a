"""Mean wall time of one device probe call, host side included: the
harness's span around ``filters.probe_pairs_device`` through
``block_until_ready`` (image build, upload, kernel, readback)."""


def read(ctx):
    calls = ctx.get("probe_call_s")
    if not calls:
        return None
    return 1e3 * sum(calls) / len(calls)
