"""Share of the traced window in which the host was inside no span, in
percent: the self time of the harness's ``cb:window``, which is the DES
dispatch, the request servers and the op stream (``zoned/sim.py``,
``workloads/runner.py``)."""

WINDOW = "cb:window"


def read(ctx):
    trace = ctx.get("trace") or {}
    window = (trace.get("spans") or {}).get(WINDOW)
    if not window or not trace.get("window_s"):
        return None
    return 100.0 * window["self_s"] / trace["window_s"]
