"""95th percentile of the gaps between a request's consecutive tokens, in
ms, over every request of the window (a token's time is the end of the
step that made it), leaving out the gaps that overlap the span in which
the profiler held the host.  A tail of a cell above the knee: each gap is
one step, set by the sequences it decodes and the prompts it admits."""


def read(ctx):
    return ctx.get("itl_p95_ms")
