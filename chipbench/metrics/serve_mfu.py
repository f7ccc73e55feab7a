"""Whole engine step's share of the chip's bf16 peak: model FLOPs of every
forward in the window (every layer at its new-token count and resident
length, plus the output head on the last position), over the wall time
spent inside ``ServingEngine.step`` times the peak, in percent.  Waits
between arrivals are outside the steps, so this moves with speed, not
with load."""


def read(ctx):
    if not ctx.get("step_s") or not ctx.get("model_flops"):
        return None
    return 100.0 * ctx["model_flops"] / (
        ctx["step_s"] * ctx["peaks"]["bf16_flops_per_s"])
