"""Share of the Bloom probe kernel's roofline: the least time the chip
could take for the pairs probed inside the traced window
(``chipbench.roofline.bloom_probe``), over the device time of the jitted
probe (``bloom_probe_pairs_ref``) in the trace, in percent."""
from chipbench.roofline import bloom_probe

PROGRAM = "bloom_probe_pairs_ref"


def read(ctx):
    trace, calls = ctx.get("trace"), ctx.get("traced_probe_calls")
    if not trace or not calls:
        return None
    device_s = trace["per_program"].get(PROGRAM)
    if not device_s:
        return None
    ideal = sum(bloom_probe.ideal_seconds(p, k, ctx["peaks"])
                for p, k in calls)
    return 100.0 * ideal / device_s
