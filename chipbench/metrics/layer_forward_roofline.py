"""Share of the layer program's roofline: the least time the chip could
take for the layer calls made inside the traced window
(``chipbench.roofline.dense_layer``), over the device time of the jitted
``_layer_forward`` in the trace, in percent."""
from chipbench.roofline import dense_layer

PROGRAM = "_layer_forward"


def read(ctx):
    trace, calls = ctx.get("trace"), ctx.get("traced_layer_calls")
    if not trace or not calls:
        return None
    device_s = trace["per_program"].get(PROGRAM)
    if not device_s:
        return None
    ideal = sum(layers * dense_layer.ideal_seconds(t, s, ctx["dims"],
                                                    ctx["peaks"])
                for t, s, layers in calls)
    return 100.0 * ideal / device_s
