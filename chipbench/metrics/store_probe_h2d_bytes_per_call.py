"""Bytes handed to the device per probe call, padding included: the
difference over the window of the tree's ``probe_h2d_bytes`` counter over
that of ``probe_calls``.  The counter adds each call's padded pairs and
each level image when it is uploaded to stay on the device, so the
images' share is spread over the calls that reuse them."""


def read(ctx):
    c = ctx["counters"]
    if "probe_h2d_bytes" not in c or not c.get("probe_calls"):
        return None
    return c["probe_h2d_bytes"] / c["probe_calls"]
