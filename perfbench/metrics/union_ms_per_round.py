"""Device time per round of the union + segment-sum + heat layer.

The sum of the durations of the layer's device operations, matched by
name, on the device that spent most in them, over the rounds of the traced
window. On several chips both passes (each shard's and the combine's
second) land on every device and both count. A backend that implements
the layer under another name adds that name here.
"""

#: names of the layer's device operations
EVENTS = ["union_segsum"]


def read(ctx):
    per_device = [ctx.trace.matching_s(d, EVENTS) for d in ctx.devices]
    per_device = [t for t in per_device if t is not None]
    if not per_device or ctx.rounds <= 0:
        return None
    return 1e3 * max(per_device) / ctx.rounds
