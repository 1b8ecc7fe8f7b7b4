"""Device time per round of the cross-shard collectives.

The sum of the durations of the all-gather / all-reduce family of device
operations on the device that spent most in them, over the rounds of the
traced window. Only a cell on several chips has any.
"""

#: names of collective device operations as XLA names them
EVENTS = ["all-gather", "all-reduce", "reduce-scatter", "collective-permute",
          "all-to-all"]


def read(ctx):
    per_device = [ctx.trace.matching_s(d, EVENTS) for d in ctx.devices]
    per_device = [t for t in per_device if t is not None]
    if not per_device or ctx.rounds <= 0:
        return None
    return 1e3 * max(per_device) / ctx.rounds
