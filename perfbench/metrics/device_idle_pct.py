"""Share of the traced window in which the busiest device ran nothing.

Busy time is the union of the device's operation intervals inside the
window (``Trace.busy_s``); the busiest of the cell's devices is reported.
"""


def read(ctx):
    if not ctx.devices or ctx.trace.window_s <= 0:
        return None
    busy = max(ctx.trace.busy_s(d) for d in ctx.devices)
    return 100.0 * (1.0 - busy / ctx.trace.window_s)
