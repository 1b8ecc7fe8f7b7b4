"""Device-idle ms per round spent waiting on the host's blocking pulls.

The time in which the busiest device ran nothing while the host was inside
one of the program's ``fedsub.sync`` spans (a blocking device-to-host pull:
the sub-id counts, a loss, a telemetry field), over the window's rounds.
"""
from bench import phases


def read(ctx):
    tr = ctx.trace
    if not phases.has_spans(tr) or ctx.rounds <= 0 or not ctx.devices:
        return None
    busiest = max(ctx.devices, key=tr.busy_s)
    waits = phases.merged(phases.spans(tr, phases.SYNC))
    return 1e-6 * phases.overlap_ns(phases.idle(tr, busiest), waits) \
        / ctx.rounds
