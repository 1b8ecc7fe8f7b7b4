"""Blocking device-to-host pulls per round.

The program's ``fedsub.sync`` spans that start in the traced window (one per
pull: the sub-id counts, the losses, each telemetry field), over the
window's rounds.
"""
from bench import phases


def read(ctx):
    tr = ctx.trace
    if not phases.has_spans(tr) or ctx.rounds <= 0:
        return None
    lo, hi = tr.window
    n = sum(1 for name, s, _ in tr.host
            if phases.span_name(name) == phases.SYNC and lo <= s < hi)
    return n / ctx.rounds
