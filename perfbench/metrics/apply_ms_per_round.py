"""Device ms per round of the server's apply.

The leaf device operations under the round step's ``fedsub.apply`` scope
(the aggregated update added into the table and the dense leaves), on the
slowest of the cell's devices, over the window's rounds.
"""
from bench import phases


def read(ctx):
    return phases.scope_ms_per_round(ctx, phases.APPLY)
