"""Device ms per round of the round step's in-jit counters.

The leaf device operations under the round step's ``fedsub.telemetry``
scope (``sub_rows``/``density`` and the ``RoundTelemetry`` fields: drop
accounting, the cohort union, norms, the heat histogram), on the slowest
of the cell's devices, over the window's rounds.
"""
from bench import phases


def read(ctx):
    return phases.scope_ms_per_round(ctx, phases.TELEMETRY)
