"""Device ms per round of the clients' local training.

The leaf device operations under the round step's ``fedsub.local`` scope
(``while``, ``conditional`` and ``call`` hold their bodies and are left
out), on the slowest of the cell's devices, over the window's rounds.
"""
from bench import phases


def read(ctx):
    return phases.scope_ms_per_round(ctx, phases.LOCAL)
