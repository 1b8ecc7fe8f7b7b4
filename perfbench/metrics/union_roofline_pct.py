"""The union layer's share of its roofline, from its algorithmic work.

The work is what any implementation of the layer must do on one device in
one round, from the shapes and the round's sub-id bucket ``R`` (the
harness replays each round's bucket): read its ``T = (K / chips) * R`` stacked ids
(int32) and rows (``T x D`` float32), gather the heat of the ``cap =
min(V, K * R)`` union rows, write ``cap`` ids and ``cap x D`` rows; ``T * D``
adds and ``cap * D`` multiplies. The bytes bound it by far (HBM bandwidth
from the peak table), and the least time, averaged over the window's
rounds, over ``union_ms_per_round`` is the share.
"""


def read(ctx):
    ms = ctx.read("union_ms_per_round")
    if ms is None:
        return None
    k, d = ctx.traffic["clients"], ctx.row_elems

    def least_s(r):
        t = (k // ctx.chips) * r
        cap = min(ctx.vocab, k * r)
        nbytes = 4 * t + 4 * t * d + 4 * cap + 4 * cap + 4 * cap * d
        flops = t * d + cap * d
        return max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                   flops / ctx.peaks["flops_bf16"])

    caps = ctx.capacities
    if not caps:
        return None
    return 100.0 * sum(map(least_s, caps)) / len(caps) / (ms * 1e-3)
