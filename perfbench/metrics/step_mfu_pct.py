"""The whole round step's share of the chips' bf16 peak.

Model FLOPs of a round are the forward and backward matmul operations of
every local step, ``K * I * B`` samples at the configuration's FLOPs per
sample (``models/<ref>.flops_per_sample``); times rounds per second of the
traced window, over chips times the peak.
"""


def read(ctx):
    if ctx.rounds <= 0 or ctx.trace.window_s <= 0:
        return None
    tf = ctx.traffic
    flops = (tf["clients"] * tf["local_iters"] * tf["local_batch"]
             * ctx.flops_per_sample)
    rate = ctx.rounds / ctx.trace.window_s
    return 100.0 * flops * rate / (ctx.chips * ctx.peaks["flops_bf16"])
