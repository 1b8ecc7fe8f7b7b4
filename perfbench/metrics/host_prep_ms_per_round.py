"""Host ms per round in which the trainer prepares its rounds.

The self time of the program's ``fedsub.sample`` (drawing each cohort),
``fedsub.sub_ids`` (feature ids to the device, ``count_sub_ids``, the
capacity bucket, ``derive_sub_ids``) and ``fedsub.account`` (comm
accounting, telemetry to the host, the sink) spans in the traced window,
less the blocking pulls (``fedsub.sync``) they hold, which
``sync_wait_ms_per_round`` reads; over the window's rounds.
"""
import bisect

from bench import phases


def read(ctx):
    tr = ctx.trace
    if not phases.has_spans(tr) or ctx.rounds <= 0:
        return None
    prep = sorted(phases.spans(tr, phases.SAMPLE, phases.SUB_IDS,
                               phases.ACCOUNT))
    starts = [s for s, _ in prep]
    held = 0
    for s, e in phases.spans(tr, phases.SYNC):
        j = bisect.bisect_right(starts, s) - 1
        if j >= 0 and e <= prep[j][1]:
            held += e - s
    return 1e-6 * (sum(e - s for s, e in prep) - held) / ctx.rounds
