"""The program's own spans and scopes in a :class:`~bench.trace.Trace`.

The trainer names each phase of a round: host spans (``fedsub.sample``,
``fedsub.sub_ids``, ``fedsub.dispatch``, ``fedsub.sync``,
``fedsub.account``, inside one ``fedsub.call`` a call) and device scopes
(``fedsub.local``, ``fedsub.aggregate``, ``fedsub.apply``, ``fedsub.loss``,
``fedsub.telemetry``) in each operation's scope path. The readers of the
phase metrics share these helpers; each returns ``None`` on a trace with no
such span or scope, as a program without them leaves. The scope paths come
from :mod:`bench.scopes`.
"""
from __future__ import annotations

from bench import scopes

#: the prefix of every span and scope name of the program
PREFIX = "fedsub."
SAMPLE, SUB_IDS, SYNC, ACCOUNT = ("fedsub.sample", "fedsub.sub_ids",
                                  "fedsub.sync", "fedsub.account")
LOCAL, APPLY, TELEMETRY = "fedsub.local", "fedsub.apply", "fedsub.telemetry"
#: opcodes whose interval holds their bodies' operations
CONTAINERS = {"while", "conditional", "call"}


def span_name(name: str) -> str:
    """A host event's name without the ``#key=value#`` metadata a span may
    carry in it."""
    return name.split("#", 1)[0]


def has_spans(tr) -> bool:
    """Does the trace hold any of the program's host spans?"""
    return any(span_name(n).startswith(PREFIX) for n, _, _ in tr.host)


def spans(tr, *names) -> list:
    """``[start, end]`` of the host spans named ``names`` that overlap the
    window, clipped to it."""
    lo, hi = tr.window
    return [(max(s, lo), min(e, hi)) for n, s, e in tr.host
            if span_name(n) in names and e > lo and s < hi]


def scope(path) -> str | None:
    """The innermost ``fedsub.*`` scope of an operation's scope path."""
    if not path:
        return None
    inner = [p for p in path.split("/") if p.startswith(PREFIX)]
    return inner[-1] if inner else None


def opcode(name: str) -> str:
    """The opcode of a device operation named ``name (opcode)``."""
    return name.rsplit(" (", 1)[-1][:-1] if name.endswith(")") else ""


def scope_s(tr, device: str) -> dict | None:
    """Seconds of the device's leaf operations in the window per
    ``fedsub.*`` scope (``while``, ``conditional`` and ``call`` hold their
    bodies and are left out); ``None`` where no operation has one."""
    lo, hi = tr.window
    out: dict = {}
    ops = tr.devices.get(device, [])
    paths = scopes.of(tr).get(device, [])
    if len(paths) != len(ops):
        return None
    for (n, s, e), path in zip(ops, paths):
        name = scope(path)
        if name is None or e <= lo or s >= hi or opcode(n) in CONTAINERS:
            continue
        out[name] = out.get(name, 0) + min(e, hi) - max(s, lo)
    return {k: v * 1e-9 for k, v in out.items()} if out else None


def scope_ms_per_round(ctx, name: str) -> float | None:
    """Device ms per round of the leaf operations under scope ``name`` on
    the slowest of the cell's devices; ``None`` on a trace without the
    program's spans, which a program without scopes leaves."""
    if not has_spans(ctx.trace):
        return None
    per_device = [scope_s(ctx.trace, d) for d in ctx.devices]
    per_device = [t for t in per_device if t is not None]
    if not per_device or ctx.rounds <= 0:
        return None
    return 1e3 * max(t.get(name, 0.0) for t in per_device) / ctx.rounds


def idle(tr, device: str) -> list:
    """``[start, end]`` of the window's stretches in which no operation ran
    on ``device``."""
    gaps, end = [], tr.window[0]
    for _, s, e in sorted(tr.ops(device), key=lambda x: x[1]):
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if tr.window[1] > end:
        gaps.append((end, tr.window[1]))
    return gaps


def overlap_ns(a: list, b: list) -> int:
    """Nanoseconds in both of two sets of intervals, each set disjoint."""
    a, b = sorted(a), sorted(b)
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def merged(intervals: list) -> list:
    """The union of ``intervals`` as disjoint intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out
