"""Record a profiler trace around the window and reduce it to events.

``record`` starts JAX's profiler (Python tracer off, so the host's own
speed is barely touched), and ``load`` reads the ``.xplane.pb`` it wrote
into a :class:`Trace`: the device operations of each accelerator, the host
spans, and the window span the harness annotated. Every per-layer reader
works on a :class:`Trace`, so a trace saved with :meth:`Trace.dump` can be
reduced again in a test.
"""
from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re
import shutil
import tempfile
from dataclasses import dataclass, field

#: the host span the harness puts around its measured window
WINDOW = "bench_window"
#: the host span around each call into the trainer
CALL = "bench_call"
#: the device plane line that holds one event per executed operation
OPS_LINE = "XLA Ops"
#: an operation's event name is its HLO text, ``%name = <shape> opcode(...``
_HLO = re.compile(r"^%?(\S+) = .*?(?<![A-Za-z0-9_\-])([a-z][a-z0-9\-]*)\(")


def op_name(text: str) -> str:
    """``name (opcode)`` of an operation's HLO text; the text itself where
    it is not HLO."""
    m = _HLO.match(text)
    return f"{m.group(1)} ({m.group(2)})" if m else text


@dataclass
class Trace:
    """Events in nanoseconds on the profiler's clock.

    ``devices`` maps a device plane's name to its operations ``[name,
    start, end]`` (``name (opcode)``); ``host`` holds the spans ``[name,
    start, end]`` of the host thread that ran the window; ``window`` is the
    ``[start, end]`` of the harness's window span.
    """
    devices: dict = field(default_factory=dict)
    host: list = field(default_factory=list)
    window: tuple = (0, 0)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def ops(self, device: str) -> list:
        """The device's operations clipped to the window."""
        lo, hi = self.window
        return [(n, max(s, lo), min(e, hi)) for n, s, e in self.devices[device]
                if e > lo and s < hi]

    def busy_s(self, device: str) -> float:
        """Seconds of the window in which some operation ran on ``device``
        (the union of the operation intervals)."""
        busy, end = 0, None
        for _, s, e in sorted(self.ops(device), key=lambda x: x[1]):
            if end is None or s > end:
                busy += e - s
                end = e
            elif e > end:
                busy += e - end
                end = e
        return busy * 1e-9

    def matching_s(self, device: str, patterns) -> float:
        """Seconds of the device's operations whose name holds any of
        ``patterns``; ``None`` where no operation matches."""
        hits = [e - s for n, s, e in self.ops(device)
                if any(p in n for p in patterns)]
        return sum(hits) * 1e-9 if hits else None

    def dump(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump({"devices": self.devices, "host": self.host,
                       "window": list(self.window)}, f)

    @classmethod
    def read(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls(d["devices"], d["host"], tuple(d["window"]))


@contextlib.contextmanager
def record():
    """Profile the body; yields a list that holds the xplane path after."""
    import jax
    out = []
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        out.extend(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                             recursive=True))
        out.append(tmp)


def load(xplane: str) -> Trace:
    """Reduce an ``.xplane.pb`` to a :class:`Trace`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane)
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.devices[plane.name] = [
                        [op_name(e.name), int(e.start_ns), int(e.end_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            # the host thread that ran the window: the harness's spans and
            # what JAX did under them (dispatch, transfers, waits)
            for line in plane.lines:
                events = [[e.name, int(e.start_ns), int(e.end_ns)]
                          for e in line.events]
                for name, s, e in events:
                    if name == WINDOW:
                        tr.window = (s, e)
                        tr.host = events
    return tr


def breakdown(tr: Trace, device: str, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle gaps
    named by the innermost host span under each gap's midpoint."""
    per_op: dict = {}
    ops = sorted(tr.ops(device), key=lambda x: x[1])
    for n, s, e in ops:
        per_op[n] = per_op.get(n, 0) + (e - s)
    gaps, end = [], tr.window[0]
    for _, s, e in ops:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if tr.window[1] > end:
        gaps.append((end, tr.window[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for a, b in gaps[:top]:
        mid = (a + b) // 2
        under = [(e - s, n) for n, s, e in tr.host
                 if s <= mid < e and n != WINDOW]
        named.append([min(under)[1] if under else "no host span",
                      (b - a) * 1e-9])
    return {"device_ops": [[n, t * 1e-9] for n, t in
                           sorted(per_op.items(), key=lambda x: -x[1])[:top]],
            "idle_gaps": named}


def cleanup(paths) -> None:
    for p in paths:
        if os.path.isdir(p):
            shutil.rmtree(p, ignore_errors=True)
