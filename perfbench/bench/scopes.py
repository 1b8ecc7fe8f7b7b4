"""The scope path of each device operation of a traced window.

JAX's ``named_scope`` lands in each HLO instruction's ``op_name`` metadata
(``jit(step)/fedsub.local/...``), and the profiler keeps the HLO of every
program it saw in the ``/host:metadata`` plane of its ``.xplane.pb``. A
:class:`~bench.trace.Trace` keeps each operation's name and times only, so
this module goes back to the profile file the trace was reduced from: it
finds the file by the trace's window span among those
:func:`bench.trace.record` left in the temporary directory (they live until
the run's per-layer metrics are read), and gives each operation the
``op_name`` of its instruction in the program run that holds it.

A trace saved for a test keeps its scopes: :class:`ScopedTrace` dumps and
reads them beside the fields of :class:`~bench.trace.Trace`.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import tempfile
from dataclasses import dataclass, field

from bench import trace as tracing

#: the device plane line that holds one event per executed program
MODULES_LINE = "XLA Modules"
#: the plane that holds the HLO of every program the trace ran
METADATA_PLANE = "/host:metadata"
#: the prefix of the directories :func:`bench.trace.record` profiles into
RECORD_PREFIX = "bench_trace_"

#: ``{window: {device: [scope path or None, ...]}}`` of the profiles found
_found: dict = {}


@dataclass
class ScopedTrace(tracing.Trace):
    """A :class:`~bench.trace.Trace` that carries ``scopes``: each device
    plane's scope paths, aligned with ``devices``; empty for a file saved
    without them."""
    scopes: dict = field(default_factory=dict)

    def dump(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump({"devices": self.devices, "host": self.host,
                       "window": list(self.window),
                       "scopes": self.scopes}, f)

    @classmethod
    def read(cls, path: str) -> "ScopedTrace":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls(d["devices"], d["host"], tuple(d["window"]),
                   d.get("scopes", {}))


def of(tr) -> dict:
    """``{device: [scope path or None, ...]}`` of the trace, aligned with
    ``tr.devices``; empty where its profile file cannot be found."""
    if isinstance(tr, ScopedTrace):
        return tr.scopes
    window = tuple(tr.window)
    if window not in _found:
        path = xplane_of(tr)
        if path is None:
            return {}
        _found[window] = load(path)
    return _found[window]


def xplane_of(tr) -> str | None:
    """The profile file, among those :func:`bench.trace.record` made, whose
    window span is the trace's; the newest first."""
    pattern = os.path.join(tempfile.gettempdir(), RECORD_PREFIX + "*", "**",
                           "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True),
                       key=os.path.getmtime, reverse=True):
        if _window(path) == tuple(tr.window):
            return path
    return None


def _window(xplane: str) -> tuple | None:
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == tracing.WINDOW:
                        return int(e.start_ns), int(e.end_ns)
    return None


def load(xplane: str) -> dict:
    """``{device: [scope path or None, ...]}`` of an ``.xplane.pb``, each
    list aligned with the device's operations as :func:`bench.trace.load`
    reads them."""
    from jax.profiler import ProfileData
    out: dict = {}
    op_names = None
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        ops, programs = None, []
        for line in plane.lines:
            if line.name == tracing.OPS_LINE:
                ops = [[e.name, int(e.start_ns), int(e.end_ns)]
                       for e in line.events]
            elif line.name == MODULES_LINE:
                programs = sorted(
                    ([e.name, int(e.start_ns), int(e.end_ns)]
                     for e in line.events), key=lambda p: p[1])
        if ops is not None:
            if op_names is None:
                op_names = hlo_op_names(xplane)
            out[plane.name] = op_scopes(ops, programs, op_names)
    return out


def op_scopes(ops: list, programs: list, op_names: dict) -> list:
    """The scope path of each operation ``[hlo text, start, end]``: the
    ``op_name`` of its instruction in the program whose run holds it."""
    starts = [s for _, s, _ in programs]
    out = []
    for text, s, _ in ops:
        j = bisect.bisect_right(starts, s) - 1
        scope = None
        if j >= 0 and s < programs[j][2]:
            inst = tracing.op_name(text).rsplit(" (", 1)[0]
            scope = op_names.get(programs[j][0], {}).get(inst)
        out.append(scope)
    return out


def hlo_op_names(xplane: str) -> dict:
    """``{program: {instruction: op_name}}`` from the HLO modules in the
    trace's metadata plane; a program is named as the device's program
    events name it (``jit_step(1234)``).

    ProfileData does not expose that plane's contents, so the file is read
    as the protobuf it is: ``XSpace.planes`` (1); ``XPlane.name`` (2),
    ``event_metadata`` (4, map entries of key 1 and value 2),
    ``stat_metadata`` (5); ``XEventMetadata.name`` (2), ``stats`` (5);
    ``XStat.metadata_id`` (1), ``bytes_value`` (6); ``HloProto.hlo_module``
    (1); ``HloModuleProto.computations`` (3);
    ``HloComputationProto.instructions`` (2); ``HloInstructionProto.name``
    (1), ``metadata`` (7); ``OpMetadata.op_name`` (2).
    """
    with open(xplane, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        parts = list(_fields(plane))
        if bytes(next((v for k, v in parts if k == 2), b"")).decode() \
                != METADATA_PLANE:
            continue
        hlo_stat = set()
        for k, entry in parts:
            if k == 5:
                meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
                if bytes(meta.get(2, b"")) == b"Hlo Proto":
                    hlo_stat.add(meta.get(1))
        for k, entry in parts:
            if k != 4:
                continue
            event = list(_fields(dict(_fields(entry)).get(2, b"")))
            name = bytes(next((v for f, v in event if f == 2), b"")).decode()
            ops = out.setdefault(name, {})
            for f, stat in event:
                st = dict(_fields(stat)) if f == 5 else {}
                if st.get(1) not in hlo_stat or 6 not in st:
                    continue
                for m, module in _fields(st[6]):
                    for c, comp in (_fields(module) if m == 1 else ()):
                        for i, inst in (_fields(comp) if c == 3 else ()):
                            if i != 2:
                                continue
                            d = dict(_fields(inst))
                            meta = dict(_fields(d.get(7, b"")))
                            ops[bytes(d.get(1, b"")).decode()] = (
                                bytes(meta.get(2, b"")).decode() or None)
    return out


def _fields(buf):
    """``(field number, value)`` of a protobuf message: an ``int`` for a
    varint, a ``memoryview`` for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i
