"""Optimized-HLO analysis: collective inventory with loop-aware multipliers.

``compiled.cost_analysis()`` gives FLOPs/bytes but no per-collective detail,
and it counts while-loop bodies ONCE (verified empirically: a 10-iteration
scan of a 128x128 matmul reports ~1 matmul of FLOPs). This module parses the
optimized HLO text into its computation graph, finds every collective op
(all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute,
incl. async ``-start``/``-done`` pairs), and multiplies ops inside while
bodies by the loop's trip count when XLA recorded one
(``known_trip_count``/``trip_count``). Unresolvable trips are reported with
multiplier 1 and flagged so the roofline layer can apply model-structure
corrections (layer counts, chunk counts).

Byte attribution rules (the contract the hlo_audit oracle depends on):

- only the RESULT shape of a collective is counted — the text between
  `` = `` and the op name. Operand shapes (inside the call parens) are never
  counted, so ``all-gather(f32[1,2,4] %x)`` contributes nothing from ``%x``.
- tuple / variadic results sum their element shapes: a merged variadic
  ``all-reduce`` with result ``(f32[4], f32[8])`` counts both outputs once.
- async pairs are counted ONCE, at the ``-done`` line (whose result is the
  final output shape — the ``-start`` result tuple for gather-like ops
  carries (operand, result) and would double-count). The ``-start`` line is
  still parsed for ``replica_groups``, which XLA attaches to the start form
  only, and the attribute is carried over to the paired ``-done``.
- ``replica_groups`` (explicit ``{{0,1},{2,3}}``, empty ``{}`` = one group of
  all devices, and non-transposed iota ``[G,S]<=[N]``) are parsed onto each
  op so :meth:`HloReport.attribute_axes` can map collectives back to the
  mesh axis that produced them (see :func:`mesh_axis_groups`).
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")

#: a collective op use-site: the base kind, an optional async suffix, and the
#: opening paren that distinguishes a call from an lhs name like
#: ``%all-gather.1`` (followed by ``.``/`` ``, never ``(``)
_COLLECTIVE_RE = re.compile(
    r"(?<![\w-])(" + "|".join(_COLLECTIVES) + r")(-start|-done)?\(")

_LHS_RE = re.compile(r"\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=")

_RG_RE = re.compile(
    r"replica_groups=(\{\{.*?\}\}|\{\}"
    r"|\[[0-9,]+\]<=\[[0-9,]+\](?:T\([0-9,]+\))?)")


def _shape_bytes_list(text: str) -> List[int]:
    """Byte sizes of every typed shape literal in a string, in order."""
    out = []
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        out.append(n * _DTYPE_BYTES[dtype])
    return out


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of every typed shape literal in a string."""
    return sum(_shape_bytes_list(text))


def _parse_replica_groups(line: str):
    """``replica_groups`` attr -> tuple of device-id groups, or None.

    ``{}`` (all devices, one group) parses to ``()``; a transposed iota
    spec (``...T(1,0)``) parses to None — the op stays unattributed rather
    than attributed wrongly.
    """
    m = _RG_RE.search(line)
    if not m:
        return None
    spec = m.group(1)
    if spec == "{}":
        return ()
    if spec.startswith("{{"):
        groups = []
        for part in spec[2:-2].split("},{"):
            part = part.strip()
            if part:
                groups.append(tuple(int(x) for x in part.split(",") if x.strip()))
        return tuple(groups)
    if "T(" in spec:
        return None
    dims_part, _ = spec.split("<=")
    dims = [int(x) for x in dims_part.strip("[]").split(",")]
    total = 1
    for d in dims:
        total *= d
    size = dims[-1]
    ids = range(total)
    return tuple(tuple(ids[i * size:(i + 1) * size])
                 for i in range(total // size))


@dataclass
class CollectiveOp:
    op: str
    computation: str
    out_bytes: int
    multiplier: int
    resolved: bool
    name: Optional[str] = None
    replica_groups: Optional[Tuple[Tuple[int, ...], ...]] = None
    mesh_axis: Optional[str] = None


@dataclass
class HloReport:
    collectives: List[CollectiveOp] = field(default_factory=list)
    unresolved_loops: int = 0

    def total_bytes(self) -> int:
        return sum(c.out_bytes * c.multiplier for c in self.collectives)

    def by_op(self) -> Dict[str, int]:
        out: Dict[str, int] = defaultdict(int)
        for c in self.collectives:
            out[c.op] += c.out_bytes * c.multiplier
        return dict(out)

    def by_axis(self) -> Dict[str, int]:
        """Collective bytes keyed by attributed mesh axis ('?' = unknown)."""
        out: Dict[str, int] = defaultdict(int)
        for c in self.collectives:
            out[c.mesh_axis or "?"] += c.out_bytes * c.multiplier
        return dict(out)

    def attribute_axes(self, axis_groups: Dict[str, Tuple[Tuple[int, ...], ...]]):
        """Stamp ``mesh_axis`` on each op whose replica_groups match an axis.

        ``axis_groups`` maps axis name -> device-id groups (see
        :func:`mesh_axis_groups`). Empty parsed groups (``{}``) match any
        axis whose groups form a single group — the all-devices case.
        """
        norm = {name: frozenset(frozenset(g) for g in groups)
                for name, groups in axis_groups.items()}
        for c in self.collectives:
            if c.replica_groups is None:
                continue
            cg = frozenset(frozenset(g) for g in c.replica_groups)
            for name, ng in norm.items():
                if cg == ng or (not c.replica_groups
                                and len(axis_groups[name]) == 1):
                    c.mesh_axis = name
                    break
        return self

    def summary(self) -> Dict:
        return {
            "total_collective_bytes": self.total_bytes(),
            "by_op": self.by_op(),
            "num_ops": len(self.collectives),
            "unresolved_loops": self.unresolved_loops,
        }


def mesh_axis_groups(mesh) -> Dict[str, Tuple[Tuple[int, ...], ...]]:
    """Per-axis device-id groups of a ``jax.sharding.Mesh``.

    For each mesh axis, the groups are the sets of device ids that a
    collective over that axis communicates within — directly comparable to
    a parsed ``replica_groups`` attribute via
    :meth:`HloReport.attribute_axes`.
    """
    import numpy as np

    ids = np.vectorize(lambda d: d.id)(np.asarray(mesh.devices))
    out: Dict[str, Tuple[Tuple[int, ...], ...]] = {}
    for i, name in enumerate(mesh.axis_names):
        moved = np.moveaxis(ids, i, -1).reshape(-1, ids.shape[i])
        out[str(name)] = tuple(tuple(int(x) for x in row) for row in moved)
    return out


def _split_computations(text: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    current = None
    for line in text.splitlines():
        stripped = line.strip()
        # computation headers look like `%name (args...) -> type {` (args may
        # contain nested parens for tuples); instruction lines contain " = "
        m = None
        if " = " not in stripped:
            m = re.match(r"(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{\s*$", stripped)
        if m and not stripped.startswith("ROOT"):
            current = m.group(1)
            comps[current] = []
            continue
        if stripped.startswith("}"):
            current = None
            continue
        if current is not None:
            comps[current].append(stripped)
    return comps


_CALLEE_RE = re.compile(
    r"(?:body|to_apply|calls)=%?([\w\.\-]+)"
    r"|branch_computations=\{([^}]*)\}")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*?(\d+)|trip_count[=:"\s]+(\d+)')


def _scan_collectives(name: str, lines: List[str], mult: int, resolved: bool,
                      out: List[CollectiveOp]) -> None:
    """Collect every collective in one computation's lines into ``out``."""
    # async starts seen so far in this computation, keyed by lhs name:
    # lhs -> (kind, replica_groups, result_region)
    starts: Dict[str, Tuple[str, Optional[tuple], str]] = {}
    for line in lines:
        m = _COLLECTIVE_RE.search(line)
        if m is None:
            continue
        kind, suffix = m.group(1), m.group(2) or ""
        eq = line.find(" = ")
        region = line[eq + 3:m.start()] if 0 <= eq < m.start() else line[:m.start()]
        lm = _LHS_RE.match(line)
        lhs = lm.group(1) if lm else None
        groups = _parse_replica_groups(line)
        if suffix == "-start":
            # replica_groups live on the start form; bytes are counted at the
            # paired -done, whose result is the final output shape (the start
            # result tuple for gather-like ops carries the operand too)
            starts[lhs] = (kind, groups, region)
            continue
        if suffix == "-done":
            om = re.search(r"%([\w\.\-]+)", line[m.end():])
            paired = starts.pop(om.group(1), None) if om else None
            if paired is not None and groups is None:
                groups = paired[1]
        out.append(CollectiveOp(
            op=kind, computation=name, out_bytes=_shape_bytes(region),
            multiplier=mult, resolved=resolved, name=lhs,
            replica_groups=groups))
    # a -start whose -done lives elsewhere (shouldn't happen in optimized
    # HLO, but don't silently drop bytes): count it from the start's own
    # result. For gather-like kinds a 2-tuple result is (operand, result) —
    # count only the result half; variadic all-reduce tuples are all outputs.
    for lhs, (kind, groups, region) in starts.items():
        sizes = _shape_bytes_list(region)
        if kind != "all-reduce" and len(sizes) == 2:
            sizes = sizes[1:]
        out.append(CollectiveOp(
            op=kind, computation=name, out_bytes=sum(sizes),
            multiplier=mult, resolved=resolved, name=lhs,
            replica_groups=groups))


def analyze_hlo(text: str, entry_hint: Optional[str] = None) -> HloReport:
    comps = _split_computations(text)
    # find entry computation name
    entry = entry_hint
    m = re.search(r"ENTRY\s+%?([\w\.\-]+)", text)
    if m:
        entry = m.group(1)
    if entry not in comps:
        # fall back: the computation containing no callers
        entry = next(iter(comps)) if comps else None

    report = HloReport()
    if entry is None:
        return report

    # walk the call graph propagating multipliers
    seen: Dict[str, int] = {}

    def walk(name: str, mult: int, resolved: bool):
        if name not in comps:
            return
        if name in seen and seen[name] >= mult:
            return
        if name in seen:
            # re-reached with a larger multiplier (e.g. first called
            # directly, then from inside a counted loop): replace the stale
            # entries instead of double-appending
            report.collectives = [c for c in report.collectives
                                  if c.computation != name]
        seen[name] = mult
        _scan_collectives(name, comps[name], mult, resolved,
                          report.collectives)
        for line in comps[name]:
            is_while = re.search(r"\bwhile\(", line) is not None
            trip = None
            if is_while:
                tm = _TRIP_RE.search(line)
                if tm:
                    trip = int(tm.group(1) or tm.group(2))
            for cm in _CALLEE_RE.finditer(line):
                if cm.group(1):
                    callees = [cm.group(1)]
                else:
                    callees = [c.strip().lstrip("%") for c in cm.group(2).split(",")]
                for callee in callees:
                    if is_while:
                        if trip is None:
                            report.unresolved_loops += 1
                            walk(callee, mult, False)
                        else:
                            walk(callee, mult * trip, resolved)
                    else:
                        walk(callee, mult, resolved)

    walk(entry, 1, True)
    return report
