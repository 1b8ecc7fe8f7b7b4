"""Kernel contract plane: static Pallas VMEM/race/cost auditor (layer 5).

The first four analysis layers stop at the ``pallas_call`` boundary: the
linter reads Python source, the jaxpr auditor reads traced programs, the
sanitizer checks runtime values, the HLO oracle reads compiled collectives —
none of them look INSIDE a Pallas kernel, and that is exactly where this
repo's worst bugs lived (the Megacore ``dimension_semantics`` race in
``union_segsum``; the ``fits_vmem``-vs-kernel block-pick drift). This layer
audits every ``pl.pallas_call`` in ``repro.kernels`` statically, from its
BlockSpecs, grid, scratch shapes and kernel-body jaxpr. Tracing needs no
TPU — only lowering does — so the whole audit runs on the CPU CI runner.

Three contracts per kernel:

- :func:`vmem_contract` — per-program VMEM footprint from the ACTUAL block
  picks in the trace: double-buffered pipeline blocks (index map varies with
  the grid), single-buffered resident blocks (constant index map), scratch,
  and SMEM scalars. Fails if the footprint exceeds the budget
  (``[vmem-budget]``), if the kernel's own ``fits_vmem`` guard disagrees
  with the trace (``[vmem-guard-drift]`` / ``[vmem-guard-underestimate]``),
  or if the guard's ``_block_sizes`` prediction differs from the blocks the
  kernel actually runs (``[block-pick-drift]`` — the PR-2 bug class,
  machine-checked for all kernels).
- :func:`race_contract` — walks the kernel body for cross-program carried
  state: scratch/SMEM accumulators whose reset schedule does not cover a
  grid dim, output blocks revisited by more than one program, and
  ``input_output_aliases``. Every grid dim the body's iteration order
  observably flows across must be declared ``"arbitrary"``; one declared
  ``"parallel"`` is the Megacore corruption bug, reported as
  ``[megacore-race]`` with the offending ref named.
- :func:`cost_model` — analytic bytes-touched and FLOPs per kernel
  invocation from the grid x BlockSpec structure. Operand fetch counts come
  from the grid dims each index map depends on, so an operand re-streamed
  across an independent grid dim (e.g. a union kernel blocked by vocabulary
  re-fetching its ids/rows once per vocab block) shows up as
  ``restream > 1``. The
  numbers feed ``bench_sparse``'s kernel roofline section (achieved vs
  analytic bandwidth per union backend), gated by ``check_regression.py``.

The per-kernel capture comes from ``repro.kernels.introspect.REGISTRY``,
which also carries each kernel's own guard verdict at the audit shape —
auditor and kernel share the ``_block_sizes`` helpers, so they cannot
drift silently.

CLI (the CI gate)::

    python -m repro.analysis.kernel_audit --json kernel-audit.json

exits non-zero on any contract failure or if a ``pallas_call`` site in
``repro.kernels`` is missing from the registry.

Race analysis, precisely
------------------------
TPU grids iterate row-major (last dim minor). For each ref the kernel
writes, the walk classifies every access: a FULL unconditional write makes
everything after it program-local; a full write guarded by a conjunction of
``program_id(k) == 0`` terms is a *reset* with dim set S; any read or
partial/conditional write before an unconditional full write means the ref
*carries* state between programs. A carried ref's state flows across grid
dim d unless the reset dims S are all strictly minor than d (``S ⊆ {k : k >
d}``): then every segment of constant d-prefix re-runs the reset before
touching the state. Input/output refs only share state across dims their
index map is constant along (or dims involved in a revisit, detected by
evaluating the index map over the dependent grid dims). Unknown constructs
degrade conservatively (flow everywhere).
"""
from __future__ import annotations

import argparse
import itertools
import json
import re
import sys
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from repro.common.hw import HW
from repro.kernels.introspect import REGISTRY, GuardReport, KernelEntry

__all__ = [
    "PallasCapture", "RefInfo", "VmemReport", "RaceReport", "CostReport",
    "KernelReport", "capture_pallas_calls", "vmem_contract", "race_contract",
    "cost_model", "audit_kernel", "audit_all", "registry_coverage", "main",
]


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


# ---------------------------------------------------------------------------
# capture: pallas_call -> structured view
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RefInfo:
    """One kernel-body ref: an input/output block or a scratch buffer."""

    name: str                       # 'args[0]' / 'outputs[0]' / 'scratch[0]'
    kind: str                       # 'input' | 'output' | 'scratch'
    space: str                      # 'vmem' | 'smem'
    block_shape: Tuple[int, ...]    # per-program window (scratch: full shape)
    array_shape: Tuple[int, ...]    # backing array (scratch: == block_shape)
    itemsize: int
    index_deps: frozenset           # grid dims the index map depends on
    index_map: Optional[Callable]   # (grid idx...) -> block idx tuple

    @property
    def block_bytes(self) -> int:
        return _prod(self.block_shape) * self.itemsize

    @property
    def array_bytes(self) -> int:
        return _prod(self.array_shape) * self.itemsize


@dataclass(frozen=True)
class PallasCapture:
    """Everything the contracts need from one traced ``pallas_call``."""

    grid: Tuple[int, ...]
    dimension_semantics: Optional[Tuple[str, ...]]
    refs: Tuple[RefInfo, ...]       # ordered as the kernel body's invars
    jaxpr: Any                      # the kernel body
    input_output_aliases: Tuple[Tuple[int, int], ...]
    num_inputs: int
    num_outputs: int


def _is_literal(a) -> bool:
    return hasattr(a, "val")


def _jaxpr_deps(closed) -> frozenset:
    """Grid dims (invar positions) a closed jaxpr's outputs depend on."""
    jaxpr = closed.jaxpr
    dep: Dict[Any, frozenset] = {
        v: frozenset([i]) for i, v in enumerate(jaxpr.invars)}

    def get(a):
        return frozenset() if _is_literal(a) else dep.get(a, frozenset())

    for eqn in jaxpr.eqns:
        d = frozenset().union(*(get(x) for x in eqn.invars)) \
            if eqn.invars else frozenset()
        for o in eqn.outvars:
            dep[o] = d
    if not jaxpr.outvars:
        return frozenset()
    return frozenset().union(*(get(o) for o in jaxpr.outvars))


def _space_of(aval) -> str:
    ms = getattr(aval, "memory_space", None)
    return "smem" if ms is not None and "smem" in str(ms).lower() else "vmem"


def _norm_shape(shape) -> Tuple[int, ...]:
    # each dim is a ``Blocked(block_size)``; a squeezed dim (``None`` in the
    # BlockSpec) has no block size and windows a single element
    return tuple(int(getattr(b, "block_size", 1)) for b in shape)


def _index_map_fn(closed) -> Callable:
    def call(*idx):
        import jax.core as jcore
        out = jcore.eval_jaxpr(closed.jaxpr, closed.consts, *idx)
        return tuple(int(x) for x in out)
    return call


def _captures_from_jaxpr(jaxpr, out: List[PallasCapture]) -> None:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(_capture_from_eqn(eqn))
        for val in eqn.params.values():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for v in vals:
                inner = getattr(v, "jaxpr", None)
                if inner is None and hasattr(v, "eqns"):
                    inner = v
                if inner is not None and hasattr(inner, "eqns"):
                    _captures_from_jaxpr(inner, out)


def _capture_from_eqn(eqn) -> PallasCapture:
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    cp = (eqn.params.get("compiler_params") or {}).get("mosaic_tpu")
    sem = getattr(cp, "dimension_semantics", None)
    sem = tuple(sem) if sem is not None else None
    body = eqn.params["jaxpr"]

    refs: List[RefInfo] = []
    n_in, n_out = int(gm.num_inputs), int(gm.num_outputs)
    for i, bm in enumerate(gm.block_mappings):
        kind = "input" if i < n_in else "output"
        origin = getattr(bm, "origin", "") or (
            f"args[{i}]" if kind == "input" else f"outputs[{i - n_in}]")
        sd = bm.array_aval
        refs.append(RefInfo(
            name=str(origin), kind=kind,
            space=_space_of(bm.transformed_block_aval),
            block_shape=_norm_shape(bm.block_shape),
            array_shape=tuple(int(s) for s in sd.shape),
            itemsize=int(sd.dtype.itemsize),
            index_deps=_jaxpr_deps(bm.index_map_jaxpr),
            index_map=_index_map_fn(bm.index_map_jaxpr),
        ))
    scratch_vars = body.invars[n_in + n_out:]
    for k, v in enumerate(scratch_vars):
        aval = v.aval
        shape = tuple(int(s) for s in getattr(aval, "shape", ()))
        dtype = getattr(aval, "dtype", None)
        refs.append(RefInfo(
            name=f"scratch[{k}]", kind="scratch", space=_space_of(aval),
            block_shape=shape, array_shape=shape,
            itemsize=int(dtype.itemsize) if dtype is not None else 4,
            index_deps=frozenset(), index_map=None,
        ))
    aliases = tuple(tuple(int(x) for x in pair)
                    for pair in (eqn.params.get("input_output_aliases") or ()))
    return PallasCapture(
        grid=grid, dimension_semantics=sem, refs=tuple(refs), jaxpr=body,
        input_output_aliases=aliases, num_inputs=n_in, num_outputs=n_out)


def capture_pallas_calls(fn: Callable, *args, **kwargs) -> List[PallasCapture]:
    """Trace ``fn(*args, **kwargs)`` and capture every ``pallas_call`` in it.

    Args may be ``jax.ShapeDtypeStruct``s — nothing is executed. Trace with
    ``interpret=False`` so the Mosaic ``dimension_semantics`` are present
    (tracing a compiled-path ``pallas_call`` works on any backend).
    """
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    out: List[PallasCapture] = []
    _captures_from_jaxpr(closed.jaxpr, out)
    return out


# ---------------------------------------------------------------------------
# kernel-body walk: guards + ref access events
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class _Event:
    ref: int                        # index into capture.refs
    kind: str                       # 'get' | 'swap' | 'opaque'
    full: bool                      # statically covers the whole ref
    guard: Optional[frozenset]      # {(axis, const), ...}; empty = always;
    #                                 None = condition unknown


def _parse_guard(var, env) -> Optional[frozenset]:
    """Parse a predicate var into ``{(axis, const), ...}`` conjuncts.

    Recognizes conjunctions of ``program_id(axis) == const`` (through
    ``convert_element_type`` casts); anything else is None (unknown).
    """
    if _is_literal(var):
        return None
    eqn = env.get(var)
    if eqn is None:
        return None
    prim = eqn.primitive.name
    if prim == "convert_element_type":
        return _parse_guard(eqn.invars[0], env)
    if prim == "and":
        a = _parse_guard(eqn.invars[0], env)
        b = _parse_guard(eqn.invars[1], env)
        return a | b if a is not None and b is not None else None
    if prim == "eq":
        for x, y in ((eqn.invars[0], eqn.invars[1]),
                     (eqn.invars[1], eqn.invars[0])):
            ax = _program_id_axis(x, env)
            cv = _literal_int(y)
            if ax is not None and cv is not None:
                return frozenset({(ax, cv)})
    return None


def _program_id_axis(var, env) -> Optional[int]:
    if _is_literal(var):
        return None
    eqn = env.get(var)
    if eqn is None:
        return None
    if eqn.primitive.name == "program_id":
        return int(eqn.params["axis"])
    if eqn.primitive.name == "convert_element_type":
        return _program_id_axis(eqn.invars[0], env)
    return None


def _literal_int(var) -> Optional[int]:
    if _is_literal(var):
        try:
            return int(var.val)
        except Exception:
            return None
    return None


def _is_full_write(eqn) -> bool:
    """A swap that statically covers its whole ref: no dynamic index
    operands and a value the size of the ref."""
    if len(eqn.invars) > 2:
        return False
    ref_shape = getattr(eqn.invars[0].aval, "shape", ())
    val_shape = getattr(eqn.invars[1].aval, "shape", ())
    return _prod(val_shape) == _prod(ref_shape)


def _collect_events(jaxpr, env, refmap, guard, events: List[_Event]) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "get":
            r = refmap.get(eqn.invars[0])
            if r is not None:
                events.append(_Event(r, "get", False, guard))
        elif prim == "swap":
            r = refmap.get(eqn.invars[0])
            if r is not None:
                events.append(_Event(r, "swap", _is_full_write(eqn), guard))
        elif prim == "cond":
            g = _parse_guard(eqn.invars[0], env)
            branches = eqn.params["branches"]
            for bi, br in enumerate(branches):
                sub = br.jaxpr
                if not sub.eqns:
                    continue
                # branch order: [false, true]; only the true branch runs
                # under the parsed conjunction — anything else is unknown
                if bi == len(branches) - 1 and g is not None and \
                        guard is not None:
                    sub_guard: Optional[frozenset] = guard | g
                else:
                    sub_guard = None
                env_b = dict(env)
                refmap_b = dict(refmap)
                for bv, ov in zip(sub.invars, eqn.invars[1:]):
                    if not _is_literal(ov):
                        if ov in env:
                            env_b[bv] = env[ov]
                        if ov in refmap:
                            refmap_b[bv] = refmap[ov]
                _collect_events(sub, env_b, refmap_b, sub_guard, events)
        else:
            # any other primitive taking a ref operand (run_scoped, loops,
            # DMA...) is opaque to this walk — degrade conservatively
            for iv in eqn.invars:
                if not _is_literal(iv) and iv in refmap:
                    events.append(_Event(refmap[iv], "opaque", False, None))
        for o in eqn.outvars:
            env[o] = eqn


def _ref_events(cap: PallasCapture) -> Dict[int, List[_Event]]:
    """Access events per ref id, in program order, guards resolved.

    Aliased inputs share their output's ref id: they are the same memory.
    """
    alias_of = {i: cap.num_inputs + o for i, o in cap.input_output_aliases}
    refmap = {}
    body = cap.jaxpr
    for i, v in enumerate(body.invars):
        refmap[v] = alias_of.get(i, i)
    env: Dict[Any, Any] = {}
    events: List[_Event] = []
    _collect_events(body, env, refmap, frozenset(), events)
    by_ref: Dict[int, List[_Event]] = {}
    for ev in events:
        by_ref.setdefault(ev.ref, []).append(ev)
    return by_ref


# ---------------------------------------------------------------------------
# contract 1: VMEM budget
# ---------------------------------------------------------------------------


@dataclass
class VmemReport:
    """Structural VMEM footprint vs budget and the kernel's own guard."""

    kernel: str
    structural_bytes: int
    budget_bytes: int
    guard_bytes: Optional[int]
    components: Dict[str, int]
    failures: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.failures

    def to_dict(self) -> Dict:
        return {"kernel": self.kernel, "ok": self.ok,
                "structural_bytes": self.structural_bytes,
                "budget_bytes": self.budget_bytes,
                "guard_bytes": self.guard_bytes,
                "components": self.components, "failures": self.failures}


def vmem_contract(cap: PallasCapture, *, kernel: str = "kernel",
                  budget: int, guard: Optional[GuardReport] = None
                  ) -> VmemReport:
    """Check the captured footprint against the budget and the guard.

    Footprint model: VMEM pipeline blocks whose index map varies with the
    grid are double-buffered (Mosaic prefetches the next window while the
    current one computes); constant-index-map blocks stay resident (x1);
    scratch and SMEM are single copies. The kernel's own guard must (a)
    accept the audit shape, (b) price at least the structural bytes, and
    (c) predict exactly the block shapes the kernel runs.
    """
    comps: Dict[str, int] = {}
    for r in cap.refs:
        if r.kind == "scratch" or r.space == "smem":
            comps[r.name] = r.block_bytes
        elif r.index_deps:
            comps[r.name] = 2 * r.block_bytes
        else:
            comps[r.name] = r.block_bytes      # grid-constant: resident
    structural = sum(comps.values())

    failures: List[str] = []
    if structural > budget:
        top = max(comps, key=comps.get)
        failures.append(
            f"[vmem-budget] {kernel}: static VMEM footprint {structural} B "
            f"exceeds the {budget} B budget (largest term {top} = "
            f"{comps[top]} B)")
    guard_bytes = None
    if guard is not None:
        guard_bytes = int(guard.footprint)
        if not guard.fits:
            failures.append(
                f"[vmem-guard-drift] {kernel}: its own fits_vmem guard "
                "rejects the audit shape the kernel traces at — guard and "
                "kernel have drifted apart")
        if guard_bytes < structural:
            failures.append(
                f"[vmem-guard-underestimate] {kernel}: fits_vmem prices "
                f"{guard_bytes} B but blocks+scratch alone are {structural} "
                "B — the guard formula undercounts the working set")
        for name, (idx, expected) in sorted(guard.blocks.items()):
            if not 0 <= idx < len(cap.refs):
                failures.append(
                    f"[block-pick-drift] {kernel}: guard names operand "
                    f"'{name}' at index {idx}, but the capture has only "
                    f"{len(cap.refs)} refs")
                continue
            got = cap.refs[idx].block_shape
            if tuple(expected) != got:
                failures.append(
                    f"[block-pick-drift] {kernel}: guard predicts '{name}' "
                    f"block {tuple(expected)}, kernel runs {got}")
    return VmemReport(kernel=kernel, structural_bytes=structural,
                      budget_bytes=int(budget), guard_bytes=guard_bytes,
                      components=comps, failures=failures)


# ---------------------------------------------------------------------------
# contract 2: grid-semantics race detector
# ---------------------------------------------------------------------------


@dataclass
class RaceReport:
    """Grid dims each written ref's state flows across vs the declaration."""

    kernel: str
    grid: Tuple[int, ...]
    dimension_semantics: Optional[Tuple[str, ...]]
    required_by_ref: Dict[str, List[int]]
    required: List[int]
    failures: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.failures

    def to_dict(self) -> Dict:
        return {"kernel": self.kernel, "ok": self.ok,
                "grid": list(self.grid),
                "dimension_semantics": (
                    list(self.dimension_semantics)
                    if self.dimension_semantics is not None else None),
                "required_by_ref": self.required_by_ref,
                "required": self.required, "failures": self.failures}


def _flow_dims(ngrid: int, reset: Optional[frozenset]) -> frozenset:
    """Dims a carried ref's state flows across given its reset dims.

    Row-major iteration: a reset guarded on dims S re-runs at the start of
    every segment where the dims major to S are constant, so state cannot
    outlive a change of dim d iff every reset dim is strictly minor
    (``S ⊆ {k : k > d}``).
    """
    if not reset:
        return frozenset(range(ngrid))
    return frozenset(
        d for d in range(ngrid)
        if not reset <= frozenset(range(d + 1, ngrid)))


def _revisits(ref: RefInfo, grid: Tuple[int, ...], limit: int = 1 << 16
              ) -> bool:
    """Whether two programs differing in the index map's dims share a block."""
    dims = sorted(ref.index_deps)
    if not dims or ref.index_map is None:
        return False
    if _prod(grid[d] for d in dims) > limit:
        return True                            # too big to check: assume yes
    seen = set()
    for combo in itertools.product(*(range(grid[d]) for d in dims)):
        idx = [0] * len(grid)
        for d, v in zip(dims, combo):
            idx[d] = v
        out = ref.index_map(*idx)
        if out in seen:
            return True
        seen.add(out)
    return False


def _ref_required_dims(ref: RefInfo, events: List[_Event],
                       grid: Tuple[int, ...]) -> frozenset:
    """Grid dims that must be 'arbitrary' on account of this ref."""
    writes = [e for e in events if e.kind != "get"]
    if not writes:
        return frozenset()
    ngrid = len(grid)
    if ref.kind == "scratch":
        shared = frozenset(range(ngrid))       # one buffer for all programs
    else:
        invariant = frozenset(range(ngrid)) - ref.index_deps
        shared = invariant | (ref.index_deps if _revisits(ref, grid)
                              else frozenset())
    if not shared:
        return frozenset()

    init_done = False
    carried = False
    reset: Optional[frozenset] = None
    for ev in events:
        if ev.kind == "swap" and ev.full and ev.guard == frozenset():
            if not carried:
                init_done = True
        elif ev.kind == "swap" and ev.full and ev.guard and \
                all(c == 0 for _, c in ev.guard):
            if reset is None:
                reset = frozenset(ax for ax, _ in ev.guard)
        else:
            if not init_done:
                carried = True
    if carried:
        return _flow_dims(ngrid, reset) & shared
    if ref.kind == "scratch":
        return frozenset()     # private temp: init'd then used per program
    # an output block overwritten whole by several programs: last writer
    # wins, so the shared dims still order the result
    return shared


def race_contract(cap: PallasCapture, *, kernel: str = "kernel") -> RaceReport:
    """Fail any 'parallel' grid dim the kernel body's order flows across."""
    by_ref = _ref_events(cap)
    required_by_ref: Dict[str, List[int]] = {}
    required: set = set()
    for rid, events in sorted(by_ref.items()):
        ref = cap.refs[rid]
        if ref.kind == "input":
            continue                            # read-only memory
        dims = _ref_required_dims(ref, events, cap.grid)
        if dims:
            required_by_ref[ref.name] = sorted(dims)
            required |= dims

    sem = cap.dimension_semantics
    failures: List[str] = []
    if sem is not None and len(sem) != len(cap.grid):
        failures.append(
            f"[megacore-race] {kernel}: {len(sem)} dimension_semantics "
            f"entries for a {len(cap.grid)}-dim grid")
        sem = None
    for d in sorted(required):
        culprits = [n for n, ds in required_by_ref.items() if d in ds]
        if sem is None:
            if cap.dimension_semantics is None:
                failures.append(
                    f"[megacore-race] {kernel}: grid dim {d} carries "
                    f"cross-program state ({', '.join(culprits)}) but no "
                    "dimension_semantics are declared — Mosaic may "
                    "parallelize it")
        elif sem[d] != "arbitrary":
            failures.append(
                f"[megacore-race] {kernel}: grid dim {d} carries "
                f"cross-program state ({', '.join(culprits)}) but is "
                f"declared '{sem[d]}' — Megacore partitioning would "
                "corrupt it")
    return RaceReport(kernel=kernel, grid=cap.grid,
                      dimension_semantics=cap.dimension_semantics,
                      required_by_ref=required_by_ref,
                      required=sorted(required), failures=failures)


# ---------------------------------------------------------------------------
# contract 3: static cost model
# ---------------------------------------------------------------------------

_ZERO_COST = frozenset({
    "get", "swap", "program_id", "iota", "broadcast_in_dim",
    "convert_element_type", "reshape", "transpose", "squeeze",
    "expand_dims", "slice", "dynamic_slice", "dynamic_update_slice",
    "concatenate", "copy", "stop_gradient", "bitcast_convert_type",
})

_REDUCE = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin", "cumsum", "cumprod",
    "cummax", "cummin", "cumlogsumexp",
})

_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "rem", "pow", "integer_pow", "neg", "abs",
    "sign", "floor", "ceil", "round", "exp", "log", "log1p", "expm1",
    "sqrt", "rsqrt", "tanh", "logistic", "max", "min", "and", "or", "xor",
    "not", "eq", "ne", "lt", "le", "gt", "ge", "select_n", "clamp",
    "is_finite", "erf", "sin", "cos", "square",
})


@dataclass
class CostReport:
    """Analytic per-invocation cost from the grid x BlockSpec structure."""

    kernel: str
    grid: Tuple[int, ...]
    flops: float
    bytes_in: int
    bytes_out: int
    bytes_touched: int
    intensity: float                      # FLOP per byte touched
    hbm_seconds: float                    # bytes_touched / peak HBM bw
    compute_seconds: float                # flops / peak fp32-ish rate
    per_operand: Dict[str, Dict]
    unmodeled: List[str] = field(default_factory=list)

    def to_dict(self) -> Dict:
        return {"kernel": self.kernel, "grid": list(self.grid),
                "flops": self.flops, "bytes_in": self.bytes_in,
                "bytes_out": self.bytes_out,
                "bytes_touched": self.bytes_touched,
                "intensity": self.intensity,
                "hbm_seconds": self.hbm_seconds,
                "compute_seconds": self.compute_seconds,
                "per_operand": self.per_operand,
                "unmodeled": self.unmodeled}


def _fetch_count(deps: frozenset, grid: Tuple[int, ...]) -> int:
    """Block fetches over the whole grid for an operand.

    Row-major order: the window only changes when a dim the index map
    depends on ticks, so consecutive programs share a fetch while the dims
    strictly minor than the most-major dependent dim cycle.
    """
    if not deps:
        return 1
    return _prod(grid[d] for d in range(max(deps) + 1))


def _guard_fraction(guard: Optional[frozenset],
                    grid: Tuple[int, ...]) -> float:
    if guard is None:
        return 1.0
    frac = 1.0
    for ax, _ in guard:
        frac /= max(grid[ax], 1)
    return frac


def _body_flops(jaxpr, env, grid, unmodeled: set) -> float:
    """FLOPs for one program's execution of ``jaxpr`` (guards weighted)."""
    total = 0.0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "cond":
            g = _parse_guard(eqn.invars[0], env)
            f = _guard_fraction(g, grid)
            branches = eqn.params["branches"]
            for bi, br in enumerate(branches):
                sub = br.jaxpr
                if not sub.eqns:
                    continue
                w = f if bi == len(branches) - 1 else (
                    1.0 - f if g is not None else 1.0)
                env_b = dict(env)
                for bv, ov in zip(sub.invars, eqn.invars[1:]):
                    if not _is_literal(ov) and ov in env:
                        env_b[bv] = env[ov]
                total += w * _body_flops(sub, env_b, grid, unmodeled)
        elif prim == "dot_general":
            (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
            ls = eqn.invars[0].aval.shape
            rs = eqn.invars[1].aval.shape
            m = _prod(ls[i] for i in range(len(ls))
                      if i not in lc and i not in lb)
            n = _prod(rs[i] for i in range(len(rs))
                      if i not in rc and i not in rb)
            k = _prod(ls[i] for i in lc)
            b = _prod(ls[i] for i in lb)
            total += 2.0 * b * m * n * k
        elif prim in _REDUCE:
            total += float(_prod(eqn.invars[0].aval.shape))
        elif prim in _ELEMENTWISE:
            shape = eqn.outvars[0].aval.shape if eqn.outvars else ()
            total += float(_prod(shape))
        elif prim in _ZERO_COST:
            pass
        else:
            unmodeled.add(prim)
            shape = eqn.outvars[0].aval.shape if eqn.outvars else ()
            total += float(_prod(shape))
        for o in eqn.outvars:
            env[o] = eqn
    return total


def cost_model(cap: PallasCapture, *, kernel: str = "kernel") -> CostReport:
    """Analytic bytes-touched + FLOPs for one invocation of the kernel.

    Bytes: every operand is fetched (and every output written back) once
    per change of its window — ``restream > 1`` means the backing array is
    streamed through VMEM more than once per invocation (e.g. the whole
    ids/rows stream re-fetched for every vocab block). FLOPs: a weighted
    walk of the body (dot_general = 2mnk, reductions/elementwise = 1/elt,
    ``pl.when`` bodies weighted by the fraction of programs that run them)
    times the number of programs.
    """
    grid = cap.grid
    programs = _prod(grid)
    per_op: Dict[str, Dict] = {}
    bytes_in = bytes_out = 0
    for r in cap.refs:
        if r.kind == "scratch":
            continue
        fetches = _fetch_count(r.index_deps, grid)
        moved = fetches * r.block_bytes
        per_op[r.name] = {
            "kind": r.kind, "array_bytes": r.array_bytes,
            "block_bytes": r.block_bytes, "fetches": fetches,
            "fetched_bytes": moved,
            "restream": moved / r.array_bytes if r.array_bytes else 0.0,
        }
        if r.kind == "input":
            bytes_in += moved
        else:
            bytes_out += moved
    unmodeled: set = set()
    flops = programs * _body_flops(cap.jaxpr, {}, grid, unmodeled)
    touched = bytes_in + bytes_out
    return CostReport(
        kernel=kernel, grid=grid, flops=flops, bytes_in=bytes_in,
        bytes_out=bytes_out, bytes_touched=touched,
        intensity=flops / touched if touched else 0.0,
        hbm_seconds=touched / HW["hbm_bandwidth"],
        compute_seconds=flops / HW["peak_flops_bf16"],
        per_operand=per_op, unmodeled=sorted(unmodeled))


# ---------------------------------------------------------------------------
# per-kernel audit + registry coverage
# ---------------------------------------------------------------------------


@dataclass
class KernelReport:
    """All three contracts for one registered kernel."""

    name: str
    grid: Tuple[int, ...]
    vmem: VmemReport
    race: RaceReport
    cost: CostReport
    failures: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not (self.failures or self.vmem.failures or
                    self.race.failures)

    def to_dict(self) -> Dict:
        return {"name": self.name, "ok": self.ok, "grid": list(self.grid),
                "vmem": self.vmem.to_dict(), "race": self.race.to_dict(),
                "cost": self.cost.to_dict(), "failures": self.failures}


def audit_kernel(entry: KernelEntry, *,
                 budget: Optional[int] = None) -> KernelReport:
    """Capture one registered kernel and run all three contracts on it."""
    fn, args = entry.build()
    caps = capture_pallas_calls(fn, *args)
    failures: List[str] = []
    if len(caps) != 1:
        failures.append(
            f"[capture] {entry.name}: expected exactly one pallas_call in "
            f"the audit trace, found {len(caps)}")
    if not caps:
        return KernelReport(
            entry.name, (), VmemReport(entry.name, 0, 0, None, {}),
            RaceReport(entry.name, (), None, {}, []),
            CostReport(entry.name, (), 0.0, 0, 0, 0, 0.0, 0.0, 0.0, {}),
            failures)
    cap = caps[0]
    guard = entry.guard()
    return KernelReport(
        name=entry.name, grid=cap.grid,
        vmem=vmem_contract(cap, kernel=entry.name,
                           budget=budget if budget is not None
                           else entry.budget, guard=guard),
        race=race_contract(cap, kernel=entry.name),
        cost=cost_model(cap, kernel=entry.name),
        failures=failures)


def audit_all(registry=REGISTRY) -> List[KernelReport]:
    return [audit_kernel(e) for e in registry]


def registry_coverage() -> List[str]:
    """Every ``pl.pallas_call`` site in repro.kernels must be registered.

    Counts call sites in the package source (one kernel wrapper = one
    site) and compares against the registry, so a new kernel module cannot
    ship unaudited.
    """
    import pathlib

    import repro.kernels as pkg
    pkg_dir = pathlib.Path(pkg.__file__).parent
    sites: List[str] = []
    for path in sorted(pkg_dir.glob("*.py")):
        text = path.read_text()
        n = len(re.findall(r"\bpl\.pallas_call\s*\(", text))
        sites.extend([path.stem] * n)
    failures = []
    if len(sites) != len(REGISTRY):
        failures.append(
            f"[coverage] repro.kernels has {len(sites)} pallas_call sites "
            f"({', '.join(sites)}) but the audit registry lists "
            f"{len(REGISTRY)} kernels — register the new kernel in "
            "repro.kernels.introspect")
    return failures


# ---------------------------------------------------------------------------
# CLI: the CI gate
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="static Pallas VMEM/race/cost contracts over "
                    "repro.kernels")
    ap.add_argument("--json", default=None,
                    help="write the audit report to this path")
    args = ap.parse_args(argv)

    reports = audit_all()
    coverage = registry_coverage()
    report = {"ok": all(r.ok for r in reports) and not coverage,
              "coverage_failures": coverage,
              "kernels": [r.to_dict() for r in reports]}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)

    for r in reports:
        status = "OK" if r.ok else "FAIL"
        sem = r.race.dimension_semantics
        max_restream = max(
            (v["restream"] for v in r.cost.per_operand.values()),
            default=0.0)
        print(f"kernel_audit {status:4s} {r.name}: grid {r.grid} "
              f"semantics {sem}, vmem {r.vmem.structural_bytes}/"
              f"{r.vmem.budget_bytes} B, carried dims {r.race.required}, "
              f"{r.cost.flops:.3g} FLOP / {r.cost.bytes_touched} B "
              f"(max restream {max_restream:.1f}x)")
        for msg in (r.failures + r.vmem.failures + r.race.failures):
            print(f"  {msg}", file=sys.stderr)
    for msg in coverage:
        print(f"  {msg}", file=sys.stderr)
    if not report["ok"]:
        bad = [r.name for r in reports if not r.ok]
        print(f"kernel_audit: contracts FAILED ({', '.join(bad) or 'coverage'})",
              file=sys.stderr)
        return 1
    print(f"kernel_audit: all {len(reports)} kernel contracts hold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
