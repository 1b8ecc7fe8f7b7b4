"""jit-hygiene linter: AST rules distilled from this repo's actual bug history.

Every rule below names a bug class a review sweep (PRs 2-6) caught by hand in
shipped code; the linter makes the catch mechanical before the vocab-sharded
and async engines multiply the number of jitted paths.

Rules
-----
``traced-float``
    ``float(x)`` / ``int(x)`` on a possibly-traced value inside a function
    reachable from ``jax.jit`` / ``shard_map`` / ``pl.pallas_call``. Host
    coercion of a tracer either crashes (ConcretizationTypeError) or — worse —
    silently bakes the value into the compiled program and forces a recompile
    per distinct value.

``host-numpy``
    ``np.*`` called on possibly-traced values in a traced context: host numpy
    forces a device sync per call and falls out of the compiled program.

``static-argnames-array``
    ``static_argnames`` naming a parameter annotated as an array: arrays are
    unhashable jit-cache keys at best, a compile per distinct value at worst.

``pallas-dim-semantics``
    Every ``pl.pallas_call`` must pass explicit ``dimension_semantics``
    (via ``compiler_params``): the silent ``"parallel"`` default corrupts any
    kernel that carries state across a grid dimension under Megacore
    partitioning (the union_segsum SMEM-carry bug class).

``pallas-blockspec-misaligned``
    A ``pl.BlockSpec`` whose literal block shape has a trailing dim pair
    that is not a multiple of the TPU (8, 128) tile (size-1 dims exempt):
    Mosaic pads or re-lays-out misaligned windows, silently wasting VMEM
    and bandwidth. Computed block picks (``_block_sizes`` helpers) are
    exempt — the kernel-audit plane checks those against the guards.

``data-dep-shape``
    ``jnp.unique`` / ``jnp.nonzero`` / ``jnp.flatnonzero`` / ``jnp.argwhere``
    without ``size=`` (or one-argument ``jnp.where``) in a traced context:
    data-dependent output shapes cannot be jitted.

``donated-reuse``
    A buffer passed to a donated argument of a jitted function is read again
    after the call: the donation invalidated it. The safe idiom rebinds the
    holder in the same statement (``self.state, m = step(self.state, ...)``).

``shard-full-aggregate``
    A ``shard_map`` body calls a full (heat-fused) aggregate
    (``aggregate_rowsparse`` / ``sparse_cohort_aggregate``) instead of
    ``aggregate_rowsparse_partial``: each shard holds a PARTIAL cohort, so
    the fused N/n_m heat correction applies per shard and the cross-shard
    combine then sums already-corrected partials — PR 5's double-correction
    bug class.

``shard-missing-psum``
    ``jnp.sum`` / ``jnp.mean`` (or ``.sum()`` / ``.mean()``) inside a
    ``shard_map`` body with no ``psum`` / ``pmean`` in reach: the result
    collapses the SHARD's slice only and silently reports one shard's value
    as the cohort's (PR 5's metrics bug class). Reductions that feed a
    collective — directly or through an assigned name — are exempt;
    deliberately per-shard values (``P(axis)`` out_specs) carry an explained
    suppression.

Traced-context heuristic
------------------------
A function is considered traced when it (a) is decorated with / passed to a
jax tracing entry point (``jit``, ``vmap``, ``grad``, ``value_and_grad``,
``shard_map``, ``pallas_call``, ``scan``, ``cond``, ``while_loop``,
``fori_loop``, ``checkify``, possibly through ``functools.partial``), (b) is
a module-level or nested non-method function whose own body uses ``jnp.*`` /
``lax.*``, or (c) is called (by name) from a traced function. Methods are
presumed host context — the trainer/dataset orchestration layer.

Values are exempt from ``traced-float`` / ``host-numpy`` when they are
statically known at trace time: shape-derived expressions (``.shape`` /
``.ndim`` / ``.size`` / ``.dtype`` / ``len()``), parameters annotated
``int`` / ``float`` / ``bool`` / ``str``, names assigned from static
expressions, module globals, and closures over host-context enclosing scopes.

Allowlist
---------
Append ``# repro-lint: ok <rule>[,<rule>] -- <reason>`` to the offending
line (or the line above it). The reason is mandatory: a suppression without
one is itself reported (``bare-allowlist``), so the lint exits clean only
with zero unexplained suppressions.

Usage
-----
    python -m repro.analysis.lint src/ [--json report.json] [--list-rules]

Exit status 0 iff no violations. Stdlib-only by design: the CI
static-analysis job runs this without jax installed.
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

RULES: Dict[str, str] = {
    "traced-float": "float()/int() coercion of a possibly-traced value "
                    "inside a jit/shard_map/pallas-reachable function",
    "host-numpy": "host np.* call on possibly-traced values in a traced "
                  "context",
    "static-argnames-array": "static_argnames naming an array-annotated "
                             "parameter",
    "pallas-dim-semantics": "pl.pallas_call without explicit "
                            "dimension_semantics (compiler_params)",
    "pallas-blockspec-misaligned": "pl.BlockSpec literal block shape with "
                                   "trailing dims off the (8, 128) TPU tile",
    "data-dep-shape": "data-dependent output shape (jnp.unique/nonzero/... "
                      "without size=) under jit",
    "donated-reuse": "donated buffer re-referenced after the donating call",
    "shard-full-aggregate": "full heat-fused aggregate called inside a "
                            "shard_map body (partial + combine required)",
    "shard-missing-psum": "per-shard jnp reduction in a shard_map body "
                          "with no psum/pmean in reach",
    "bare-allowlist": "repro-lint suppression without a ' -- reason'",
}

#: names that mark a call target as a jax tracing entry point
_TRACE_ENTRIES = {
    "jit", "vmap", "pmap", "grad", "value_and_grad", "shard_map",
    "pallas_call", "scan", "cond", "while_loop", "fori_loop", "checkify",
    "custom_jvp", "custom_vjp", "remat", "checkpoint",
}

#: annotation name tails that mark a parameter as array-valued
_ARRAY_ANNOTATIONS = {"Array", "ndarray", "ArrayLike"}

#: annotation names that mark a parameter as a static scalar
_SCALAR_ANNOTATIONS = {"int", "float", "bool", "str"}

#: builtins whose result is static when every argument is static
_STATIC_BUILTINS = {
    "int", "float", "bool", "str", "len", "min", "max", "abs", "round",
    "sum", "tuple", "list", "sorted", "range", "divmod", "pow", "getattr",
    "isinstance", "hasattr", "type",
}

#: attribute reads that are static regardless of the base value
_STATIC_ATTRS = {"shape", "ndim", "size", "dtype", "itemsize"}

#: jnp callees with data-dependent output shapes unless size= is passed
_DATA_DEP_SHAPE_FNS = {"unique", "nonzero", "flatnonzero", "argwhere"}

_ALLOW_RE = re.compile(
    r"#\s*repro-lint:\s*ok\s+([a-z0-9*,\s-]+?)\s*(?:--\s*(\S.*))?$")


@dataclasses.dataclass
class Violation:
    rule: str
    path: str
    line: int
    col: int
    message: str

    def as_dict(self) -> Dict[str, object]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Suppression:
    rule: str
    path: str
    line: int
    reason: str

    def as_dict(self) -> Dict[str, object]:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# small AST helpers
# ---------------------------------------------------------------------------


def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` as a string, or None for non-name expressions."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


def _name_tail(node: ast.AST) -> Optional[str]:
    """Last component of a (possibly dotted) callee name."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


def _annotation_names(ann: Optional[ast.AST]) -> Set[str]:
    if ann is None:
        return set()
    return {n.id for n in ast.walk(ann) if isinstance(n, ast.Name)} | {
        n.attr for n in ast.walk(ann) if isinstance(n, ast.Attribute)}


def _target_names(target: ast.AST) -> List[str]:
    """Dotted names bound by an assignment target (tuples flattened)."""
    if isinstance(target, (ast.Tuple, ast.List)):
        out: List[str] = []
        for elt in target.elts:
            out.extend(_target_names(elt))
        return out
    if isinstance(target, ast.Starred):
        return _target_names(target.value)
    d = _dotted(target)
    return [d] if d else []


def _own_statements(fn: ast.AST) -> Iterable[ast.AST]:
    """Every node of ``fn``'s own scope (nested def/class bodies excluded)."""
    for stmt in fn.body:
        yield from _walk_scope(stmt)


def _walk_scope(node: ast.AST) -> Iterable[ast.AST]:
    yield node
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
        # the nested scope's body belongs to the nested scope; its decorators
        # and defaults still evaluate in ours
        for dec in getattr(node, "decorator_list", []):
            yield from _walk_scope(dec)
        return
    if isinstance(node, ast.Lambda):
        return
    for child in ast.iter_child_nodes(node):
        yield from _walk_scope(child)


def _flat_stmts(body: Sequence[ast.stmt]) -> Iterable[ast.stmt]:
    """Statements of a scope in source order, control-flow bodies flattened,
    nested function/class scopes skipped."""
    for st in body:
        yield st
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef)):
            continue
        for attr in ("body", "orelse", "finalbody"):
            sub = getattr(st, attr, None)
            if sub:
                yield from _flat_stmts(sub)
        for handler in getattr(st, "handlers", []) or []:
            yield from _flat_stmts(handler.body)


def _stmt_nodes(st: ast.stmt) -> Iterable[ast.AST]:
    """Nodes of one statement of :func:`_flat_stmts`: a control-flow
    statement's own header (a ``with`` item, an ``if`` test) without the
    bodies that :func:`_flat_stmts` yields as statements of their own."""
    if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
        yield from ast.walk(st)
        return
    for name, value in ast.iter_fields(st):
        if name in ("body", "orelse", "finalbody", "handlers"):
            continue
        for v in value if isinstance(value, list) else [value]:
            if isinstance(v, ast.AST):
                yield from ast.walk(v)


def _uses_tracer_namespace(fn: ast.AST) -> bool:
    """Does the function's own scope touch ``jnp.*`` / ``lax.*``?

    ``jax.random`` / ``jax.tree`` do not count: they are routine in host
    orchestration (seeding, pytree bookkeeping) and would misclassify it.
    """
    for node in _own_statements(fn):
        if isinstance(node, ast.Attribute):
            d = _dotted(node)
            if d and (d.startswith("jnp.") or d.startswith("lax.")
                      or d.startswith("jax.numpy.") or d.startswith("jax.lax.")):
                return True
    return False


# ---------------------------------------------------------------------------
# per-module analysis
# ---------------------------------------------------------------------------


class _FuncInfo:
    __slots__ = ("node", "parent", "is_method", "traced", "static_names")

    def __init__(self, node, parent, is_method):
        self.node = node
        self.parent = parent          # enclosing _FuncInfo or None (module)
        self.is_method = is_method
        self.traced = False
        self.static_names: Set[str] = set()


class _ModuleIndex(ast.NodeVisitor):
    """Collects the function table + module-global bindings."""

    def __init__(self):
        self.funcs: List[_FuncInfo] = []
        self.by_node: Dict[ast.AST, _FuncInfo] = {}
        self.by_name: Dict[str, List[_FuncInfo]] = {}
        self.globals: Set[str] = set()
        self._stack: List[_FuncInfo] = []
        self._class_depth = 0

    def visit_Module(self, node):
        for st in node.body:
            if isinstance(st, (ast.Import, ast.ImportFrom)):
                for alias in st.names:
                    self.globals.add((alias.asname or alias.name).split(".")[0])
            elif isinstance(st, ast.Assign):
                for t in st.targets:
                    self.globals.update(_target_names(t))
            elif isinstance(st, ast.AnnAssign) and st.target is not None:
                self.globals.update(_target_names(st.target))
            elif isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                self.globals.add(st.name)
        self.generic_visit(node)

    def _visit_func(self, node):
        info = _FuncInfo(node, self._stack[-1] if self._stack else None,
                         is_method=self._class_depth > 0 and not self._stack)
        self.funcs.append(info)
        self.by_node[node] = info
        self.by_name.setdefault(node.name, []).append(info)
        self._stack.append(info)
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_ClassDef(self, node):
        self._class_depth += 1
        self.generic_visit(node)
        self._class_depth -= 1


def _mark_traced(index: _ModuleIndex, tree: ast.Module) -> None:
    """Seed + propagate the traced-context marking over the function table."""
    # (a) explicit roots: decorators and arguments of tracing entry points
    explicit: Set[str] = set()
    for info in index.funcs:
        for dec in info.node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            tail = _name_tail(target)
            if tail in _TRACE_ENTRIES or tail == "partial":
                inner = None
                if isinstance(dec, ast.Call) and dec.args:
                    inner = _name_tail(dec.args[0])
                if tail in _TRACE_ENTRIES or inner in _TRACE_ENTRIES:
                    info.traced = True
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        tail = _name_tail(node.func)
        if tail not in _TRACE_ENTRIES:
            continue
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if isinstance(arg, ast.Name):
                explicit.add(arg.id)
            elif (isinstance(arg, ast.Call)
                  and _name_tail(arg.func) == "partial" and arg.args
                  and isinstance(arg.args[0], ast.Name)):
                explicit.add(arg.args[0].id)
    for name in explicit:
        for info in index.by_name.get(name, []):
            info.traced = True

    # (b) presumption: non-method functions whose own scope uses jnp/lax
    for info in index.funcs:
        if not info.is_method and _uses_tracer_namespace(info.node):
            info.traced = True

    # (c) downward call-graph propagation (by bare callee name)
    changed = True
    while changed:
        changed = False
        for info in index.funcs:
            if not info.traced:
                continue
            for node in _own_statements(info.node):
                if isinstance(node, ast.Call) and isinstance(node.func,
                                                             ast.Name):
                    for callee in index.by_name.get(node.func.id, []):
                        if not callee.traced and not callee.is_method:
                            callee.traced = True
                            changed = True


# ---------------------------------------------------------------------------
# static-provenance analysis
# ---------------------------------------------------------------------------


def _scope_bindings(fn: ast.AST) -> Set[str]:
    """Every name the function's own scope binds (params + assignments)."""
    out: Set[str] = set()
    args = fn.args
    for a in (args.posonlyargs + args.args + args.kwonlyargs
              + ([args.vararg] if args.vararg else [])
              + ([args.kwarg] if args.kwarg else [])):
        out.add(a.arg)
    for node in _own_statements(fn):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                out.update(_target_names(t))
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            out.update(_target_names(node.target))
        elif isinstance(node, ast.For):
            out.update(_target_names(node.target))
        elif isinstance(node, ast.withitem) and node.optional_vars:
            out.update(_target_names(node.optional_vars))
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
            out.add(node.name)
    return out


def _host_closure_names(info: _FuncInfo, index: _ModuleIndex) -> Set[str]:
    """Names bound by host-context enclosing scopes (static for ``info``)."""
    out: Set[str] = set(index.globals)
    cur = info.parent
    while cur is not None:
        if not cur.traced:
            out.update(_scope_bindings(cur.node))
        cur = cur.parent
    return out


class _StaticScope:
    """Static-provenance tracking for one function scope."""

    def __init__(self, info: _FuncInfo, index: _ModuleIndex):
        self.static: Set[str] = set()
        self.closure = _host_closure_names(info, index)
        fn = info.node
        args = fn.args
        all_args = args.posonlyargs + args.args + args.kwonlyargs
        defaults = list(args.defaults)
        # align positional defaults to the tail of (posonly + args)
        pos = args.posonlyargs + args.args
        defaulted = {a.arg for a, _ in zip(pos[len(pos) - len(defaults):],
                                           defaults)}
        kw_defaulted = {a.arg for a, d in zip(args.kwonlyargs,
                                              args.kw_defaults) if d is not None}
        for a in all_args:
            names = _annotation_names(a.annotation)
            if names & _SCALAR_ANNOTATIONS and not names & _ARRAY_ANNOTATIONS:
                self.static.add(a.arg)
        # parameters with scalar-constant defaults and no annotation are
        # treated as static knobs (block sizes, flags)
        for a, d in zip(pos[len(pos) - len(defaults):], defaults):
            if a.annotation is None and isinstance(d, ast.Constant) \
                    and not isinstance(d.value, (bytes,)):
                self.static.add(a.arg)
        for a, d in zip(args.kwonlyargs, args.kw_defaults):
            if d is not None and a.annotation is None \
                    and isinstance(d, ast.Constant):
                self.static.add(a.arg)
        del defaulted, kw_defaulted
        # fixpoint over this scope's assignments
        for _ in range(3):
            changed = False
            for node in _own_statements(fn):
                if isinstance(node, ast.Assign):
                    if self.is_static(node.value):
                        for t in node.targets:
                            for n in _target_names(t):
                                if n not in self.static:
                                    self.static.add(n)
                                    changed = True
                elif isinstance(node, ast.AnnAssign) and node.value is not None:
                    names = _annotation_names(node.annotation)
                    if (names & _SCALAR_ANNOTATIONS
                            or self.is_static(node.value)):
                        for n in _target_names(node.target):
                            if n not in self.static:
                                self.static.add(n)
                                changed = True
                elif isinstance(node, ast.For):
                    if self.is_static(node.iter):
                        for n in _target_names(node.target):
                            if n not in self.static:
                                self.static.add(n)
                                changed = True
                elif isinstance(node, (ast.GeneratorExp, ast.ListComp,
                                       ast.SetComp, ast.DictComp)):
                    for g in node.generators:
                        if self.is_static(g.iter):
                            for n in _target_names(g.target):
                                if n not in self.static:
                                    self.static.add(n)
                                    changed = True
            if not changed:
                break

    def is_static(self, e: ast.AST) -> bool:
        """Is ``e`` statically known at trace time (never a tracer)?"""
        if isinstance(e, ast.Constant):
            return True
        if isinstance(e, ast.Name):
            return e.id in self.static or e.id in self.closure
        if isinstance(e, ast.Attribute):
            if e.attr in _STATIC_ATTRS:
                return True
            return self.is_static(e.value)
        if isinstance(e, ast.Subscript):
            return self.is_static(e.value)
        if isinstance(e, ast.Call):
            tail = _name_tail(e.func)
            root = _dotted(e.func) or ""
            callable_ok = (tail in _STATIC_BUILTINS
                           or root.startswith("np.")
                           or root.startswith("numpy.")
                           or root.startswith("math."))
            if not callable_ok:
                return False
            return all(self.is_static(a) for a in e.args) and all(
                self.is_static(kw.value) for kw in e.keywords)
        if isinstance(e, ast.BinOp):
            return self.is_static(e.left) and self.is_static(e.right)
        if isinstance(e, ast.UnaryOp):
            return self.is_static(e.operand)
        if isinstance(e, ast.BoolOp):
            return all(self.is_static(v) for v in e.values)
        if isinstance(e, ast.Compare):
            return self.is_static(e.left) and all(
                self.is_static(c) for c in e.comparators)
        if isinstance(e, ast.IfExp):
            return (self.is_static(e.test) and self.is_static(e.body)
                    and self.is_static(e.orelse))
        if isinstance(e, (ast.Tuple, ast.List)):
            return all(self.is_static(x) for x in e.elts)
        if isinstance(e, ast.Starred):
            return self.is_static(e.value)
        if isinstance(e, ast.GeneratorExp):
            # sum(... for k in feature_keys)-style reductions over static
            # iterables of static expressions
            return all(self.is_static(g.iter) for g in e.generators) \
                and self.is_static(e.elt)
        return False


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------


def _check_traced_coercions(info: _FuncInfo, index: _ModuleIndex, path: str,
                            out: List[Violation]) -> None:
    scope = _StaticScope(info, index)
    for node in _own_statements(info.node):
        if not isinstance(node, ast.Call):
            continue
        tail = _name_tail(node.func)
        root = _dotted(node.func) or ""
        if isinstance(node.func, ast.Name) and tail in ("float", "int") \
                and len(node.args) == 1 and not node.keywords:
            if not scope.is_static(node.args[0]):
                out.append(Violation(
                    "traced-float", path, node.lineno, node.col_offset,
                    f"{tail}() on a possibly-traced value in "
                    f"{info.node.name}(): use jnp casts, or annotate the "
                    "source as a static scalar"))
        elif root.startswith("np.") or root.startswith("numpy."):
            args = list(node.args) + [kw.value for kw in node.keywords]
            if args and not all(scope.is_static(a) for a in args):
                out.append(Violation(
                    "host-numpy", path, node.lineno, node.col_offset,
                    f"host {root}() on possibly-traced values in "
                    f"{info.node.name}(): use the jnp equivalent"))


def _check_data_dep_shapes(info: _FuncInfo, path: str,
                           out: List[Violation]) -> None:
    for node in _own_statements(info.node):
        if not isinstance(node, ast.Call):
            continue
        root = _dotted(node.func) or ""
        if not (root.startswith("jnp.") or root.startswith("jax.numpy.")):
            continue
        tail = _name_tail(node.func)
        kwargs = {kw.arg for kw in node.keywords}
        if tail in _DATA_DEP_SHAPE_FNS and "size" not in kwargs:
            out.append(Violation(
                "data-dep-shape", path, node.lineno, node.col_offset,
                f"jnp.{tail} without size= in {info.node.name}(): the "
                "output shape is data-dependent and cannot be jitted"))
        elif tail == "where" and len(node.args) == 1 and not kwargs:
            out.append(Violation(
                "data-dep-shape", path, node.lineno, node.col_offset,
                f"one-argument jnp.where in {info.node.name}() is "
                "jnp.nonzero in disguise: pass size= via jnp.nonzero"))


def _check_pallas_semantics(tree: ast.Module, index: _ModuleIndex, path: str,
                            out: List[Violation]) -> None:
    def encloser(node):
        best = None
        for info in index.funcs:
            f = info.node
            if (f.lineno <= node.lineno <= (f.end_lineno or f.lineno)
                    and (best is None or f.lineno > best.node.lineno)):
                best = info
        return best

    def binds_compiler_params(fn: ast.AST) -> bool:
        for n in _own_statements(fn):
            if isinstance(n, ast.Call) and any(
                    kw.arg == "compiler_params" for kw in n.keywords):
                return True
            if isinstance(n, ast.Assign):
                for t in n.targets:
                    if (isinstance(t, ast.Subscript)
                            and isinstance(t.slice, ast.Constant)
                            and t.slice.value == "compiler_params"):
                        return True
        return False

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        tail = _name_tail(node.func)
        if tail == "pallas_call":
            if any(kw.arg == "compiler_params" for kw in node.keywords):
                continue
            info = encloser(node)
            if info is not None and binds_compiler_params(info.node):
                continue
            out.append(Violation(
                "pallas-dim-semantics", path, node.lineno, node.col_offset,
                "pl.pallas_call without compiler_params: pass explicit "
                "dimension_semantics (Megacore partitioning corrupts "
                "grid-carried state under the silent 'parallel' default)"))
        elif tail == "CompilerParams":
            if not any(kw.arg == "dimension_semantics"
                       for kw in node.keywords):
                out.append(Violation(
                    "pallas-dim-semantics", path, node.lineno,
                    node.col_offset,
                    "CompilerParams without dimension_semantics"))
        elif tail and tail.endswith("compiler_params") and tail != \
                "compiler_params":
            # helper wrappers (e.g. _tpu_compiler_params): a bare zero-
            # argument call inherits whatever default the helper bakes in —
            # the call site must state the grid's semantics
            if not node.args and not any(
                    kw.arg in ("semantics", "dimension_semantics")
                    for kw in node.keywords):
                out.append(Violation(
                    "pallas-dim-semantics", path, node.lineno,
                    node.col_offset,
                    f"{tail}() call relies on the helper's default "
                    "dimension_semantics: pass them explicitly per grid"))


def _check_blockspec_alignment(tree: ast.Module, path: str,
                               out: List[Violation]) -> None:
    """pallas-blockspec-misaligned: literal block shapes off the TPU tile.

    Only ALL-literal shapes are judged — a computed dim (``v_blk``, ``hd``)
    means the block pick flows through a ``_block_sizes`` helper, which the
    kernel-audit plane pins against the kernel's guard instead. Size-1 dims
    are exempt: squeezed / leading axes are laid out for free.
    """
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and _name_tail(node.func) == "BlockSpec"):
            continue
        shape_node = node.args[0] if node.args else None
        if shape_node is None:
            for kw in node.keywords:
                if kw.arg == "block_shape":
                    shape_node = kw.value
        if not isinstance(shape_node, (ast.Tuple, ast.List)):
            continue
        elts = shape_node.elts
        if not elts or not all(isinstance(e, ast.Constant)
                               and isinstance(e.value, int)
                               for e in elts):
            continue
        dims = [e.value for e in elts]
        bad: List[str] = []
        last = dims[-1]
        if last != 1 and last % 128 != 0:
            bad.append(f"lane dim {last} is not a multiple of 128")
        if len(dims) >= 2:
            sub = dims[-2]
            if sub != 1 and sub % 8 != 0:
                bad.append(f"sublane dim {sub} is not a multiple of 8")
        if bad:
            out.append(Violation(
                "pallas-blockspec-misaligned", path, node.lineno,
                node.col_offset,
                f"pl.BlockSpec block shape {tuple(dims)}: "
                f"{'; '.join(bad)} — TPU tiles are (8, 128), so Mosaic "
                "pads/re-lays-out this window, wasting VMEM and bandwidth"))


def _static_argnames_values(call: ast.Call) -> List[Tuple[str, ast.AST]]:
    for kw in call.keywords:
        if kw.arg != "static_argnames":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, str):
            return [(v.value, v)]
        if isinstance(v, (ast.Tuple, ast.List)):
            return [(e.value, e) for e in v.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, str)]
    return []


def _check_static_argnames(tree: ast.Module, index: _ModuleIndex, path: str,
                           out: List[Violation]) -> None:
    def annotated_array_params(fn: ast.AST) -> Set[str]:
        bad = set()
        args = fn.args
        for a in args.posonlyargs + args.args + args.kwonlyargs:
            names = _annotation_names(a.annotation)
            if names & _ARRAY_ANNOTATIONS:
                bad.add(a.arg)
        return bad

    # decorator form: @functools.partial(jax.jit, static_argnames=...) / the
    # call form jax.jit(f, static_argnames=...) with f a module function
    for info in index.funcs:
        fn = info.node
        bad = annotated_array_params(fn)
        if not bad:
            continue
        for dec in fn.decorator_list:
            if isinstance(dec, ast.Call):
                for name, node in _static_argnames_values(dec):
                    if name in bad:
                        out.append(Violation(
                            "static-argnames-array", path, node.lineno,
                            node.col_offset,
                            f"static_argnames={name!r} on {fn.name}() names "
                            "an array-annotated parameter: arrays are not "
                            "hashable jit-cache keys"))
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and _name_tail(node.func) == "jit"):
            continue
        names = _static_argnames_values(node)
        if not names or not node.args or not isinstance(node.args[0], ast.Name):
            continue
        for target in index.by_name.get(node.args[0].id, []):
            bad = annotated_array_params(target.node)
            for name, vnode in names:
                if name in bad:
                    out.append(Violation(
                        "static-argnames-array", path, vnode.lineno,
                        vnode.col_offset,
                        f"static_argnames={name!r} on "
                        f"{target.node.name}() names an array-annotated "
                        "parameter: arrays are not hashable jit-cache keys"))


def _donating_call(node: ast.Call) -> Optional[Set[int]]:
    """Donated positional indices if ``node`` constructs a donated callable."""
    for kw in node.keywords:
        if kw.arg in ("donate_argnums", "donate_argnames"):
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return {v.value}
            if isinstance(v, (ast.Tuple, ast.List)) and all(
                    isinstance(e, ast.Constant) for e in v.elts):
                idx = {e.value for e in v.elts if isinstance(e.value, int)}
                return idx if idx else None    # empty literal: no donation
            return {0}                         # non-literal: assume arg 0
    return None


def _check_donated_reuse(tree: ast.Module, index: _ModuleIndex, path: str,
                         out: List[Violation]) -> None:
    donated: Dict[str, Set[int]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            idx = _donating_call(node.value)
            if idx:
                for t in node.targets:
                    for name in _target_names(t):
                        donated[name] = idx
    if not donated:
        return

    for info in index.funcs:
        active: Dict[str, Tuple[int, int]] = {}   # dotted name -> call pos
        for st in _flat_stmts(info.node.body):
            if active:
                for n in _stmt_nodes(st):
                    if isinstance(n, (ast.Name, ast.Attribute)) \
                            and isinstance(getattr(n, "ctx", None), ast.Load):
                        d = _dotted(n)
                        if d in active:
                            line, _ = active.pop(d)
                            out.append(Violation(
                                "donated-reuse", path, n.lineno, n.col_offset,
                                f"{d!r} was donated at line {line} and is "
                                "re-referenced here: the donation "
                                "invalidated the buffer — rebind it in the "
                                "donating statement"))
            targets: List[str] = []
            if isinstance(st, ast.Assign):
                for t in st.targets:
                    targets.extend(_target_names(t))
            elif isinstance(st, (ast.AugAssign, ast.AnnAssign)):
                targets.extend(_target_names(st.target))
            for name in targets:
                active.pop(name, None)
            for n in _stmt_nodes(st):
                if isinstance(n, ast.Call):
                    callee = _dotted(n.func)
                    if callee in donated:
                        for i in donated[callee]:
                            if i < len(n.args):
                                d = _dotted(n.args[i])
                                if d and d not in targets:
                                    active[d] = (n.lineno, n.col_offset)


#: full (heat-fused) aggregates that must never run per shard: inside a
#: shard_map body each shard sees a PARTIAL cohort, so the fused N/n_m heat
#: correction would apply per shard and then be summed across shards
_SHARD_BANNED_AGGREGATES = {"aggregate_rowsparse", "sparse_cohort_aggregate"}

#: collective callees that legitimately consume a per-shard reduction
_COLLECTIVE_CALLS = {"psum", "pmean", "pmax", "pmin", "all_gather",
                     "all_to_all", "ppermute", "psum_scatter"}

#: reduction callee tails that collapse a per-shard axis
_REDUCTION_TAILS = {"sum", "mean"}


def _walk_shard_scope(root: ast.AST) -> Iterable[ast.AST]:
    """Own scope of a function/lambda, DESCENDING into lambdas.

    Unlike :func:`_walk_scope`, lambda bodies are included: a shard_map body
    is routinely ``lambda p, d, c: body(p, d, None, c)`` and the reference
    to ``body`` lives inside the lambda. Nested def/class scopes are still
    excluded — they are marked as their own shard scopes when referenced.
    """
    stack = [root.body] if isinstance(root, ast.Lambda) else list(root.body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _shard_scopes(index: _ModuleIndex, tree: ast.Module) -> List[ast.AST]:
    """Scopes that execute inside a ``shard_map`` body.

    Roots: the callable passed to ``shard_map`` (first positional argument,
    possibly a lambda or a ``partial``). Propagation: any module function a
    shard scope references by name joins the set, to fixpoint — the body
    helpers (``run_local``, ``agg_leaf``-style tree_map callbacks) execute
    under the same mesh axis. Within-module only, so a sparse-plane module
    that merely DEFINES combine helpers is never marked.
    """
    scopes: List[ast.AST] = []
    seen: Set[ast.AST] = set()
    work: List[ast.AST] = []
    names: Set[str] = set()
    done: Set[str] = set()

    def add_scope(node: ast.AST) -> None:
        if node not in seen:
            seen.add(node)
            work.append(node)

    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and _name_tail(node.func) == "shard_map"):
            continue
        cand = node.args[0] if node.args else None
        if cand is None:
            for kw in node.keywords:
                if kw.arg in ("f", "fun"):
                    cand = kw.value
        if isinstance(cand, ast.Lambda):
            add_scope(cand)
        elif isinstance(cand, ast.Call) and _name_tail(cand.func) == "partial" \
                and cand.args:
            t = _name_tail(cand.args[0])
            if t:
                names.add(t)
        elif cand is not None:
            t = _name_tail(cand)
            if t:
                names.add(t)

    while work or names - done:
        for name in sorted(names - done):
            done.add(name)
            for info in index.by_name.get(name, []):
                add_scope(info.node)
        while work:
            scope = work.pop()
            scopes.append(scope)
            for sub in _walk_shard_scope(scope):
                if isinstance(sub, ast.Name) \
                        and isinstance(sub.ctx, ast.Load) \
                        and sub.id in index.by_name:
                    names.add(sub.id)
    return scopes


def _check_shard_hygiene(tree: ast.Module, index: _ModuleIndex, path: str,
                         out: List[Violation]) -> None:
    """shard-full-aggregate + shard-missing-psum over every shard scope."""
    for scope in _shard_scopes(index, tree):
        sname = getattr(scope, "name", "<lambda>")
        nodes = list(_walk_shard_scope(scope))
        # reductions nested under a collective call are combined on the spot
        exempt: Set[ast.AST] = set()
        fed: Set[str] = set()     # names a collective consumes later
        for n in nodes:
            if isinstance(n, ast.Call) \
                    and _name_tail(n.func) in _COLLECTIVE_CALLS:
                exempt.update(ast.walk(n))
                for a in list(n.args) + [kw.value for kw in n.keywords]:
                    fed.update(s.id for s in ast.walk(a)
                               if isinstance(s, ast.Name))
        # reductions whose assigned name feeds a collective elsewhere in the
        # scope are the two-statement combine idiom
        for n in nodes:
            if isinstance(n, ast.Assign):
                tnames: List[str] = []
                for t in n.targets:
                    tnames.extend(_target_names(t))
                if any(t in fed for t in tnames):
                    exempt.update(ast.walk(n.value))
        for n in nodes:
            if not isinstance(n, ast.Call):
                continue
            tail = _name_tail(n.func)
            if tail in _SHARD_BANNED_AGGREGATES:
                out.append(Violation(
                    "shard-full-aggregate", path, n.lineno, n.col_offset,
                    f"{tail}() inside the shard_map body {sname}(): each "
                    "shard holds a PARTIAL cohort, so the fused heat "
                    "correction applies per shard and the cross-shard "
                    "combine sums already-corrected partials — use "
                    "aggregate_rowsparse_partial + "
                    "combine_rowsparse_partials"))
                continue
            root = _dotted(n.func) or ""
            is_reduction = (tail in _REDUCTION_TAILS and (
                root.startswith("jnp.") or root.startswith("jax.numpy.")
                or (isinstance(n.func, ast.Attribute)
                    and not root.startswith("np.")
                    and not root.startswith("numpy."))))
            if not is_reduction or n in exempt:
                continue
            if any(kw.arg == "axis_name" for kw in n.keywords):
                continue
            out.append(Violation(
                "shard-missing-psum", path, n.lineno, n.col_offset,
                f"{tail}() reduction in the shard_map body {sname}() with "
                "no psum/pmean in reach: the result collapses this SHARD's "
                "slice only — combine it over the mesh axis "
                "(jax.lax.psum/pmean), or suppress with the per-shard "
                "intent explained"))


# ---------------------------------------------------------------------------
# allowlist + driver
# ---------------------------------------------------------------------------


def _collect_allowlist(source: str, path: str):
    """line -> (rules, reason); plus bare-suppression violations.

    A suppression comment covers its own line and — when the comment block
    stands alone — every following comment-only continuation line plus the
    first code line after it, so multi-line explanations stay legal.
    """
    allow: Dict[int, Tuple[Set[str], Optional[str]]] = {}
    bare: List[Violation] = []
    lines = source.splitlines()
    for i, line in enumerate(lines, start=1):
        m = _ALLOW_RE.search(line)
        if not m:
            continue
        rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
        # the reason may continue onto following comment-only lines
        reason = m.group(2)
        j = i
        while j < len(lines) and lines[j].strip().startswith("#"):
            if reason is None:
                cont = lines[j].strip().lstrip("#").strip()
                if cont.startswith("--"):
                    cont = cont[2:].strip()
                reason = cont or None
            j += 1
        unknown = {r for r in rules if r != "*" and r not in RULES}
        if unknown:
            bare.append(Violation(
                "bare-allowlist", path, i, 0,
                f"repro-lint suppression names unknown rule(s) "
                f"{sorted(unknown)}"))
        if not reason:
            bare.append(Violation(
                "bare-allowlist", path, i, 0,
                "repro-lint suppression without a ' -- reason': every "
                "allowlisted line must explain itself"))
        entry = (rules, reason)
        allow[i] = entry
        # comment-only suppression: extend through the block to the first
        # code line it annotates
        if line.strip().startswith("#"):
            for k in range(i + 1, j + 2):
                allow.setdefault(k, entry)
    return allow, bare


def lint_source(source: str, path: str):
    """Lint one module's source. Returns (violations, suppressions)."""
    allow, bare = _collect_allowlist(source, path)
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Violation("traced-float", path, e.lineno or 0, 0,
                          f"syntax error: {e.msg}")], []
    index = _ModuleIndex()
    index.visit(tree)
    _mark_traced(index, tree)

    raw: List[Violation] = []
    for info in index.funcs:
        if info.traced:
            _check_traced_coercions(info, index, path, raw)
            _check_data_dep_shapes(info, path, raw)
    _check_pallas_semantics(tree, index, path, raw)
    _check_blockspec_alignment(tree, path, raw)
    _check_static_argnames(tree, index, path, raw)
    _check_donated_reuse(tree, index, path, raw)
    _check_shard_hygiene(tree, index, path, raw)

    violations: List[Violation] = list(bare)
    suppressions: List[Suppression] = []
    for v in raw:
        hit = None
        for line in (v.line, v.line - 1):
            entry = allow.get(line)
            if entry and ("*" in entry[0] or v.rule in entry[0]):
                hit = entry
                break
        if hit and hit[1]:
            suppressions.append(Suppression(v.rule, path, v.line, hit[1]))
        elif hit:                      # suppressed but unexplained: already a
            continue                   # bare-allowlist violation on that line
        else:
            violations.append(v)
    return violations, suppressions


def iter_python_files(paths: Sequence[str]) -> Iterable[str]:
    for p in paths:
        if os.path.isfile(p):
            yield p
            continue
        for root, dirs, files in os.walk(p):
            dirs[:] = sorted(d for d in dirs
                             if not d.startswith(".") and d != "__pycache__")
            for f in sorted(files):
                if f.endswith(".py"):
                    yield os.path.join(root, f)


def lint_paths(paths: Sequence[str]):
    """Lint every .py file under ``paths``; returns (violations,
    suppressions, files_scanned)."""
    violations: List[Violation] = []
    suppressions: List[Suppression] = []
    count = 0
    for path in iter_python_files(paths):
        count += 1
        with open(path, encoding="utf-8") as f:
            source = f.read()
        v, s = lint_source(source, path)
        violations.extend(v)
        suppressions.extend(s)
    return violations, suppressions, count


def report_dict(violations, suppressions, files_scanned: int,
                paths: Sequence[str]) -> Dict[str, object]:
    return {
        "tool": "repro.analysis.lint",
        "version": 1,
        "paths": list(paths),
        "files_scanned": files_scanned,
        "rules": dict(RULES),
        "ok": not violations,
        "violations": [v.as_dict() for v in violations],
        "suppressions": [s.as_dict() for s in suppressions],
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="jit-hygiene linter (see module docstring for the rules)")
    ap.add_argument("paths", nargs="*", default=["src"],
                    help="files or directories to lint (default: src)")
    ap.add_argument("--json", metavar="PATH",
                    help="write the machine-readable report to PATH")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        for name, desc in RULES.items():
            print(f"{name}: {desc}")
        return 0

    paths = args.paths or ["src"]
    violations, suppressions, count = lint_paths(paths)
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(report_dict(violations, suppressions, count, paths),
                      f, indent=2)
    if not args.quiet:
        for v in violations:
            print(f"{v.path}:{v.line}:{v.col}: [{v.rule}] {v.message}",
                  file=sys.stderr)
        status = "clean" if not violations else f"{len(violations)} violation(s)"
        print(f"repro-lint: {count} file(s), {status}, "
              f"{len(suppressions)} explained suppression(s)")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
