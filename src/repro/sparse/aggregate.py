"""Server-side aggregation over row-sparse cohort updates.

The FedSubAvg server step on the sparse plane is a segment-sum: every client
contributes ``(ids_i, rows_i)``; the server sums rows landing on the same
feature id, scales by ``1/K`` (cohort mean) and fuses the heat correction
``N / n_m`` — one pass over the non-zeros, never touching cold rows.

Three union backends, selected at runtime (``union_backend="auto"``):

``bitmap``  mark touched rows in a (V,) bitmap, rank by cumsum — O(V)
            streamed vector work, the CPU fast path for moderate V.
``sort``    sort/searchsorted — O(T log T), for huge feature spaces.
``pallas``  the ``union_segsum`` kernel (``repro.kernels``): a sort of the
            T ids, then the segment-sum as one-hot MXU matmuls over row
            tiles — O(T log T + T t_blk D), no sweep of V; the server
            hot-loop path whenever the union fits VMEM (compiled on TPU;
            interpret-mode parity elsewhere).

``aggregate_rowsparse_dense`` additionally routes through the dense-output
``rowsparse_scatter`` kernel when the server applies into a dense table.

Cohort-sharded rounds split the segment-sum in two: each device shard runs
``aggregate_rowsparse_partial`` over its own clients (a plain union
segment-sum — no heat, no cohort scale), and ``combine_rowsparse_partials``
reduces the per-shard partial unions across the mesh axis inside
``shard_map`` — either a ``psum`` of the densified rows (small tables) or a
gathered union-of-unions that stays RowSparse (large tables), with the heat
correction and cohort mean fused exactly once at the combine.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.aggregate import HeatSpec, correct_dense_leaf
from repro.core.heat import heat_correction_factors
from repro.sparse.encode import DEFAULT_SPARSE_SPACES
from repro.sparse.rowsparse import RowSparse, is_rowsparse, remap_ids, unique_ids_padded

Array = jax.Array


def heat_factor_at(heat: Array, ids: Array, total: float,
                   scale: float = 1.0) -> Array:
    """Per-row ``scale * N / n_m`` gathered at ``ids`` (0 for cold/pad rows).

    The single source of the FedSubAvg correction in gathered (row-sparse)
    form — the dense-broadcast twin is ``heat_correction_factors``.
    """
    h = jnp.take(heat, jnp.maximum(ids, 0))
    f = jnp.where(h > 0, total / jnp.maximum(h, 1.0), 0.0)
    return jnp.where(ids >= 0, f * scale, 0.0)


def correct_rowsparse(rs: RowSparse, heat: Optional[Array], total: float,
                      scale: float = 1.0) -> RowSparse:
    """Scale an unbatched RowSparse by ``scale * N / n_m`` (heat given) or by
    ``scale`` with padding rows zeroed (heat ``None`` — the FedAvg baseline).

    The RowSparse twin of ``correct_dense_leaf``: both sparse server paths
    (fused aggregation and the flat fedsgd-on-sparse plan) route through it,
    so the correction can never drift between them.
    """
    if heat is not None:
        factor = heat_factor_at(jnp.asarray(heat), rs.ids, total, scale)
    else:
        factor = jnp.where(rs.ids >= 0, scale, 0.0)
    bshape = factor.shape + (1,) * (rs.rows.ndim - rs.ids.ndim)
    return RowSparse(rs.ids, rs.rows * factor.reshape(bshape), rs.num_rows)


#: dense-bitmap union is O(V) vectorised work and V bits of scratch — the
#: fast path whenever the feature space fits comfortably in cache-adjacent
#: memory; beyond this the O(T log T) sort path takes over.
_BITMAP_MAX_ROWS = 1 << 22


def _resolve_backend(backend: str, num_rows: int, cap: int,
                     row_elems: int, num_elems: int) -> str:
    """Runtime union-backend selection for ``"auto"``.

    On TPU the ``union_segsum`` kernel wins whenever its VMEM-resident
    union fits the budget; otherwise (and everywhere on CPU, where the
    interpreter would crawl) the jnp backends split by feature-space size.
    ``num_elems`` is forwarded so the budget check uses the same row tile
    the kernel will actually pick.
    """
    if backend != "auto":
        return backend
    from repro.kernels.heat_scatter import on_tpu
    from repro.kernels.union_segsum import fits_vmem
    # beyond the bitmap regime the sort backend is kept until a cell with
    # such a table has measured the kernel there
    if (on_tpu() and num_rows <= _BITMAP_MAX_ROWS
            and fits_vmem(cap, row_elems, t=num_elems)):
        return "pallas"
    return "bitmap" if num_rows <= _BITMAP_MAX_ROWS else "sort"


def _union_and_slots(flat_ids: Array, num_rows: int, cap: int, backend: str):
    """(union ids (cap,), per-element slot (T,)) under either jnp backend.

    ``bitmap``: mark touched rows in a (V,) bitmap, rank by cumsum, compact
    with size-bounded ``nonzero`` — no sort, everything streams. ``sort``:
    the generic O(T log T) path for huge feature spaces. (The ``pallas``
    backend derives its slots inside ``union_segsum`` —
    ``aggregate_rowsparse`` dispatches to it before reaching here.)
    """
    if backend == "auto":
        backend = "bitmap" if num_rows <= _BITMAP_MAX_ROWS else "sort"
    if backend == "bitmap":
        safe = jnp.where(flat_ids >= 0, flat_ids, num_rows)
        mark = jnp.zeros((num_rows,), bool).at[safe].set(True, mode="drop")
        rank = jnp.cumsum(mark.astype(jnp.int32)) - 1
        union = jnp.nonzero(mark, size=cap, fill_value=-1)[0].astype(jnp.int32)
        pos = jnp.take(rank, jnp.minimum(safe, num_rows - 1))
        pos = jnp.where(flat_ids >= 0, pos, cap)         # pads -> dropped
        return union, pos
    if backend == "sort":
        union = unique_ids_padded(flat_ids, cap)
        pos = remap_ids(flat_ids, union)
        return union, jnp.where(flat_ids >= 0, pos, cap)
    raise ValueError(backend)


def aggregate_rowsparse(stacked: RowSparse, heat: Optional[Array] = None,
                        total: float = 1.0, scale: float = 1.0,
                        union_capacity: Optional[int] = None,
                        union_backend: str = "auto") -> RowSparse:
    """Segment-sum a stacked cohort ``RowSparse`` into its union-id rows.

    ``stacked``: ids ``(K, R)``, rows ``(K, R, ...)``. Returns an unbatched
    RowSparse on the cohort's union ids (capacity ``min(V, K*R)`` unless
    given), rows scaled by ``scale`` and — when ``heat`` is provided — by the
    fused FedSubAvg correction ``total / n_m``. O(K R D) on the payload plus
    the union cost (bitmap: O(V) streamed; sort: O(K R log K R)); the dense
    ``(V, D)`` update is never materialised.
    """
    k, r = stacked.ids.shape
    cap = union_capacity or min(stacked.num_rows, k * r)
    flat_ids = stacked.ids.reshape(-1)
    flat_rows = stacked.rows.reshape((k * r,) + tuple(stacked.rows.shape[2:]))
    row_elems = int(flat_rows.size) // max(k * r, 1)

    union_backend = _resolve_backend(union_backend, stacked.num_rows, cap,
                                     row_elems, k * r)
    if union_backend == "pallas":
        from repro.kernels import ops
        # total/scale pass through untouched — the kernel takes them as
        # traced scalar operands, so they may be tracers (no recompile)
        union, summed = ops.union_segsum(
            flat_ids, flat_rows, heat, total, cap, scale=scale)
        return RowSparse(union, summed, stacked.num_rows)

    union, pos = _union_and_slots(flat_ids, stacked.num_rows, cap, union_backend)
    summed = jnp.zeros((cap,) + tuple(flat_rows.shape[1:]), jnp.float32)
    summed = summed.at[pos].add(flat_rows.astype(jnp.float32), mode="drop")
    return correct_rowsparse(RowSparse(union, summed, stacked.num_rows),
                             heat, total, scale)


#: psum-densify combine budget (bytes of one dense ``(V, row_elems)`` f32
#: buffer). Mirrors the ``fits_vmem`` philosophy of the pallas backend pick:
#: below the budget an all-reduce of the densified union rows is one fused
#: collective; above it the gathered union-of-unions keeps the RowSparse form
#: and never materialises the (V, D) table — every shard would otherwise pay
#: an O(V * D) densify + all-reduce per table per round (V=65k x D=16 is
#: already 4 MiB of dense traffic for a union of a few hundred rows).
_PSUM_COMBINE_MAX_BYTES = 1 << 21


def pick_combine(num_rows: int, row_elems: int, combine: str = "auto") -> str:
    """Resolve the cross-shard combine strategy for a sharded aggregation.

    ``"psum"``: densify each shard's partial union to ``(V, ...)`` and
    all-reduce — cheapest when the dense buffer is small. ``"union"``:
    all-gather the per-shard partial unions and run a second (replicated)
    union segment-sum — the RowSparse form survives, so huge feature spaces
    never pay a dense ``(V, D)`` collective. ``"auto"`` picks by the dense
    buffer's byte size, the same budget-style heuristic the union backend
    uses for its VMEM fit.
    """
    if combine != "auto":
        if combine not in ("psum", "union"):
            raise ValueError(f"unknown combine strategy {combine!r}: "
                             "expected 'auto', 'psum' or 'union'")
        return combine
    dense_bytes = int(num_rows) * max(int(row_elems), 1) * 4
    return "psum" if dense_bytes <= _PSUM_COMBINE_MAX_BYTES else "union"


def aggregate_rowsparse_partial(stacked: RowSparse,
                                union_capacity: Optional[int] = None,
                                union_backend: str = "auto") -> RowSparse:
    """Per-shard partial reduction: union segment-sum with NO heat, NO scale.

    One device shard's half of the sharded cohort aggregation: its clients'
    stacked ``(K_shard, R)`` deltas collapse onto the shard's union ids.
    The FedSubAvg correction and the ``1/K`` cohort mean are deliberately NOT
    applied — they are per-row multiplicative and must enter exactly once, at
    :func:`combine_rowsparse_partials`, after the cross-shard sum.
    """
    return aggregate_rowsparse(stacked, heat=None, total=1.0, scale=1.0,
                               union_capacity=union_capacity,
                               union_backend=union_backend)


def combine_rowsparse_partials(partial: RowSparse, axis_name: str,
                               num_shards: int, heat: Optional[Array],
                               total: float, scale: float = 1.0,
                               combine: str = "auto",
                               union_backend: str = "auto"):
    """Cross-device combine of per-shard partial unions (shard_map only).

    ``partial`` is this shard's :func:`aggregate_rowsparse_partial` output;
    the return value is the SAME on every shard (the replicated global
    aggregate), so the server apply that follows is identical everywhere:

    ``psum``   densify the shard partial and all-reduce; returns the dense
               corrected ``(V, ...)`` update (cold rows are exact zeros).
    ``union``  all-gather the shard unions into a ``(num_shards, cap)`` stack
               and run the ordinary :func:`aggregate_rowsparse` over it —
               every shard computes the same global union; returns RowSparse.

    Either way the heat correction (``total / n_m``) and ``scale`` are fused
    here, once, exactly as the single-device fused aggregation applies them.
    """
    row_elems = 1
    for d in partial.rows.shape[1:]:
        row_elems *= int(d)
    mode = pick_combine(partial.num_rows, row_elems, combine)
    if mode == "psum":
        dense = lax.psum(partial.to_dense().astype(jnp.float32), axis_name)
        if heat is not None:
            factors = heat_correction_factors(heat, total) * scale
        else:
            factors = jnp.full((partial.num_rows,), scale, jnp.float32)
        return dense * factors.reshape((-1,) + (1,) * (dense.ndim - 1))
    ids_g = lax.all_gather(partial.ids, axis_name)        # (ndev, cap)
    rows_g = lax.all_gather(partial.rows, axis_name)      # (ndev, cap, ...)
    stacked = RowSparse(ids_g, rows_g, partial.num_rows)
    cap = min(partial.num_rows, int(num_shards) * partial.capacity)
    return aggregate_rowsparse(stacked, heat, total, scale,
                               union_capacity=cap, union_backend=union_backend)


def aggregate_rowsparse_dense(stacked: RowSparse, heat: Array, total: float,
                              scale: float = 1.0, backend: str = "auto") -> Array:
    """Cohort aggregation to a *dense* corrected update ``(V, ...)``.

    ``backend="pallas"`` routes through the fused ``rowsparse_scatter`` TPU
    kernel (interpret-mode on CPU); ``"jnp"`` segment-sums into the union and
    scatters once; ``"auto"`` picks pallas on TPU, jnp elsewhere.
    """
    if backend == "auto":
        from repro.kernels.heat_scatter import on_tpu
        backend = "pallas" if on_tpu() else "jnp"
    if backend == "pallas":
        from repro.kernels import ops
        k, r = stacked.ids.shape
        flat_ids = stacked.ids.reshape(-1)
        rows = stacked.rows.reshape(k * r, -1)
        out = ops.rowsparse_scatter(flat_ids, rows, jnp.asarray(heat, jnp.float32),
                                    total, stacked.num_rows, scale=scale)
        return out.reshape((stacked.num_rows,) + tuple(stacked.rows.shape[2:]))
    if backend == "jnp":
        return aggregate_rowsparse(stacked, heat, total, scale).to_dense()
    raise ValueError(backend)


def sparse_cohort_aggregate(updates, heat_spec: HeatSpec,
                            heat_counts: Dict[str, Array], total: float,
                            num_clients_in_cohort: int, correct: bool = True,
                            spaces: Sequence[str] = DEFAULT_SPARSE_SPACES,
                            union_backend: str = "auto"):
    """Tree-level cohort aggregation mixing RowSparse and dense leaves.

    ``updates``: per-client stack — RowSparse leaves carry ``(K, R)`` ids,
    dense leaves are ``(K, ...)``. Returns the corrected cohort-mean update:
    RowSparse union leaves for the sparse plane; dense leaves are cohort
    means, with the broadcast heat correction applied to the ones that still
    carry a feature space (e.g. an LM head with vocab on a trailing axis) —
    exactly matching the dense server's ``correct_update_tree``.

    With ``correct=False`` this is sparse FedAvg — identical execution path,
    no heat scaling — so baselines stay comparable.
    """
    scale = 1.0 / float(num_clients_in_cohort)

    def agg(leaf, space):
        if is_rowsparse(leaf):
            heat = None
            if correct and space is not None and space[0] in heat_counts:
                heat = heat_counts[space[0]]
            return aggregate_rowsparse(leaf, heat, total, scale,
                                       union_backend=union_backend)
        mean = leaf.mean(axis=0)
        if correct:
            mean = correct_dense_leaf(mean, space, heat_counts, total)
        return mean

    def is_leaf(x):
        return x is None or is_rowsparse(x)

    return jax.tree.map(agg, updates, heat_spec.leaf_spaces, is_leaf=is_leaf)


def apply_rowsparse(table: Array, rs: RowSparse, scale: float = 1.0) -> Array:
    """``table + scale * rs`` without densifying the update."""
    safe = jnp.where(rs.ids >= 0, rs.ids, rs.num_rows)
    add = (rs.rows * scale).astype(table.dtype)
    return table.at[safe].add(add, mode="drop")
