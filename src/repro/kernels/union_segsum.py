"""Pallas TPU kernel: union + segment-sum + heat scaling (sparse server).

The FedSubAvg server step over a cohort's row-sparse deltas has three parts:
build the union of the clients' submodel ids, segment-sum the contributed rows
onto those union slots, and scale each slot by ``scale * N / n_m`` (Algorithm
1 line 9, fused with the cohort mean). The jnp backends in
``repro.sparse.aggregate`` express the segment-sum as an XLA scatter-add;
here it runs on the MXU, with work proportional to the cohort's ``T``
stacked rows and independent of the vocabulary ``V``.

The wrapper (XLA, in the caller's jit) sorts the ``T`` flat ids with their
row index, pads mapped above every id so they sort last, and gathers the
rows in that order. Each sorted element's union slot is
``cumsum(first occurrence) - 1``; pads and slots ``>= cap`` are marked
``-1`` and contribute nothing, which drops the largest ids first, exactly
like ``unique_ids_padded``. The union ids are the sorted ids at their first
occurrences (integer ops only, so ids ``>= 2^24`` stay exact).

Kernel, grid ``(nt,)`` over row tiles of ``t_blk``: slots never decrease
along the sorted order and grow by at most one per element, so tile ``i``'s
slots lie in ``[off[i], off[i] + t_blk)``, ``off[i]`` its first slot (an
SMEM operand). The tile builds one ``(t_blk, t_blk)`` one-hot of
``slot - off[i]`` and adds ``one_hot @ rows`` into the VMEM-resident output
window at ``off[i]``; a segment that straddles two tiles accumulates across
them. The output carries a ``t_blk`` padding tail so every window fits; the
wrapper slices it off and applies the heat factor once per union row, after
the sum.

The output ``(cap + t_blk, D)`` stays VMEM-resident for the whole kernel
(constant index map), so the kernel targets union capacities that fit VMEM.
``fits_vmem`` is the guard the ``"auto"`` backend selection consults;
beyond it the jnp backends take over. Backend selection mirrors
``heat_scatter``: compiled on TPU, interpret mode elsewhere (the CI parity
target).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.heat_scatter import (TILE_1D, VMEM_BUDGET, _check_tiled,
                                        _fit_blk, _tpu_compiler_params, on_tpu)

DEFAULT_T_BLK = TILE_1D

__all__ = ["union_segsum", "fits_vmem", "vmem_footprint", "VMEM_BUDGET"]


#: Grid dimension semantics for the compiled path. The one grid dim carries
#: the VMEM-resident output across row tiles (a straddling segment adds into
#: the window of the tile before), so it may not be declared 'parallel'
#: (Megacore would split it across cores and corrupt the sums). Do not reuse
#: ``heat_scatter``'s default ('parallel', ...) here.
_DIM_SEMANTICS = ("arbitrary",)

#: sort key of a pad: above every id, so pads sort after the union
_PAD_KEY = jnp.iinfo(jnp.int32).max


def _kernel(off_ref, slot_ref, rows_ref, out_ref, *, t_blk: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    off = off_ref[i]
    # slots of this tile relative to its window; -1 slots (pads, ids beyond
    # cap) land below 0 and match no row of the one-hot
    local = slot_ref[...] - off                            # (t_blk,)
    srows = jax.lax.broadcasted_iota(jnp.int32, (t_blk, t_blk), 0)
    onehot = (srows == local[None, :]).astype(jnp.float32)  # (slot, elem)
    rows = rows_ref[...].astype(jnp.float32)               # (t_blk, D)
    # HIGHEST keeps the accumulation in true f32 on TPU (the default MXU
    # bf16 passes would cost ~1e-3 relative error vs the jnp backends)
    out_ref[pl.ds(off, t_blk), :] += jnp.dot(
        onehot, rows, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


def _block_sizes(t, t_blk: int) -> int:
    """The row tile the kernel actually runs with — the single source of
    the block adjustment, shared by ``union_segsum`` and ``fits_vmem`` so
    the ``"auto"`` budget guard and the kernel never drift apart."""
    return _fit_blk(t, t_blk)


def vmem_footprint(cap: int, row_elems: int, *, t: int | None = None,
                   t_blk: int = DEFAULT_T_BLK) -> int:
    """Analytic per-program VMEM bytes for ``union_segsum``.

    Applies the same ``_block_sizes`` adjustment ``union_segsum`` itself
    makes when ``t`` is given, so the ``"auto"`` guard, the kernel, and the
    static auditor agree near the budget boundary.
    """
    d = max(int(row_elems), 1)
    t_blk = _block_sizes(t, t_blk)
    resident = (cap + t_blk) * d * 4                 # out rows
    # double-buffered pipeline input blocks (slots, rows), the one-hot and
    # the (t_blk, D) matmul window
    blocks = (2 * (t_blk + t_blk * d) + t_blk * t_blk + t_blk * d) * 4
    nt = -(-t // t_blk) if t else 1
    smem = nt * 4                                    # per-tile offsets
    return resident + blocks + smem


def fits_vmem(cap: int, row_elems: int, *, t: int | None = None,
              t_blk: int = DEFAULT_T_BLK, budget: int = VMEM_BUDGET) -> bool:
    """Whether the kernel's VMEM-resident footprint fits the compiled budget."""
    return vmem_footprint(cap, row_elems, t=t, t_blk=t_blk) <= budget


def _sorted_slots(ids, cap: int):
    """Sort ``ids`` (pads last): ``(order, slot, union)``.

    ``order`` permutes the elements into ascending id order; ``slot`` is
    each sorted element's union slot, ``-1`` for pads and for ids beyond
    the first ``cap`` distinct ones; ``union`` the ``(cap,)`` union ids,
    ascending, ``-1``-padded.
    """
    t = ids.shape[0]
    key = jnp.where(ids >= 0, ids, _PAD_KEY)
    skey, order = jax.lax.sort(
        (key, jax.lax.iota(jnp.int32, t)), num_keys=1)
    real = skey != _PAD_KEY
    first = real & jnp.concatenate(
        [jnp.ones((1,), bool), skey[1:] != skey[:-1]])
    pos = jnp.cumsum(first.astype(jnp.int32)) - 1
    keep = real & (pos < cap)
    slot = jnp.where(keep, pos, -1)
    union = jnp.full((cap,), -1, jnp.int32).at[
        jnp.where(first & keep, pos, cap)].set(skey, mode="drop")
    return order, slot, union


def union_segsum(ids, rows, heat, total: float, cap: int, *,
                 scale: float = 1.0, t_blk: int = DEFAULT_T_BLK,
                 interpret=None):
    """Union + segment-sum + FedSubAvg scaling over cohort deltas.

    ids: ``(K, R)`` or flat ``(T,)`` int32 feature ids (-1 pads, dropped);
    rows: matching ``(K, R, ...)`` / ``(T, ...)`` payload; heat: ``(V,)`` or
    None (factor ``scale`` for every union row). Returns ``(union_ids,
    union_rows)``: sorted-ascending union ids padded with -1 to ``cap`` and
    the summed rows scaled by ``scale * total / n_m`` (0 where heat is 0).
    Ids beyond ``cap`` distinct values are dropped largest-first, matching
    ``unique_ids_padded``.

    ``total`` and ``scale`` may be Python floats or traced scalars — they
    scale the kernel's output in XLA, so varying them never retraces or
    recompiles. ``interpret=None`` selects the compiled TPU path on TPU and
    the interpreter elsewhere.
    """
    if interpret is None:
        interpret = not on_tpu()
    ids = jnp.asarray(ids)
    rows = jnp.asarray(rows)
    trailing = tuple(rows.shape[ids.ndim:])      # payload dims beyond the ids'
    ids = ids.reshape(-1).astype(jnp.int32)
    rows = rows.reshape((ids.shape[0], -1))
    t, d = rows.shape
    out_shape = (cap,) + trailing
    if t == 0 or cap == 0:
        return (jnp.full((cap,), -1, jnp.int32),
                jnp.zeros(out_shape, jnp.float32))

    order, slot, union = _sorted_slots(ids, cap)
    rows = jnp.take(rows, order, axis=0)
    t_blk = _block_sizes(t, t_blk)
    pad = (-t) % t_blk
    if pad:
        slot = jnp.concatenate([slot, jnp.full((pad,), -1, slot.dtype)])
        rows = jnp.concatenate([rows, jnp.zeros((pad, d), rows.dtype)])
        t += pad
    nt = t // t_blk
    # each tile's window starts at its first slot; a tile of -1 slots only
    # (all pads or beyond cap) gets the padding tail at cap
    off = slot[::t_blk]
    off = jnp.where(off >= 0, off, cap)

    kwargs = {}
    if not interpret:
        _check_tiled(t_blk)
        kwargs["compiler_params"] = _tpu_compiler_params(
            semantics=_DIM_SEMANTICS)
    summed = pl.pallas_call(
        functools.partial(_kernel, t_blk=t_blk),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((t_blk,), lambda i: (i,)),
            pl.BlockSpec((t_blk, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((cap + t_blk, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((cap + t_blk, d), jnp.float32),
        interpret=interpret,
        name="union_segsum",
        **kwargs,
    )(off, slot, rows)[:cap]

    valid = union >= 0
    if heat is not None:
        h = jnp.take(jnp.asarray(heat, jnp.float32), jnp.maximum(union, 0))
        factor = jnp.where(valid & (h > 0),
                           scale * total / jnp.maximum(h, 1.0), 0.0)
    else:
        factor = jnp.where(valid, scale, 0.0)
    summed = summed * factor.astype(jnp.float32)[:, None]
    return union, summed.reshape(out_shape)
