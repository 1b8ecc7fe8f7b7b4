"""Pallas TPU kernel: fused union + segment-sum + heat scaling (sparse server).

The FedSubAvg server step over a cohort's row-sparse deltas has three parts:
build the union of the clients' submodel ids, segment-sum the contributed rows
onto those union slots, and scale each slot by ``scale * N / n_m`` (Algorithm
1 line 9, fused with the cohort mean). The jnp backends in
``repro.sparse.aggregate`` express this as a chain of sort/searchsorted (or
bitmap/cumsum) + scatter ops; this kernel does all three in one blocked pass
so the server hot loop issues a single fused program instead of a dispatch
chain.

Layout: grid ``(nv, nt)`` over vocab blocks x row blocks, both sequential on
TPU (row-major), with the vocab axis outer. Per vocab block the kernel

1. accumulates the block's segment-sums as a blocked one-hot MXU matmul
   ``(v_blk, t_blk) @ (t_blk, D)`` into a VMEM scratch accumulator across the
   row blocks (same scheme as ``heat_scatter``), together with per-row match
   counts;
2. on the block's last row tile, applies the fused heat factor, ranks the
   touched rows with an in-block prefix count (a 0/1 triangular matmul on
   the MXU — Mosaic has no cumsum), compacts them to the front of the block
   through a ``(v_blk, v_blk)`` permutation matmul, and
3. appends the compacted ``(ids, rows)`` window to the output at the running
   union offset (an SMEM carry across vocab blocks) with a dynamic store.

Because vocab blocks are visited in ascending order the emitted union ids are
sorted — the same invariant as ``unique_ids_padded`` — and overflow beyond
``cap`` falls into a ``v_blk`` padding tail that is sliced off, which drops
the largest ids exactly like the sort backend's capacity drop.

The union outputs ``(cap + v_blk,)`` ids and ``(cap + v_blk, D)`` rows stay
VMEM-resident for the whole kernel (constant output index map), so the kernel
targets union capacities that fit VMEM — the regime the sparse plane is for.
``fits_vmem`` is the runtime guard the ``"auto"`` backend selection consults;
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

DEFAULT_V_BLK = TILE_1D
DEFAULT_T_BLK = TILE_1D

__all__ = ["union_segsum", "fits_vmem", "vmem_footprint", "VMEM_BUDGET"]


#: Grid dimension semantics for the compiled path. BOTH dims are
#: order-dependent — the SMEM ``carry_ref`` union offset threads across vocab
#: blocks and the VMEM accumulator across row tiles — so neither may be
#: declared 'parallel' (Megacore would split it across cores and corrupt the
#: union). Do not reuse ``heat_scatter``'s default ('parallel', ...) here.
_DIM_SEMANTICS = ("arbitrary", "arbitrary")


def _kernel(params_ref, ids_ref, rows_ref, heat_ref, out_ids_ref, out_rows_ref,
            acc_ref, cnt_ref, carry_ref, *, use_heat: bool, v_blk: int,
            t_blk: int, nt: int, cap: int):
    iv = pl.program_id(0)
    it = pl.program_id(1)

    @pl.when((iv == 0) & (it == 0))
    def _init_out():
        carry_ref[0] = 0
        out_ids_ref[...] = jnp.full_like(out_ids_ref, -1)
        out_rows_ref[...] = jnp.zeros_like(out_rows_ref)

    @pl.when(it == 0)
    def _init_block():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    ids = ids_ref[...]                                     # (t_blk,)
    base = iv * v_blk
    vrows = base + jax.lax.broadcasted_iota(jnp.int32, (v_blk, t_blk), 0)
    # padding ids (-1) are < 0 and match no vocab row in any tile
    onehot = (vrows == ids[None, :]).astype(jnp.float32)   # (v_blk, t_blk)
    rows = rows_ref[...].astype(jnp.float32)               # (t_blk, D)
    # HIGHEST keeps the accumulation in true f32 on TPU (the default MXU
    # bf16 passes would cost ~1e-3 relative error vs the jnp backends)
    acc_ref[...] += jnp.dot(onehot, rows, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    cnt_ref[...] += onehot.sum(axis=1)

    @pl.when(it == nt - 1)
    def _emit():
        touched = cnt_ref[...] > 0                         # (v_blk,)
        total = params_ref[0]
        scale = params_ref[1]
        if use_heat:
            heat = heat_ref[...].astype(jnp.float32)
            factor = jnp.where(heat > 0,
                               scale * total / jnp.maximum(heat, 1.0), 0.0)
        else:
            factor = jnp.broadcast_to(scale, (v_blk,)).astype(jnp.float32)
        scaled = acc_ref[...] * factor[:, None]
        t_row = touched.astype(jnp.float32)[None, :]       # (1, v_blk)
        # in-block rank: rank[v] = #touched u <= v, minus one — a matmul with
        # the 0/1 upper triangle; exact in bf16 inputs with f32 accumulation.
        # DEFAULT is stated: a caller's "highest" default would ask Mosaic
        # for an fp32 contraction of bf16 operands, which it refuses
        upper = (jax.lax.broadcasted_iota(jnp.int32, (v_blk, v_blk), 0)
                 <= jax.lax.broadcasted_iota(jnp.int32, (v_blk, v_blk), 1))
        rank = jnp.dot(t_row.astype(jnp.bfloat16),
                       upper.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.DEFAULT
                       ).astype(jnp.int32) - 1             # (1, v_blk)
        n_new = jnp.sum(touched.astype(jnp.int32))
        # compact the touched rows to the window front: P[s, v] = 1 iff the
        # touched vocab row v has rank s — a permutation matmul on the MXU
        srange = jax.lax.broadcasted_iota(jnp.int32, (v_blk, v_blk), 0)
        sel = (srange == rank) & (t_row > 0)               # (slot, vocab)
        win_rows = jnp.dot(sel.astype(jnp.float32), scaled,
                           preferred_element_type=jnp.float32,
                           precision=jax.lax.Precision.HIGHEST)
        # ids stay integer end-to-end: each window slot selects exactly one
        # vocab row, so an int32 max-reduction extracts it exactly at any
        # vocab size (a f32 matmul would corrupt ids >= 2^24)
        vr = base + jax.lax.broadcasted_iota(jnp.int32, (v_blk, v_blk), 1)
        win_ids_m = jnp.max(jnp.where(sel, vr, -1), axis=1)
        slot = jax.lax.broadcasted_iota(jnp.int32, (v_blk, 1), 0)
        win_ids = jnp.where(slot < n_new, win_ids_m[:, None], -1)
        carry = carry_ref[0]
        # clamp: once the union overflows cap, windows land in the padding
        # tail [cap, cap + v_blk) and are sliced off by the wrapper
        offset = jnp.minimum(carry, cap)
        out_ids_ref[pl.ds(offset, v_blk), :] = win_ids
        out_rows_ref[pl.ds(offset, v_blk), :] = win_rows
        carry_ref[0] = carry + n_new


def _block_sizes(num_rows, t, v_blk: int, t_blk: int):
    """The (v_blk, t_blk) the kernel actually runs with — the single source
    of the block adjustments, shared by ``union_segsum`` and ``fits_vmem``
    so the ``"auto"`` budget guard and the kernel never drift apart."""
    return _fit_blk(num_rows, v_blk), _fit_blk(t, t_blk)


def vmem_footprint(cap: int, row_elems: int, *, num_rows: int | None = None,
                   t: int | None = None, v_blk: int = DEFAULT_V_BLK,
                   t_blk: int = DEFAULT_T_BLK) -> int:
    """Analytic per-program VMEM bytes for ``union_segsum``.

    Applies the same ``_block_sizes`` adjustments ``union_segsum`` itself
    makes when ``num_rows`` / ``t`` are given, so the ``"auto"`` guard, the
    kernel, and the static auditor agree near the budget boundary.
    """
    d = max(int(row_elems), 1)
    v_blk, t_blk = _block_sizes(num_rows, t, v_blk, t_blk)
    resident = (cap + v_blk) * (d + 1) * 4          # out rows + ids
    # double-buffered pipeline input blocks (ids, rows, heat), scratch
    # accumulators (acc, cnt), and the onehot/sel matmul temporaries
    blocks = (2 * (t_blk + t_blk * d + v_blk)
              + v_blk * d + v_blk
              + v_blk * t_blk + v_blk * v_blk) * 4
    smem = (2 + 1) * 4                               # params pair + carry
    return resident + blocks + smem


def fits_vmem(cap: int, row_elems: int, *, num_rows: int | None = None,
              t: int | None = None, v_blk: int = DEFAULT_V_BLK,
              t_blk: int = DEFAULT_T_BLK, budget: int = VMEM_BUDGET) -> bool:
    """Whether the kernel's VMEM-resident footprint fits the compiled budget."""
    return vmem_footprint(cap, row_elems, num_rows=num_rows, t=t,
                          v_blk=v_blk, t_blk=t_blk) <= budget


def union_segsum(ids, rows, heat, total: float, cap: int, num_rows: int, *,
                 scale: float = 1.0, v_blk: int = DEFAULT_V_BLK,
                 t_blk: int = DEFAULT_T_BLK, interpret=None):
    """Fused union + segment-sum + FedSubAvg scaling over cohort deltas.

    ids: ``(K, R)`` or flat ``(T,)`` int32 feature ids (-1 pads, dropped);
    rows: matching ``(K, R, ...)`` / ``(T, ...)`` payload; heat: ``(num_rows,)``
    or None (factor ``scale`` for every union row). Returns ``(union_ids,
    union_rows)``: sorted-ascending union ids padded with -1 to ``cap`` and
    the summed rows scaled by ``scale * total / n_m`` (0 where heat is 0).
    Ids beyond ``cap`` distinct values are dropped largest-first, matching
    ``unique_ids_padded``.

    ``total`` and ``scale`` may be Python floats or traced scalars — they
    reach the kernel through an SMEM operand, so varying them never
    retraces or recompiles. ``interpret=None`` selects the compiled TPU
    path on TPU and the interpreter elsewhere.
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

    use_heat = heat is not None
    heat = (jnp.asarray(heat, jnp.float32) if use_heat
            else jnp.zeros((num_rows,), jnp.float32))
    v_blk, t_blk = _block_sizes(num_rows, t, v_blk, t_blk)
    pad = (-t) % t_blk
    if pad:
        ids = jnp.concatenate([ids, jnp.full((pad,), -1, ids.dtype)])
        rows = jnp.concatenate([rows, jnp.zeros((pad, d), rows.dtype)])
        t += pad
    vpad = (-num_rows) % v_blk
    v_p = num_rows + vpad
    if vpad:
        # padded vocab rows are matched by no id, so they are never touched
        # and never emitted into the union
        heat = jnp.concatenate([heat, jnp.zeros((vpad,), heat.dtype)])
    nv, nt = v_p // v_blk, t // t_blk
    cap_p = cap + v_blk

    params = jnp.stack([jnp.asarray(total, jnp.float32),
                        jnp.asarray(scale, jnp.float32)])

    kwargs = {}
    if not interpret:
        _check_tiled(v_blk, t_blk)
        kwargs["compiler_params"] = _tpu_compiler_params(
            semantics=_DIM_SEMANTICS)
    out_ids, out_rows = pl.pallas_call(
        functools.partial(_kernel, use_heat=use_heat, v_blk=v_blk, t_blk=t_blk,
                          nt=nt, cap=cap),
        grid=(nv, nt),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((t_blk,), lambda iv, it: (it,)),
            pl.BlockSpec((t_blk, d), lambda iv, it: (it, 0)),
            pl.BlockSpec((v_blk,), lambda iv, it: (iv,)),
        ],
        out_specs=[
            pl.BlockSpec((cap_p, 1), lambda iv, it: (0, 0)),
            pl.BlockSpec((cap_p, d), lambda iv, it: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((cap_p, 1), jnp.int32),
            jax.ShapeDtypeStruct((cap_p, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((v_blk, d), jnp.float32),
            pltpu.VMEM((v_blk,), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
        interpret=interpret,
        **kwargs,
    )(params, ids, rows, heat)
    return out_ids[:cap, 0], out_rows[:cap].reshape(out_shape)
