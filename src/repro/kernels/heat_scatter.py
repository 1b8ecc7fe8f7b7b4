"""Pallas TPU kernel: fused FedSubAvg row-sparse aggregation.

The paper's server-side hot path, generalised from token-level embedding
gradients to arbitrary row-sparse deltas: rows ``(T, D)`` tagged with target
ids ``(T,)`` must be (a) scatter-added into the ``(V, D)`` feature table and
(b) scaled by ``scale * N / n_v`` — the cohort-mean factor and the heat
correction (Algorithm 1 line 9) fused into one pass. Token gradients are the
special case where ids repeat per occurrence; cohort row-sparse deltas are
the case where ids repeat once per contributing client.

GPU implementations scatter with atomics; the TPU-native form is a blocked
one-hot matmul — for each (vocab_tile x row_tile) grid cell, build the
(V_BLK, T_BLK) one-hot match matrix in VREGs and accumulate
``one_hot @ rows_block`` on the MXU into the VMEM-resident output tile. The
fused scaling happens in the final row-block iteration, so the corrected
update never round-trips through HBM uncorrected.

Grid: (vocab_tiles, row_tiles); the row dim is the TPU-sequential minor grid
axis, so accumulation into ``out_ref`` across row tiles is well-defined (the
vocab axis is embarrassingly parallel and marked as such for Mosaic).

Backend selection happens at runtime: on TPU the kernel compiles for real
(``interpret=False``); everywhere else it falls back to interpret mode,
which executes the same kernel body and is the CI validation target.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common.hw import HW

DEFAULT_V_BLK = 1024
DEFAULT_T_BLK = 1024

#: XLA tiles a 1-D 32-bit array in chunks of 1024 elements, and Mosaic
#: refuses a 1-D block that does not match that tiling. Every compiled
#: kernel here streams its ids/heat as 1-D blocks, so those blocks are
#: multiples of this tile and the arrays are padded to them.
TILE_1D = 1024

#: VMEM budget (bytes) a kernel's per-program working set must fit for the
#: compiled path: the per-core capacity from ``repro.common.hw`` minus 1/4
#: headroom for Mosaic's own pipeline buffers and compiler scratch. Shared
#: by every kernel guard in this package and by the static auditor
#: (``repro.analysis.kernel_audit``).
VMEM_BUDGET = 3 * HW["vmem_bytes"] // 4


def _kernel(params_ref, ids_ref, rows_ref, heat_ref, out_ref, *,
            v_blk: int, t_blk: int, nt: int):
    iv = pl.program_id(0)
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]                                   # (T_BLK,)
    base = iv * v_blk
    vrows = base + jax.lax.broadcasted_iota(jnp.int32, (v_blk, t_blk), 0)
    # padding ids (-1) are < 0 and match no vocab row in any tile
    onehot = (vrows == ids[None, :]).astype(jnp.float32)  # (V_BLK, T_BLK)
    rows = rows_ref[...].astype(jnp.float32)             # (T_BLK, D)
    # HIGHEST keeps the accumulation in true f32 on TPU, as in union_segsum
    out_ref[...] += jnp.dot(onehot, rows, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)

    @pl.when(it == nt - 1)
    def _finalize():
        total = params_ref[0]
        scale = params_ref[1]
        heat = heat_ref[...].astype(jnp.float32)         # (V_BLK,)
        factor = jnp.where(heat > 0, scale * total / jnp.maximum(heat, 1.0), 0.0)
        out_ref[...] *= factor[:, None]


def _fit_blk(dim, blk: int) -> int:
    """``blk`` clamped to ``dim`` rounded up to ``TILE_1D``.

    A block never outgrows its tile-padded array, and is never shrunk below
    the tile: a small ``dim`` is padded up to the block instead, so the
    block keeps matching XLA's 1-D tiling at every size.
    """
    if dim is None or dim <= 0:
        return blk
    return min(blk, -(-dim // TILE_1D) * TILE_1D)


def _check_tiled(*blocks: int) -> None:
    """Refuse compiled-path blocks that Mosaic would reject at lowering."""
    bad = [b for b in blocks if b % TILE_1D]
    if bad:
        raise ValueError(
            f"compiled Pallas blocks {bad} are not multiples of the 1-D tile "
            f"{TILE_1D}; smaller blocks run only in interpret mode")


def _block_sizes(vocab, t, v_blk: int, t_blk: int):
    """The (v_blk, t_blk) the kernel actually runs with — the single source
    of the block adjustments, shared by ``rowsparse_scatter``, its
    ``fits_vmem`` guard, and the static auditor so they cannot drift."""
    return _fit_blk(vocab, v_blk), _fit_blk(t, t_blk)


def vmem_footprint(row_elems: int, *, vocab: int | None = None,
                   t: int | None = None, v_blk: int = DEFAULT_V_BLK,
                   t_blk: int = DEFAULT_T_BLK) -> int:
    """Analytic per-program VMEM bytes for ``rowsparse_scatter``.

    Double-buffered pipeline blocks (ids, rows, heat inputs and the output
    tile — its index map varies with the grid), the (v_blk, t_blk) one-hot
    matmul operand, and the SMEM params pair.
    """
    d = max(int(row_elems), 1)
    v_blk, t_blk = _block_sizes(vocab, t, v_blk, t_blk)
    blocks = 2 * (t_blk + t_blk * d + v_blk + v_blk * d) * 4
    onehot = v_blk * t_blk * 4
    smem = 2 * 4
    return blocks + onehot + smem


def fits_vmem(row_elems: int, *, vocab: int | None = None,
              t: int | None = None, v_blk: int = DEFAULT_V_BLK,
              t_blk: int = DEFAULT_T_BLK, budget: int = VMEM_BUDGET) -> bool:
    """Whether ``rowsparse_scatter``'s working set fits the compiled budget."""
    return vmem_footprint(row_elems, vocab=vocab, t=t, v_blk=v_blk,
                          t_blk=t_blk) <= budget


def on_tpu() -> bool:
    """Single source of the runtime backend check for kernel dispatch."""
    return jax.default_backend() == "tpu"


def _tpu_compiler_params(semantics=("parallel", "arbitrary")):
    """Mosaic params for the compiled path.

    ``semantics`` declares one entry per grid dim. ``heat_scatter``'s vocab
    axis is safe to split across cores ('parallel': its vocab blocks touch
    disjoint output rows); a kernel that carries state across a grid dim
    (e.g. ``union_segsum``'s resident output, added into across row tiles)
    must declare that dim 'arbitrary' or Megacore partitioning will corrupt
    it.
    """
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics))


def rowsparse_scatter(ids, rows, heat, total: float, vocab: int, *,
                      scale: float = 1.0, v_blk: int = DEFAULT_V_BLK,
                      t_blk: int = DEFAULT_T_BLK, interpret=None):
    """Fused scatter-add + FedSubAvg correction for row-sparse deltas.

    ids: (T,) int32 target rows (-1 pads, dropped); rows: (T, D); heat:
    (vocab,). Returns ``(vocab, D)`` float32 where row v holds
    ``scale * total / heat[v] * sum_{t: ids[t]=v} rows[t]`` (0 if heat 0).

    ``total`` and ``scale`` may be Python floats or traced scalars — they
    reach the kernel through an SMEM operand, so varying them never
    retraces or recompiles. ``interpret=None`` selects the real compiled
    TPU path when running on TPU and the interpreter elsewhere. Neither row
    count nor vocab need align to the block sizes — rows are padded with
    ``-1`` ids (free: they match nothing) and the vocab axis is padded with
    zero-heat rows (which no id targets and the correction zeroes), then
    sliced off.
    """
    if interpret is None:
        interpret = not on_tpu()
    t, d = rows.shape
    if t == 0:
        # an empty grid would never run the kernel body (or its output init)
        return jnp.zeros((vocab, d), jnp.float32)
    v_blk, t_blk = _block_sizes(vocab, t, v_blk, t_blk)
    pad = (-t) % t_blk
    if pad:
        ids = jnp.concatenate([ids, jnp.full((pad,), -1, ids.dtype)])
        rows = jnp.concatenate([rows, jnp.zeros((pad, d), rows.dtype)])
        t += pad
    vpad = (-vocab) % v_blk
    vocab_p = vocab + vpad
    if vpad:
        heat = jnp.concatenate([heat, jnp.zeros((vpad,), heat.dtype)])
    nv, nt = vocab_p // v_blk, t // t_blk
    params = jnp.stack([jnp.asarray(total, jnp.float32),
                        jnp.asarray(scale, jnp.float32)])

    kwargs = {}
    if not interpret:
        _check_tiled(v_blk, t_blk)
        # vocab grid axis: disjoint output rows per block, Megacore-safe to
        # split; row axis: sequential accumulation into out_ref
        kwargs["compiler_params"] = _tpu_compiler_params(
            semantics=("parallel", "arbitrary"))
    return pl.pallas_call(
        functools.partial(_kernel, v_blk=v_blk, t_blk=t_blk, nt=nt),
        grid=(nv, nt),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((t_blk,), lambda iv, it: (it,)),
            pl.BlockSpec((t_blk, d), lambda iv, it: (it, 0)),
            pl.BlockSpec((v_blk,), lambda iv, it: (iv,)),
        ],
        out_specs=pl.BlockSpec((v_blk, d), lambda iv, it: (iv, 0)),
        out_shape=jax.ShapeDtypeStruct((vocab_p, d), jnp.float32),
        interpret=interpret,
        **kwargs,
    )(params, ids, rows, heat)[:vocab]


def heat_scatter(ids, grads, heat, total: float, vocab: int, *,
                 v_blk: int = DEFAULT_V_BLK, t_blk: int = DEFAULT_T_BLK,
                 interpret=None):
    """Token-gradient aggregation (the original paper hot path).

    ids: (T,) int32 token ids (-1 pads); grads: (T, D); heat: (vocab,).
    Returns the corrected dense update (vocab, D) float32. Token grads are
    row-sparse deltas with per-occurrence duplicate ids, so this is
    ``rowsparse_scatter`` with ``scale=1``.
    """
    return rowsparse_scatter(ids, grads, heat, total, vocab, scale=1.0,
                             v_blk=v_blk, t_blk=t_blk, interpret=interpret)
