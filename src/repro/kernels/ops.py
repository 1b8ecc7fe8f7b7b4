"""Jitted public wrappers around the Pallas kernels with platform dispatch.

On TPU the kernels run compiled; on CPU (this container) they run in
``interpret=True`` mode, which executes the kernel body faithfully and is the
validation target for the test suite's oracle sweeps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.kernels.flash_decode import flash_decode as _flash_decode
from repro.kernels.heat_scatter import TILE_1D
from repro.kernels.heat_scatter import heat_scatter as _heat_scatter
from repro.kernels.heat_scatter import on_tpu as _on_tpu
from repro.kernels.heat_scatter import rowsparse_scatter as _rowsparse_scatter
from repro.kernels.union_segsum import union_segsum as _union_segsum


@functools.partial(jax.jit, static_argnames=("vocab", "v_blk", "t_blk"))
def heat_scatter(ids, grads, heat, total, vocab: int,
                 v_blk: int = TILE_1D, t_blk: int = TILE_1D):
    return _heat_scatter(ids, grads, heat, total, vocab, v_blk=v_blk, t_blk=t_blk,
                         interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("vocab", "v_blk", "t_blk"))
def rowsparse_scatter(ids, rows, heat, total, vocab: int,
                      scale=1.0, v_blk: int = TILE_1D, t_blk: int = TILE_1D):
    """Fused cohort row-sparse aggregation + heat correction (see kernel).

    As with ``union_segsum``, ``total``/``scale`` are traced scalar
    operands — only the shape parameters are static.
    """
    return _rowsparse_scatter(ids, rows, heat, total, vocab, scale=scale,
                              v_blk=v_blk, t_blk=t_blk, interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("cap", "t_blk"))
def union_segsum(ids, rows, heat, total, cap: int, scale=1.0,
                 t_blk: int = TILE_1D):
    """Union + segment-sum + heat scaling (see kernel module).

    ``total`` and ``scale`` are traced scalar operands — varying them (e.g.
    across rounds or in a sweep) hits the same compiled kernel; only the
    true shape parameters (``cap``, the row tile) are static.
    """
    return _union_segsum(ids, rows, heat, total, cap, scale=scale,
                         t_blk=t_blk, interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("causal", "window", "blk_q", "blk_k"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    blk_q: int = 512, blk_k: int = 512):
    return _flash_attention(q, k, v, causal=causal, window=window,
                            blk_q=blk_q, blk_k=blk_k, interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("window", "blk_s"))
def flash_decode(q, k_cache, v_cache, k_positions, q_position,
                 window: int = 0, blk_s: int = 1024):
    return _flash_decode(q, k_cache, v_cache, k_positions, q_position,
                         window=window, blk_s=blk_s, interpret=not _on_tpu())
