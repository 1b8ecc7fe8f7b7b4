"""Pallas TPU kernel: single-token flash decode against a long KV cache.

One query vector per (batch, head) attends to S cached keys streamed through
VMEM in (BLK_S, hd) tiles with running (m, l, acc). Slot positions (absolute
token index per cache slot, -1 = empty) come in as a streamed int tile, so
ring-buffer (sliding-window) caches mask correctly.

The per-shard form of this kernel plus a psum-LSE merge is the seq-sharded
distributed decode path (see repro.models.decode / EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.heat_scatter import VMEM_BUDGET, _tpu_compiler_params

NEG_INF = -1e30


def _block_sizes(s, blk_s: int):
    """The blk_s the kernel actually runs with — the single source of the
    block clamp, shared by ``flash_decode``, its ``fits_vmem`` guard, and
    the static auditor so they cannot drift."""
    if s is not None:
        blk_s = min(blk_s, s)
    return blk_s


def vmem_footprint(hd: int, *, s: int | None = None, blk_s: int = 1024) -> int:
    """Analytic per-program VMEM bytes for ``flash_decode``.

    Double-buffered pipeline blocks (qpos, q, k, v, positions in; o out),
    the (m, l, acc) scratch, and the (1, blk_s) f32 score/prob temporaries.
    """
    blk_s = _block_sizes(s, blk_s)
    blocks = 2 * (1 + hd + 2 * blk_s * hd + blk_s + hd) * 4
    scratch = (2 + hd) * 4
    scores = 2 * blk_s * 4
    return blocks + scratch + scores


def fits_vmem(hd: int, *, s: int | None = None, blk_s: int = 1024,
              budget: int = VMEM_BUDGET) -> bool:
    """Whether ``flash_decode``'s working set fits the compiled budget."""
    return vmem_footprint(hd, s=s, blk_s=blk_s) <= budget


def _kernel(qpos_ref, q_ref, k_ref, v_ref, pos_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, window: int, blk_s: int, ns: int):
    isb = pl.program_id(1)

    @pl.when(isb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                 # (1, hd)
    k = k_ref[0].astype(jnp.float32)                 # (BLK_S, hd)
    v = v_ref[0].astype(jnp.float32)
    kpos = pos_ref[...]                              # (BLK_S,)
    qpos = qpos_ref[0]

    s = (q @ k.T) * scale                            # (1, BLK_S)
    valid = (kpos >= 0) & (kpos <= qpos)
    if window > 0:
        valid &= kpos > qpos - window
    s = jnp.where(valid[None, :], s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(isb == ns - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_decode(q, k_cache, v_cache, k_positions, q_position, *, window: int = 0,
                 blk_s: int = 1024, interpret: bool = True):
    """q: (B, H, hd); caches: (B, KV, S, hd); k_positions: (S,) -> (B, H, hd)."""
    b, h, hd = q.shape
    _, kvh, s, _ = k_cache.shape
    groups = h // kvh
    blk_s = _block_sizes(s, blk_s)
    assert s % blk_s == 0
    ns = s // blk_s
    scale = 1.0 / float(hd) ** 0.5

    qh = q.reshape(b * h, 1, hd)
    kh = k_cache.reshape(b * kvh, s, hd)
    vh = v_cache.reshape(b * kvh, s, hd)
    qpos = jnp.broadcast_to(jnp.asarray(q_position, jnp.int32), (b * h,))

    def kv_index(ibh, isb):
        bidx = ibh // h
        head = ibh % h
        return (bidx * kvh + head // groups, isb, 0)

    kwargs = {}
    if not interpret:
        # the (batch*head) axis writes disjoint outputs; the cache-block
        # axis carries (m, l, acc) scratch and must stay sequential
        kwargs["compiler_params"] = _tpu_compiler_params(
            semantics=("parallel", "arbitrary"))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, blk_s=blk_s, ns=ns),
        grid=(b * h, ns),
        in_specs=[
            pl.BlockSpec((1,), lambda ibh, isb: (ibh,)),
            pl.BlockSpec((1, 1, hd), lambda ibh, isb: (ibh, 0, 0)),
            pl.BlockSpec((1, blk_s, hd), kv_index),
            pl.BlockSpec((1, blk_s, hd), kv_index),
            pl.BlockSpec((blk_s,), lambda ibh, isb: (isb,)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda ibh, isb: (ibh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        interpret=interpret,
        **kwargs,
    )(qpos, qh, kh, vh, k_positions.astype(jnp.int32))
    return out.reshape(b, h, hd)
