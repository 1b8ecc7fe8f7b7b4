"""Pallas TPU kernel: causal GQA flash attention with optional sliding window.

Online-softmax attention tiled as (B*H, q_blocks, k_blocks): each grid step
streams one (BLK_K, hd) K/V tile through VMEM against a resident (BLK_Q, hd)
query tile, maintaining running (m, l, acc) in VMEM scratch. GQA is handled
in the BlockSpec index map (query head h reads KV head h // group_size) — no
materialised K/V repeat. The sliding window adds a lower bound to the same
position mask that enforces causality.

Block sizes default to (512, 512): at hd=128 the working set is
  q 512x128x4B + k/v 2x512x128x4B + acc 512x128x4B + scores 512x512x4B ~ 2.3 MB
well inside a v5e core's 16 MB VMEM with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.heat_scatter import VMEM_BUDGET, _tpu_compiler_params

NEG_INF = -1e30


def _block_sizes(sq, sk, blk_q: int, blk_k: int):
    """The (blk_q, blk_k) the kernel actually runs with — the single source
    of the block clamps, shared by ``flash_attention``, its ``fits_vmem``
    guard, and the static auditor so they cannot drift."""
    if sq is not None:
        blk_q = min(blk_q, sq)
    if sk is not None:
        blk_k = min(blk_k, sk)
    return blk_q, blk_k


def vmem_footprint(hd: int, *, sq: int | None = None, sk: int | None = None,
                   blk_q: int = 512, blk_k: int = 512) -> int:
    """Analytic per-program VMEM bytes for ``flash_attention``.

    Double-buffered pipeline blocks (q, k, v in; o out), the (m, l, acc)
    scratch, and the two (blk_q, blk_k) f32 score/prob temporaries.
    """
    blk_q, blk_k = _block_sizes(sq, sk, blk_q, blk_k)
    blocks = 2 * (blk_q * hd + 2 * blk_k * hd + blk_q * hd) * 4
    scratch = (2 * blk_q + blk_q * hd) * 4
    scores = 2 * blk_q * blk_k * 4
    return blocks + scratch + scores


def fits_vmem(hd: int, *, sq: int | None = None, sk: int | None = None,
              blk_q: int = 512, blk_k: int = 512,
              budget: int = VMEM_BUDGET) -> bool:
    """Whether ``flash_attention``'s working set fits the compiled budget."""
    return vmem_footprint(hd, sq=sq, sk=sk, blk_q=blk_q, blk_k=blk_k) <= budget


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, blk_q: int, blk_k: int, nk: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                       # (BLK_Q, hd)
    k = k_ref[0].astype(jnp.float32)                       # (BLK_K, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    qpos = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    kpos = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    blk_q: int = 512, blk_k: int = 512, interpret: bool = True):
    """q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd) -> (B, Sq, H, hd)."""
    b, sq, h, hd = q.shape
    _, sk, kvh, _ = k.shape
    groups = h // kvh
    blk_q, blk_k = _block_sizes(sq, sk, blk_q, blk_k)
    assert sq % blk_q == 0 and sk % blk_k == 0
    nq, nk = sq // blk_q, sk // blk_k
    scale = 1.0 / float(hd) ** 0.5

    qh = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(b * kvh, sk, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(b * kvh, sk, hd)

    def kv_index(ibh, iq, ik):
        # query stream ibh = b * h + head; KV stream = b * kvh + head // groups
        bidx = ibh // h
        head = ibh % h
        return (bidx * kvh + head // groups, ik, 0)

    kwargs = {}
    if not interpret:
        # (batch*head, q-block) axes write disjoint output tiles; the
        # k-block axis carries (m, l, acc) scratch and must stay sequential
        kwargs["compiler_params"] = _tpu_compiler_params(
            semantics=("parallel", "parallel", "arbitrary"))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          blk_q=blk_q, blk_k=blk_k, nk=nk),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, hd), lambda ibh, iq, ik: (ibh, iq, 0)),
            pl.BlockSpec((1, blk_k, hd), kv_index),
            pl.BlockSpec((1, blk_k, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, blk_q, hd), lambda ibh, iq, ik: (ibh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
        interpret=interpret,
        **kwargs,
    )(qh, kh, vh)
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
