"""Precisions one step below what a configuration states, for the control.

On the chip the control is the reference computed under the TPU's own
lower matmul precision (:data:`BELOW`: ``high``, bf16 x 3, for float32 at
``highest``). A CPU ignores matmul precision settings, so the tests on the
CPU stand in for it with :func:`mm_bf16`, which rounds both operands of
every matmul, forward and backward, to bfloat16: a step further down than
``high``, written out so that it reads alike everywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: the TPU matmul precision one step below each precision a configuration
#: may state
BELOW = {"highest": "high"}


def _bf16(x):
    """``x`` rounded to the nearest bfloat16 (ties to even), kept float32.

    Integer arithmetic on the bits: a float32 -> bfloat16 -> float32 round
    trip may be folded away by XLA, which allows excess precision."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    one = jnp.uint32(1)
    u = (u + jnp.uint32(0x7FFF) + ((u >> 16) & one)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _mm(a, b):
    return jnp.matmul(_bf16(a), _bf16(b), precision="highest")


@jax.custom_vjp
def mm_bf16(a, b):
    """``a @ b`` on bfloat16-rounded operands, for ``a`` of shape
    ``(..., k)`` and ``b`` of ``(k, n)``."""
    return _mm(a, b)


def _fwd(a, b):
    return _mm(a, b), (a, b)


def _bwd(res, g):
    a, b = res
    k, n = b.shape
    return _mm(g, b.T), _mm(a.reshape(-1, k).T, g.reshape(-1, n))


mm_bf16.defvjp(_fwd, _bwd)
