"""Seeded violation: pl.pallas_call without explicit dimension_semantics.

The union_segsum Megacore bug class: a kernel that carries state across a
grid dimension is corrupted when Mosaic partitions that dimension across
cores under the silent ``"parallel"`` default. Every ``pallas_call`` must
state its grid semantics via ``compiler_params``. The linter must flag the
first call below; the second states its semantics and must not fire.
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def double(x):
    return pl.pallas_call(          # VIOLATION: no compiler_params
        _kernel,
        grid=(x.shape[0] // 128,),
        in_specs=[pl.BlockSpec((128,), lambda i: (i,))],
        out_specs=pl.BlockSpec((128,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        interpret=True,
    )(x)


def double_declared(x):
    return pl.pallas_call(          # safe: semantics stated per grid dim
        _kernel,
        grid=(x.shape[0] // 128,),
        in_specs=[pl.BlockSpec((128,), lambda i: (i,))],
        out_specs=pl.BlockSpec((128,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(x)
