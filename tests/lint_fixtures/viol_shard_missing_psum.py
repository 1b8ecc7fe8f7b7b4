"""Seeded fixture: per-shard jnp reduction with no psum/pmean in reach.

Inside a shard_map body, ``jnp.mean(losses)`` collapses THIS shard's
slice only; unless the result feeds a ``jax.lax.psum``/``pmean`` over
the mesh axis (or the per-shard intent is suppressed with a reason),
every device reports a different "mean" and downstream metrics silently
diverge from the replicated run.

This file is an AST-only lint fixture: it is never imported or executed,
so the imports need not resolve.
"""
import jax
import jax.numpy as jnp
from jax import shard_map


def bad_loss_body(losses):
    return jnp.mean(losses)  # VIOLATION: per-shard mean, never combined


def good_loss_body(losses):
    shard_sum = jnp.sum(losses)
    total = jax.lax.psum(shard_sum, "data")
    return total / losses.shape[0]


def run(mesh, losses):
    bad = shard_map(bad_loss_body, mesh=mesh, in_specs=None, out_specs=None,
                    check_vma=False)
    good = shard_map(good_loss_body, mesh=mesh, in_specs=None,
                     out_specs=None, check_vma=False)
    return bad(losses), good(losses)
