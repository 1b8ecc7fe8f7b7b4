"""Seeded fixture: full heat-fused aggregate inside a shard_map body.

The PR 5 bug class: under cohort sharding each shard holds a PARTIAL
cohort, so calling the fused ``aggregate_rowsparse`` (which applies the
N/n_m heat correction) per shard applies the correction to per-shard
counts, and the cross-shard psum then sums already-corrected partials —
a silent double correction. The partial/combine split
(``aggregate_rowsparse_partial`` + ``combine_rowsparse_partials``) is
the only sound decomposition.

This file is an AST-only lint fixture: it is never imported or executed,
so the imports need not resolve.
"""
import jax
from jax import shard_map

from repro.sparse.aggregate import (aggregate_rowsparse,
                                    aggregate_rowsparse_partial,
                                    combine_rowsparse_partials)


def bad_shard_body(stacked, heat, total):
    agg = aggregate_rowsparse(stacked, heat, total)  # VIOLATION: full aggregate per shard
    return jax.lax.psum(agg.to_dense(), "data")


def good_shard_body(stacked, heat, total):
    partial = aggregate_rowsparse_partial(stacked)
    return combine_rowsparse_partials(partial, heat, total, axis="data")


def run(mesh, stacked, heat, total):
    bad = shard_map(bad_shard_body, mesh=mesh, in_specs=None, out_specs=None,
                    check_vma=False)
    good = shard_map(good_shard_body, mesh=mesh, in_specs=None,
                     out_specs=None, check_vma=False)
    return bad(stacked, heat, total), good(stacked, heat, total)
