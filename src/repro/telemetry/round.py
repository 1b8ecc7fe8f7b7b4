"""In-jit round telemetry: the observability plane of a federated round.

FedSubAvg's claim is about *which rows move and how they are weighted*
(Ding et al., NeurIPS 2022); losses and comm bytes alone cannot show it.
:class:`RoundTelemetry` is a pytree of counters computed INSIDE the jitted
round step — it rides the step's ``metrics`` dict, stacks along the scan
axis under the ``run_rounds`` engine, and crosses ``shard_map`` boundaries
via psums/all-gathers — so the numbers describe exactly the program that
ran, not a host-side re-derivation:

``dropped_ids`` / ``dropped_mass`` / ``dropped_per_client``
    The ``unique_ids_padded`` capacity contract drops the largest ids when a
    client's distinct-feature count exceeds its sub-id capacity — silently,
    before this plane existed. ``dropped_ids`` counts the distinct ids lost,
    ``dropped_mass`` the batch occurrences referencing them (how much data
    pointed at rows the submodel never carried).
``union_size`` / ``shard_union_sizes`` / ``agg_rows``
    Distinct ids across the cohort's submodels; the per-shard partial-union
    sizes on a :class:`~repro.federated.plan.CohortSharding` mesh; and the
    valid rows of the aggregated RowSparse update (post top-k).
``delta_norm_pre`` / ``delta_norm_post``
    L2 of the transported update stack before and after wire compression
    (top-k + int8) — the live distortion measurement.
``heat_hist``
    Per-bucket histogram (log2 heat buckets) of the round's touched union
    ids — the paper's hot/cold dichotomy as a per-round metric.
``density``
    Effective table density this round: ``union_size / V``.
``staleness_hist`` / ``buffer_occupancy``
    Buffered-async engine only (:mod:`repro.federated.async_engine`): the
    per-fire histogram of the aggregated arrivals' staleness (server
    versions elapsed between a delta's dispatch and its arrival) and the
    number of in-flight dispatched-but-unarrived deltas at the fire event.
    ``None`` on every synchronous path (a barrier round has neither).

Fields that do not apply to a given execution layout are ``None`` (an empty
pytree subtree, so scan/vmap/shard_map handle them transparently); scalar
drop counters are zero on layouts with no capacity contract (dense
transport), so the JSONL schema stays stable.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.sparse.rowsparse import (count_unique_ids, is_rowsparse,
                                    membership, unique_ids_padded)
from repro.telemetry.spans import host_pull

Array = jax.Array

#: log2 heat buckets: bucket b holds union ids with heat in [2^b, 2^{b+1})
#: (bucket 0 also holds h <= 1); 16 buckets cover cohorts of 65k clients.
HEAT_BUCKETS = 16

#: linear staleness buckets: bucket s counts buffered arrivals that were
#: dispatched s server versions ago (the last bucket absorbs the tail).
STALENESS_BUCKETS = 16


class RoundTelemetry(NamedTuple):
    """One round's in-jit counters (see module docstring for semantics)."""

    dropped_ids: Any            # i32 scalar: distinct ids dropped by capacity
    dropped_mass: Any           # f32 scalar: batch occurrences of dropped ids
    dropped_per_client: Any     # (K,) i32 | None (per-client layouts only)
    union_size: Any             # i32 scalar: distinct ids across submodels
    agg_rows: Any               # i32 scalar | None: aggregated RowSparse rows
    shard_union_sizes: Any      # (ndev,) i32 | None (sharded rounds only)
    delta_norm_pre: Any         # f32 scalar: L2 of the raw update stack
    delta_norm_post: Any        # f32 scalar: L2 after top-k / int8
    heat_hist: Any              # (HEAT_BUCKETS,) f32 over touched union ids
    density: Any                # f32 scalar: union_size / V
    # buffered-async fields (None on every synchronous path; defaulted so
    # existing constructors stay source-compatible)
    staleness_hist: Any = None  # (STALENESS_BUCKETS,) f32 | None: per fire
    buffer_occupancy: Any = None  # i32 scalar | None: in-flight deltas at fire


def valid_feature_ids(ids: Array, vocab: int) -> Array:
    """Ids outside ``[0, vocab)`` become -1 (the padding convention)."""
    ids = ids.astype(jnp.int32)
    return jnp.where((ids >= 0) & (ids < vocab), ids, -1)


def _drop_stats_one(feats: Array, sub_ids: Array, vocab: int):
    """(dropped distinct ids, dropped occurrence mass) for one id vector."""
    f = valid_feature_ids(feats.reshape(-1), vocab)
    distinct = count_unique_ids(f)
    kept = (sub_ids >= 0).sum(dtype=jnp.int32)
    dropped = jnp.maximum(distinct - kept, 0)
    covered = membership(f, sub_ids)
    mass = ((f >= 0) & ~covered).sum(dtype=jnp.float32)
    return dropped, mass


def drop_stats(feats: Array, sub_ids: Array, vocab: int):
    """Capacity-overflow accounting against the sub-id contract.

    ``feats``: raw feature ids — ``(K, M)`` per-client or flat ``(M,)``;
    ``sub_ids``: the -1-padded sub-id vectors actually consumed — ``(K, R)``
    or ``(R,)`` matching. Returns ``(dropped, mass)`` per client (or flat):
    distinct ids the capacity dropped, and the number of valid feature
    occurrences referencing a dropped id. Exact when ``sub_ids`` came from
    ``unique_ids_padded`` over the same ``feats`` (every execution path's
    contract); zero when the capacity fit.
    """
    if sub_ids.ndim == 2:
        return jax.vmap(lambda f, s: _drop_stats_one(f, s, vocab))(
            feats, sub_ids)
    return _drop_stats_one(feats, sub_ids, vocab)


def union_ids_vec(ids: Array, vocab: int) -> Array:
    """Sorted distinct valid ids of ``ids`` (static capacity, -1 padded)."""
    flat = ids.reshape(-1)
    cap = min(int(vocab), int(flat.shape[0])) if vocab else 0
    return unique_ids_padded(valid_feature_ids(flat, vocab), max(cap, 1))


def heat_histogram(heat: Array, ids: Array,
                   nbuckets: int = HEAT_BUCKETS) -> Array:
    """Histogram of ``heat`` values gathered at the valid ids of ``ids``.

    Bucket ``b`` counts ids whose heat lies in ``[2^b, 2^{b+1})`` (``b = 0``
    also holds ``h <= 1``); padding ids fall in no bucket. The live form of
    the paper's hot/cold feature split: a cohort touching mostly-cold rows
    piles into the low buckets.
    """
    h = jnp.take(jnp.asarray(heat, jnp.float32), jnp.maximum(ids, 0),
                 mode="clip")
    b = jnp.clip(jnp.floor(jnp.log2(jnp.maximum(h, 1.0))), 0,
                 nbuckets - 1).astype(jnp.int32)
    b = jnp.where(ids >= 0, b, nbuckets)          # pads -> dropped
    return jnp.zeros((nbuckets,), jnp.float32).at[b].add(1.0, mode="drop")


def staleness_histogram(staleness: Array,
                        nbuckets: int = STALENESS_BUCKETS) -> Array:
    """Histogram of the buffered arrivals' staleness values.

    ``staleness``: (M,) i32 server-versions-elapsed per buffered delta.
    Bucket ``s`` counts deltas with staleness exactly ``s``; the last bucket
    absorbs everything ``>= nbuckets - 1``. Negative entries (unused buffer
    slots, if a caller ever passes a partial buffer) fall in no bucket.
    """
    s = jnp.asarray(staleness, jnp.int32)
    b = jnp.where(s >= 0, jnp.minimum(s, nbuckets - 1), nbuckets)
    return jnp.zeros((nbuckets,), jnp.float32).at[b].add(1.0, mode="drop")


def tree_sq_sum(tree) -> Array:
    """Sum of squares over every leaf (RowSparse-aware), in float32.

    RowSparse padding rows are zero by construction on every encoder path,
    so no masking is needed.
    """
    total = jnp.zeros((), jnp.float32)
    for leaf in jax.tree.leaves(tree, is_leaf=is_rowsparse):
        rows = leaf.rows if is_rowsparse(leaf) else leaf
        total += jnp.sum(jnp.square(rows.astype(jnp.float32)))
    return total


def tree_sq_per_client(tree, k: int) -> Array:
    """Per-client sum of squares ``(K,)`` of a stacked update tree."""
    total = jnp.zeros((k,), jnp.float32)
    for leaf in jax.tree.leaves(tree, is_leaf=is_rowsparse):
        rows = leaf.rows if is_rowsparse(leaf) else leaf
        total += jnp.square(rows.astype(jnp.float32)).reshape(k, -1).sum(-1)
    return total


def tree_agg_rows(tree) -> Optional[Array]:
    """Valid rows summed over the RowSparse leaves of an aggregated update.

    ``None`` when no leaf is RowSparse (dense transport, or a psum-densified
    sharded combine) — there is no aggregation union to size.
    """
    counts = [leaf.valid_count()
              for leaf in jax.tree.leaves(tree, is_leaf=is_rowsparse)
              if is_rowsparse(leaf)]
    if not counts:
        return None
    total = counts[0]
    for c in counts[1:]:
        total = total + c
    return total.astype(jnp.int32)


def telemetry_to_host(tel: RoundTelemetry) -> dict:
    """One round's telemetry as plain Python (JSONL-ready; None fields kept).

    Works on a stacked telemetry too (each field gains a leading round axis
    under the scan engine) — use :func:`split_rounds` to slice it per round.
    Each field is one blocking pull (:func:`~repro.telemetry.spans.host_pull`).
    """
    out = {}
    for name, v in tel._asdict().items():
        if v is None:
            out[name] = None
            continue
        a = host_pull(v, f"telemetry.{name}")
        out[name] = a.item() if a.ndim == 0 else a.tolist()
    return out


def split_rounds(tel: RoundTelemetry, n: int) -> list:
    """Split a scan-stacked telemetry (leading axis ``n``) into host dicts,
    one blocking pull per field."""
    host = {name: (None if v is None else host_pull(v, f"telemetry.{name}"))
            for name, v in tel._asdict().items()}
    events = []
    for r in range(n):
        d = {}
        for name, a in host.items():
            if a is None:
                d[name] = None
            else:
                ar = a[r]
                d[name] = ar.item() if np.ndim(ar) == 0 else ar.tolist()
        events.append(d)
    return events
