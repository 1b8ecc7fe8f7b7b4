"""Observability plane: in-jit round telemetry, spans and counters, trace sink.

Deliberately a sibling package of ``repro.federated`` (whose public API
surface is pinned): the execution plane imports nothing from here except
``repro.telemetry.round``'s pure-jnp helpers and ``repro.telemetry.spans``,
and everything host-side (sink, JSONL readers) lives behind this namespace.

Three channels, one per reader:

- **The profiler** (``repro.telemetry.spans``). The trainer's host spans
  (``fedsub.call``, ``fedsub.sample``, ``fedsub.sub_ids``,
  ``fedsub.dispatch``, ``fedsub.sync``, ``fedsub.account``,
  ``fedsub.chunk``) and the round step's device scopes (``fedsub.local``,
  ``fedsub.aggregate``, ``fedsub.apply``, ``fedsub.loss``,
  ``fedsub.telemetry``) land in the ``.xplane.pb`` of any
  ``jax.profiler`` trace, e.g. ``trainer.run(..., profile_dir=d)``. Read
  it in TensorBoard's profile plugin, or with
  ``jax.profiler.ProfileData.from_file``: host spans are events of the
  Python thread's line (span args are event stats), and each device
  operation carries its scope path in its HLO metadata.
- **In-jit counters** (:class:`RoundTelemetry`), computed inside the round
  step, one ``round`` event per round in the sink and ``telemetry_log``.
- **The sink** (:class:`TraceSink`): ``round`` and ``record`` events as
  JSONL. Each ``record`` carries ``host_syncs`` and ``compiles`` since the
  previous record (``spans.counters``), so an operator without a profiler
  sees the same counts.
"""
from repro.telemetry.round import (HEAT_BUCKETS, RoundTelemetry, drop_stats,
                                   heat_histogram, split_rounds,
                                   telemetry_to_host, tree_agg_rows,
                                   tree_sq_per_client, tree_sq_sum,
                                   union_ids_vec, valid_feature_ids)
from repro.telemetry.sink import TraceSink, read_events
from repro.telemetry.spans import counters, host_pull, span

__all__ = [
    "HEAT_BUCKETS",
    "RoundTelemetry",
    "TraceSink",
    "counters",
    "drop_stats",
    "heat_histogram",
    "host_pull",
    "read_events",
    "span",
    "split_rounds",
    "telemetry_to_host",
    "tree_agg_rows",
    "tree_sq_per_client",
    "tree_sq_sum",
    "union_ids_vec",
    "valid_feature_ids",
]
