"""Composable execution plans for one federated round.

The paper's FedSubAvg protocol (Ding et al., NeurIPS 2022) is ONE server
update behind many execution layouts. A :class:`RoundPlan` names a layout as
three orthogonal strategy choices instead of a mode string:

``LocalStep`` — how the cohort produces update deltas:
    :class:`FedSgdLocal`              I = 1 on the pooled cohort batch
                                      (optionally microbatched); the cohort
                                      mean is one gradient.
    :class:`ReplicatedLocal`          true I > 1 local SGD on per-client
                                      DENSE model replicas (vmap).
    :class:`SubmodelReplicatedLocal`  I > 1 local SGD on per-client
                                      gathered SUBMODEL replicas — the
                                      paper's download-a-submodel protocol;
                                      deltas are born RowSparse.

``Transport`` — what ships between clients and server (and what one round
costs in bytes — the transport owns comm accounting):
    :class:`DenseTransport`           full dense update trees.
    :class:`RowSparseTransport`       row-sparse ``(ids, rows)`` updates with
                                      optional top-k row selection, int8
                                      stochastic-rounding quantisation, and a
                                      union-backend choice for the server
                                      segment-sum.

``ServerUpdate`` — the heat correction plus the algorithm that applies the
aggregated update: plain (fedavg / fedprox / fedsubavg) or the stateful
server optimizers (scaffold / fedadam), reusing
``repro.core.algorithms.make_server_algorithm`` slots.

``CohortSharding`` — the optional fourth strategy, orthogonal to the other
three: split the cohort axis over a device mesh. ``build_round_step`` wraps
the local phase in ``shard_map``; each shard runs its K/dev clients and a
per-shard partial aggregation, a cross-device combine produces the global
update, and the (replicated) server apply is identical on every shard —
exact vs the single-device step to 1e-5 under the same RNG stream.

:func:`build_round_step` compiles a plan into the single jitted round step
both entry points run: ``make_round_step`` (mode strings are thin aliases via
:func:`resolve_plan`) and ``FederatedTrainer`` (``FedConfig`` flags resolve
via :func:`plan_from_config`, or pass ``plan=`` explicitly). One dispatch
system, two entry points — and compositions no mode string ever expressed
(top-k/int8 under the simulation's sparse path, submodel-replica local
training against a dense server transport) fall out for free.

Shared concerns that were once copy-pasted per mode branch live here (or in
the module that owns them) exactly once: heat-batch splitting
(:func:`split_heat_batch`), CE-label pinning (``repro.sparse.encode.
pin_labels``), sub-id derivation, loss/density metrics, boxed/unboxed
plumbing, and compression (``repro.sparse.compress.compress_delta_tree``).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.pytree import tree_path_keys, tree_scale
from repro.configs.base import SERVER_ALGORITHMS, FedConfig
from repro.core.aggregate import HeatSpec, correct_dense_leaf, correct_update_tree
from repro.core.algorithms import ServerState, make_server_algorithm
from repro.federated.client import (cohort_deltas, cohort_submodel_deltas,
                                    make_local_trainer,
                                    make_submodel_local_trainer)
from repro.analysis import sanitize
from repro.sharding.logical import axes_tree, boxed_like, unbox
from repro.sparse.aggregate import (aggregate_rowsparse_partial,
                                    apply_rowsparse,
                                    combine_rowsparse_partials,
                                    correct_rowsparse, pick_combine,
                                    sparse_cohort_aggregate)
from repro.sparse.comm import CommMeta, CommStats, model_comm_meta, round_comm_stats
from repro.sparse.compress import compress_delta_tree
from repro.sparse.encode import (DEFAULT_SPARSE_SPACES, batch_union_ids,
                                 decode_delta_tree, encode_delta_tree,
                                 flat_feature_ids, pin_labels, sparse_eligible,
                                 stacked_feature_ids, submodel_value_and_grad,
                                 tree_leaf_at)
from repro.sparse.rowsparse import (RowSparse, count_unique_ids, is_rowsparse,
                                    unique_ids_padded)
from repro.telemetry.round import (HEAT_BUCKETS, RoundTelemetry, drop_stats,
                                   heat_histogram, tree_agg_rows, tree_sq_sum,
                                   union_ids_vec)
from repro.telemetry.spans import AGGREGATE, APPLY, LOCAL, LOSS, TELEMETRY

Array = jax.Array

#: round-plan server algorithms ("central" is not a federated round)
PLAN_ALGORITHMS = tuple(a for a in SERVER_ALGORITHMS if a != "central")


# ---------------------------------------------------------------------------
# heat-spec derivation (moved here from simulation.py; re-exported there)
# ---------------------------------------------------------------------------


def heat_spec_from_axes(boxed_params,
                        spaces: Dict[str, str] = None) -> HeatSpec:
    """Derive the HeatSpec from Param logical axes.

    spaces maps logical axis name -> heat space name; default:
    "vocab" axis -> "vocab" space, "experts" axis -> "expert" space.
    """
    spaces = spaces or {"vocab": "vocab", "experts": "expert"}
    axes = axes_tree(boxed_params)

    def is_axes(x):
        return x is None or (isinstance(x, tuple)
                             and all(e is None or isinstance(e, str) for e in x))

    def leaf_space(ax):
        if ax is None:
            return None
        for i, name in enumerate(ax):
            if name in spaces:
                return (spaces[name], i)
        return None

    return HeatSpec(jax.tree.map(leaf_space, axes, is_leaf=is_axes))


def _is_space(x) -> bool:
    return x is None or (isinstance(x, tuple) and len(x) == 2
                         and isinstance(x[0], str) and isinstance(x[1], int))


def sparse_table_paths(heat_spec: HeatSpec, spaces=None):
    """Paths of the leaves that ride the sparse plane (axis-0 feature tables)."""
    if spaces is None:
        spaces = DEFAULT_SPARSE_SPACES
    flat, _ = jax.tree_util.tree_flatten_with_path(heat_spec.leaf_spaces,
                                                   is_leaf=_is_space)
    return [(tree_path_keys(path), space) for path, space in flat
            if sparse_eligible(space, spaces)]


def round_capacity(vocab: int, ids_size: int, align: int = 8) -> int:
    """Union-id capacity for one sparse round step.

    ``min(vocab, ids_size)`` rounded up to a multiple of ``align`` for tiling,
    then clamped back to ``vocab`` — the rounding must never allocate union
    slots past the feature table (e.g. V=50257 would otherwise get 50264
    slots, gathering rows that don't exist in the table's id space).
    """
    cap = min(int(vocab), int(ids_size))
    cap += (-cap) % align
    return min(cap, int(vocab))


def split_heat_batch(batch: Dict) -> Tuple[Dict, Dict]:
    """Split a round batch into its static heat vectors and the cohort data.

    ``heat_*`` entries (``heat_vocab``, ``heat_expert``, ...) ride along the
    batch on the simulation entry point; the trainer bakes heat statically
    and its batches simply carry no such keys.
    """
    heat = {k: v for k, v in batch.items() if k.startswith("heat_")}
    data = {k: v for k, v in batch.items() if not k.startswith("heat_")}
    return heat, data


# ---------------------------------------------------------------------------
# strategy objects
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class FedSgdLocal:
    """I = 1: the cohort-mean delta is one gradient of the pooled batch.

    ``microbatches > 1`` splits the batch for gradient accumulation (dense
    transport only — the sparse plane computes one fused cohort gradient).
    Data layout: flat ``(B, ...)`` leaves. FedProx is a no-op here: a single
    step taken AT the prox anchor has identically zero prox gradient.
    """

    microbatches: int = 1
    stacked = False


@dataclass(frozen=True)
class ReplicatedLocal:
    """True I > 1 local SGD on per-client DENSE replicas under vmap.

    Data layout: ``(K, I, B, ...)`` leaves. ``prox_mu`` overrides the FedProx
    proximal coefficient (``None`` derives it from the config: active iff
    ``cfg.algorithm == "fedprox"``). Memory: K full model replicas.
    """

    prox_mu: Optional[float] = None
    stacked = True


@dataclass(frozen=True)
class SubmodelReplicatedLocal:
    """I > 1 local SGD on per-client gathered SUBMODEL replicas.

    The paper's protocol made literal: each client's replica is its gathered
    ``(capacity, D)`` feature rows plus the dense leaves; deltas are born
    RowSparse on the client's sub-ids. Memory: K * capacity * D feature-table
    HBM instead of the K * V * D dense-replica wall. Data layout and
    ``prox_mu`` as :class:`ReplicatedLocal`.
    """

    prox_mu: Optional[float] = None
    stacked = True


LocalStep = Union[FedSgdLocal, ReplicatedLocal, SubmodelReplicatedLocal]


@dataclass(frozen=True)
class DenseTransport:
    """Full dense update trees ship both ways (the classic FL layout)."""

    sparse = False

    def round_comm(self, rnd: int, meta: CommMeta, valid_counts: np.ndarray,
                   num_features: int, capacity: Optional[int] = None,
                   submodel_downlink: bool = False,
                   local_iters: int = 1) -> Optional[CommStats]:
        """Dense rounds have no sparse-plane pricing to log."""
        return None


@dataclass(frozen=True)
class RowSparseTransport:
    """Row-sparse ``(ids, rows)`` updates — the paper's submodel wire format.

    ``topk``: keep only the k largest-L2 delta rows per client (0 = off).
    ``int8``: unbiased stochastic-rounding int8 row payloads.
    ``union_backend``: server segment-sum backend (``"auto"``/``"bitmap"``/
    ``"sort"``/``"pallas"`` — see ``repro.sparse.aggregate``).
    """

    topk: int = 0
    int8: bool = False
    union_backend: str = "auto"
    sparse = True

    def __post_init__(self):
        if self.topk < 0:
            raise ValueError(f"topk must be >= 0 (0 disables), got {self.topk}")

    def round_comm(self, rnd: int, meta: CommMeta, valid_counts: np.ndarray,
                   num_features: int, capacity: Optional[int] = None,
                   submodel_downlink: bool = False,
                   local_iters: int = 1) -> CommStats:
        """Price one round in exact bytes from per-client sub-id counts.

        Uplink: top-k ships exactly ``min(topk, valid)`` delta rows per
        client (int8 pricing applied when enabled). Downlink prices what the
        execution actually ships: the gathered ``capacity``-row submodel
        buffer (clamped to the table — pow2 padding past V never hits the
        wire) when ``submodel_downlink``, else the full feature table. The
        dense baseline carries the ``local_iters`` factor (the I=1 dense
        protocol re-ships the model every local step).
        """
        valid_counts = np.asarray(valid_counts)
        k = len(valid_counts)
        up = (np.minimum(valid_counts, self.topk) if self.topk
              else valid_counts)
        if submodel_downlink:
            if capacity is None:
                raise ValueError("submodel downlink pricing needs the "
                                 "gathered replica capacity")
            down = np.full(k, min(int(capacity), int(num_features)))
        else:
            down = np.full(k, int(num_features))
        return round_comm_stats(
            rnd, meta.dense_bytes, meta.sparse_static_bytes,
            meta.row_payload_bytes, valid_counts, num_features,
            int8=self.int8, row_elems=meta.row_elems,
            uplink_rows_per_client=up, downlink_rows_per_client=down,
            local_iters=local_iters)


Transport = Union[DenseTransport, RowSparseTransport]


@dataclass(frozen=True)
class ServerUpdate:
    """Heat correction + the server algorithm that applies the update.

    ``algorithm`` picks the apply slot: plain (``fedavg``/``fedprox``/
    ``fedsubavg``) applies ``X += eta * update`` (sparse leaves via
    scatter-add, never densified); the stateful optimizers (``scaffold``/
    ``fedadam``) consume a dense mean delta — densified once at the server
    boundary on the sparse plane. The FedSubAvg correction ``N / n_m`` is
    applied iff ``algorithm == "fedsubavg"`` — fused into the sparse
    aggregation, broadcast onto dense leaves.
    """

    algorithm: str = "fedsubavg"

    def __post_init__(self):
        if self.algorithm not in PLAN_ALGORITHMS:
            raise ValueError(
                f"unknown server algorithm {self.algorithm!r}: expected one "
                f"of {PLAN_ALGORITHMS}")

    @property
    def correct(self) -> bool:
        return self.algorithm == "fedsubavg"

    @property
    def stateless(self) -> bool:
        return self.algorithm in ("fedavg", "fedprox", "fedsubavg")


@dataclass(frozen=True)
class CohortSharding:
    """Shard one round's cohort axis over a device mesh (FedAvg-style rounds
    are embarrassingly parallel over clients until the union segment-sum).

    ``mesh``/``axis`` name the data-parallel mesh axis the cohort is split
    over; ``build_round_step`` wraps the local phase in ``shard_map`` so each
    device shard runs its K/dev clients' local steps and a *per-shard*
    partial aggregation, then a cross-device combine produces the global
    update before the (replicated, identical-on-all-shards) server apply.

    ``combine`` picks the sparse-plane cross-shard reduction: ``"psum"``
    (densify + all-reduce, small tables), ``"union"`` (all-gather the shard
    unions, second RowSparse segment-sum, large tables) or ``"auto"``
    (byte-budget heuristic — see ``repro.sparse.aggregate.pick_combine``).
    """

    mesh: jax.sharding.Mesh
    axis: str = "data"
    combine: str = "auto"

    def __post_init__(self):
        if self.axis not in self.mesh.axis_names:
            raise ValueError(
                f"CohortSharding axis {self.axis!r} not in mesh axes "
                f"{self.mesh.axis_names}")
        if self.combine not in ("auto", "psum", "union"):
            raise ValueError(
                f"unknown combine strategy {self.combine!r}: expected "
                "'auto', 'psum' or 'union'")

    @property
    def num_shards(self) -> int:
        return int(self.mesh.shape[self.axis])


@dataclass(frozen=True)
class RoundPlan:
    """One federated round as a composition of three orthogonal strategies.

    ``sharding`` is the optional fourth, orthogonal to all of them: a
    :class:`CohortSharding` runs the SAME plan multi-device by splitting the
    cohort over a mesh axis — every local/transport/server composition gains
    multi-device execution without changing its math (parity to 1e-5 against
    the single-device step, same RNG stream).
    """

    local: LocalStep
    transport: Transport
    server: ServerUpdate
    feature_keys: Tuple[str, ...] = ("tokens",)
    sharding: Optional[CohortSharding] = None
    #: emit in-jit RowSparse contract checks (checkify) at the plane
    #: boundaries. Off by default: the checks are simply not traced, so the
    #: compiled program is byte-identical to a plan without the flag. When
    #: on, the step must run through ``repro.analysis.sanitize.checked_jit``
    #: (``make_round_step`` / ``FederatedTrainer`` handle this) — a bare
    #: ``jax.jit`` over an emitting step raises at trace time.
    debug_checks: bool = False

    def describe(self) -> str:
        base = (f"{type(self.local).__name__} -> "
                f"{type(self.transport).__name__} -> "
                f"ServerUpdate({self.server.algorithm})")
        if self.sharding is not None:
            base += (f" [sharded x{self.sharding.num_shards} over "
                     f"'{self.sharding.axis}']")
        if self.debug_checks:
            base += " [debug_checks]"
        return base


# ---------------------------------------------------------------------------
# mode-string / config resolution (the two legacy dispatch systems, unified)
# ---------------------------------------------------------------------------


def resolve_plan(mode_or_plan, cfg: FedConfig, correct: bool = True,
                 feature_key: str = "tokens") -> RoundPlan:
    """Resolve a legacy ``make_round_step`` mode string into its RoundPlan.

    The four strings are thin aliases — each names the composition that
    reproduces the historical branch byte-for-byte. A RoundPlan passes
    through unchanged (so callers can hand either to ``make_round_step``),
    but then the plan is the whole truth: the string-mode knobs must not
    silently contradict it.
    """
    if isinstance(mode_or_plan, RoundPlan):
        plan = mode_or_plan
        if not correct and plan.server.correct:
            raise ValueError(
                "correct=False conflicts with an explicit RoundPlan whose "
                "ServerUpdate applies the heat correction — encode the "
                "choice in the plan (ServerUpdate('fedavg'), etc.)")
        if feature_key != "tokens" and feature_key not in plan.feature_keys:
            raise ValueError(
                f"feature_key={feature_key!r} conflicts with the explicit "
                f"RoundPlan's feature_keys={plan.feature_keys} — set it on "
                "the plan")
        return plan
    server = ServerUpdate("fedsubavg" if correct else "fedavg")
    fk = (feature_key,)
    if mode_or_plan == "fedsgd":
        return RoundPlan(FedSgdLocal(max(cfg.microbatches, 1)),
                         DenseTransport(), server, fk)
    if mode_or_plan == "sparse":
        if cfg.microbatches > 1:
            raise ValueError(
                "mode='sparse' composes with microbatches=1: the sparse "
                "plane computes one fused cohort gradient per round")
        return RoundPlan(FedSgdLocal(), RowSparseTransport(), server, fk)
    if mode_or_plan == "replicated":
        return RoundPlan(ReplicatedLocal(), DenseTransport(), server, fk)
    if mode_or_plan == "sparse_replicated":
        return RoundPlan(SubmodelReplicatedLocal(), RowSparseTransport(),
                         server, fk)
    raise ValueError(mode_or_plan)


def plan_from_config(cfg: FedConfig, feature_keys: Tuple[str, ...] = ("tokens",),
                     gatherable: bool = True) -> RoundPlan:
    """Resolve ``FedConfig`` flags into the RoundPlan the trainer executes.

    ``gatherable``: whether the model's axis-0 feature tables span the
    dataset's id space (the precondition for submodel replicas) — decides
    the ``sparse_local="auto"`` branch.
    """
    if cfg.algorithm == "central":
        raise ValueError("central training is not a federated round plan")
    server = ServerUpdate(cfg.algorithm)
    if not cfg.sparse:
        return RoundPlan(ReplicatedLocal(), DenseTransport(), server,
                         tuple(feature_keys))
    mode = cfg.sparse_local
    if mode == "auto":
        mode = "sparse_replicated" if gatherable else "replicated"
    local = (SubmodelReplicatedLocal() if mode == "sparse_replicated"
             else ReplicatedLocal())
    transport = RowSparseTransport(topk=cfg.sparse_topk, int8=cfg.sparse_int8)
    return RoundPlan(local, transport, server, tuple(feature_keys))


def plan_comm_meta(boxed_params) -> CommMeta:
    """Static comm geometry of a model for ``Transport.round_comm``."""
    spec = heat_spec_from_axes(boxed_params)
    paths = {p for p, _ in sparse_table_paths(spec)}
    return model_comm_meta(unbox(boxed_params), paths)


def round_collective_budget(plan: "RoundPlan", boxed_params_template,
                            cfg: FedConfig, batch: Dict, *,
                            sub_ids=None) -> Dict:
    """Analytic per-collective budget of one cohort-sharded round step.

    Mirrors, term by term, the collectives ``build_round_step``'s shard
    bodies emit — so ``analysis.hlo_audit.collective_contract`` can compare
    the compiled HLO's inventory against what the plan PROMISED, and any
    extra kind or byte (an XLA resharding all-gather, an accidentally
    densified combine) is a contract violation, not noise.

    Per-device bytes, telemetry-off steps only (telemetry's host-side
    drop-stat assembly reshards the per-device id stacks in ways no static
    budget predicts; the oracle lowers steps with ``telemetry=False``).
    Payloads are priced as f32 (the update-tree dtype) and ids as s32.

    The budget's terms per path:

    - stacked locals (``ReplicatedLocal``/``SubmodelReplicatedLocal``):
      loss psum (4 B) + sparse ``sub_rows`` psum (4 B) + dense-leaf psums
      (non-table leaves, or the whole densified tree on a dense transport)
      + the per-table combine: ``pick_combine`` decides psum (all-reduce of
      the densified (V, E_t) f32 partial) vs union (all-gather of the
      partial's ``min(V, K/ndev * cap_client)`` ids + rows).
    - flat local (``FedSgdLocal`` sparse): loss pmean + dense-leaf pmeans
      + the single-table combine on the round-union capacity + the extra
      ``used_ids`` all-gather that computes the cross-shard union count.

    Returns ``{"axis", "num_shards", "vocab", "stacked", "combine":
    {table: mode}, "capacity": {table: per-shard partial capacity},
    "components": {name: {"op", "bytes"}}, "by_op", "allowed_ops"}``.
    """
    sharding = plan.sharding
    if sharding is None:
        raise ValueError("round_collective_budget prices the cross-shard "
                         "combine: the plan has no CohortSharding")
    local, transport, server = plan.local, plan.transport, plan.server
    sparse = transport.sparse
    ndev = sharding.num_shards
    feature_keys = tuple(plan.feature_keys)
    heat_spec = heat_spec_from_axes(boxed_params_template)
    table_paths = [p for p, _ in sparse_table_paths(heat_spec)]
    plain = unbox(boxed_params_template)
    vocabs = sorted({int(tree_leaf_at(plain, p).shape[0])
                     for p in table_paths})
    vocab = vocabs[-1] if vocabs else 0
    _, data = split_heat_batch(batch)

    tables = []  # (name, vocab_t, row_elems_t)
    for p in table_paths:
        leaf = tree_leaf_at(plain, p)
        tables.append(("/".join(str(k) for k in p),
                       int(leaf.shape[0]),
                       max(int(np.prod(leaf.shape[1:])), 1)))
    static_f32 = sum(
        float(np.prod(leaf.shape))
        for path, leaf in jax.tree_util.tree_flatten_with_path(plain)[0]
        if tree_path_keys(path) not in set(table_paths)) * 4.0

    components: Dict[str, Dict] = {}
    combine_modes: Dict[str, str] = {}
    capacities: Dict[str, int] = {}

    def add(name, op, nbytes):
        if nbytes > 0:
            components[name] = {"op": op, "bytes": float(nbytes)}

    add("loss", "all-reduce", 4.0)
    if local.stacked:
        k_real = int(data[feature_keys[0]].shape[0])
        k_shard = -(-k_real // ndev)
        if sparse:
            add("sub_rows", "all-reduce", 4.0)
            add("dense_leaves", "all-reduce", static_f32)
            if sub_ids is not None:
                cap_client = int(sub_ids.shape[-1])
            else:
                feats = sum(int(np.prod(data[k].shape[1:]))
                            for k in feature_keys)
                cap_client = round_capacity(vocab, feats)
            for name, v_t, elems_t in tables:
                mode = pick_combine(v_t, elems_t, sharding.combine)
                combine_modes[name] = mode
                cap_part = min(v_t, k_shard * cap_client)
                capacities[name] = cap_part
                if mode == "psum":
                    add(f"combine:{name}", "all-reduce",
                        float(v_t) * elems_t * 4.0)
                else:
                    add(f"combine:{name}", "all-gather",
                        float(ndev) * cap_part * (4.0 + elems_t * 4.0))
        else:
            # dense transport: every leaf (densified for submodel replicas)
            # rides one psum of its f32 shard-mean
            add("dense_tree", "all-reduce", sum(
                float(np.prod(leaf.shape)) * 4.0
                for leaf in jax.tree.leaves(plain)))
    else:
        # flat pooled batch (FedSgdLocal)
        if sparse:
            add("dense_leaves", "all-reduce", static_f32)
            if sub_ids is not None:
                cap = int(sub_ids.shape[-1])
            else:
                ids_size = sum(int(np.prod(data[k].shape)) // ndev
                               for k in feature_keys)
                cap = round_capacity(vocab, ids_size)
            name, v_t, elems_t = tables[0]
            mode = pick_combine(v_t, elems_t, sharding.combine)
            combine_modes[name] = mode
            capacities[name] = cap
            if mode == "psum":
                add(f"combine:{name}", "all-reduce",
                    float(v_t) * elems_t * 4.0)
            else:
                add(f"combine:{name}", "all-gather",
                    float(ndev) * cap * (4.0 + elems_t * 4.0))
            # the cross-shard union count gathers every shard's used_ids
            add("used_ids", "all-gather", float(ndev) * cap * 4.0)
        else:
            add("dense_tree", "all-reduce", sum(
                float(np.prod(leaf.shape)) * 4.0
                for leaf in jax.tree.leaves(plain)))

    by_op: Dict[str, float] = {}
    for c in components.values():
        by_op[c["op"]] = by_op.get(c["op"], 0.0) + c["bytes"]
    return {
        "axis": sharding.axis, "num_shards": ndev, "vocab": vocab,
        "stacked": bool(local.stacked), "combine": combine_modes,
        "capacity": capacities, "components": components, "by_op": by_op,
        "allowed_ops": sorted(by_op),
    }


# ---------------------------------------------------------------------------
# the compiler: plan -> jitted round step
# ---------------------------------------------------------------------------


def _scale_tree_f32(tree, s: float):
    """``s * tree`` in float32, RowSparse-aware (the sparse-plane scaling)."""

    def f(leaf):
        if is_rowsparse(leaf):
            return RowSparse(leaf.ids, leaf.rows.astype(jnp.float32) * s,
                             leaf.num_rows)
        return leaf.astype(jnp.float32) * s

    return jax.tree.map(f, tree, is_leaf=is_rowsparse)


def _densify_stacked(tree):
    """Scatter per-client RowSparse leaves ``(K, R)`` back to dense ``(K, V)``."""
    return jax.tree.map(
        lambda l: jax.vmap(RowSparse.to_dense)(l) if is_rowsparse(l) else l,
        tree, is_leaf=is_rowsparse)


def _apply_plain(plain_params, update, eta: float):
    """``X += eta * update`` leaf-wise, RowSparse leaves via scatter-add."""

    def ap(p, u):
        if is_rowsparse(u):
            return apply_rowsparse(p, u, eta)
        return p + (u * eta).astype(p.dtype)

    return jax.tree.map(ap, plain_params, update)


def build_round_step(plan: RoundPlan, loss_fn: Callable, boxed_params_template,
                     cfg: FedConfig, *, heat_counts: Optional[Dict] = None,
                     total: Optional[float] = None,
                     server_alg=None, telemetry: bool = False) -> Callable:
    """Compile a :class:`RoundPlan` into the single jittable round step.

    ``step(state, batch, sub_ids=None) -> (new_state, metrics)`` over a
    ``ServerState``. ``batch`` carries the cohort data — flat ``(B, ...)``
    for :class:`FedSgdLocal`, ``(K, I, B, ...)`` for the replicated locals —
    plus, on the simulation entry point, the ``heat_*`` vectors.

    ``heat_counts``/``total``: bake the heat statistics statically (the
    trainer path); when omitted, counts are read from the batch's ``heat_*``
    entries and ``total = cfg.num_clients`` (the simulation path).
    ``sub_ids``: per-client submodel ids ``(K, capacity)`` (or the flat
    union ``(capacity,)``); derived in-step from the batch's feature keys
    when ``None``. ``server_alg``: pass an existing ``ServerAlgorithm`` so
    the trainer's step applies through the exact object it initialised;
    built on demand otherwise.

    ``metrics`` always carries ``"loss"``; sparse transports add
    ``"sub_rows"`` and ``"density"``. ``telemetry=True`` additionally puts a
    :class:`repro.telemetry.round.RoundTelemetry` pytree under
    ``metrics["telemetry"]`` — computed in-jit from values the step already
    produces (no extra PRNG draws, no change to losses or parameters), so it
    stacks along the scan axis under a multi-round ``lax.scan`` engine and
    crosses ``shard_map`` boundaries via psums/all-gathers.

    Each phase of the step runs under a ``jax.named_scope``
    (``repro.telemetry.spans``), so its device operations carry the phase
    in their HLO metadata: ``fedsub.local`` (local training),
    ``fedsub.aggregate`` (compression, union and segment-sum, the
    cross-shard combine), ``fedsub.apply``, ``fedsub.loss`` (the monitoring
    forward pass) and ``fedsub.telemetry`` (``sub_rows``/``density`` and
    the telemetry). Scopes are metadata: no number changes.
    """
    local, transport, server = plan.local, plan.transport, plan.server
    feature_keys = tuple(plan.feature_keys)
    heat_spec = heat_spec_from_axes(boxed_params_template)
    n_total = float(cfg.num_clients if total is None else total)
    eta = cfg.server_lr
    sparse = transport.sparse
    static_heat = heat_counts is not None
    debug = bool(plan.debug_checks) and sparse  # dense plans: nothing to check

    # ---- static metadata + build-time validation --------------------------
    paths = sparse_table_paths(heat_spec)
    table_paths = [p for p, _ in paths]
    plain_template = unbox(boxed_params_template)
    vocabs = sorted({int(tree_leaf_at(plain_template, p).shape[0])
                     for p in table_paths})
    vocab = vocabs[-1] if vocabs else 0
    if isinstance(local, SubmodelReplicatedLocal):
        if not table_paths:
            raise ValueError(
                "submodel-replica local training needs at least one axis-0 "
                "feature table")
        if len(vocabs) != 1:
            # one shared feature-id space is what lets a single per-client
            # sub_ids vector cover every table's gradient support
            raise ValueError(
                f"submodel-replica feature tables disagree on vocab: {vocabs}")
    if isinstance(local, FedSgdLocal) and not sparse:
        if max(local.microbatches, 1) != max(cfg.microbatches, 1):
            raise ValueError(
                f"cfg.microbatches={cfg.microbatches} conflicts with "
                f"FedSgdLocal(microbatches={local.microbatches}): an "
                "explicit plan owns the knob — set it on the plan")
    if sparse and isinstance(local, FedSgdLocal):
        if max(local.microbatches, 1) > 1 or cfg.microbatches > 1:
            raise ValueError(
                "FedSgdLocal on the sparse transport computes one fused "
                "cohort gradient: microbatches must be 1")
        if len(table_paths) != 1:
            # one table <-> one feature-id union is what keeps this path
            # exact: with several tables a single batch union could not
            # cover every table's gradient support (the replicated locals
            # carry per-client sub_ids and handle multi-table models)
            raise ValueError(
                f"FedSgdLocal sparse mode supports exactly one axis-0 "
                f"feature table, found {len(table_paths)}: {table_paths}")
    if not server.stateless and server_alg is None:
        acfg = dataclasses.replace(cfg, algorithm=server.algorithm)
        server_alg = make_server_algorithm(acfg)
    if server.stateless and not sparse and static_heat and server_alg is None:
        # dense transport with baked heat: the ServerAlgorithm owns the
        # correction (exactly the trainer's historical apply)
        acfg = dataclasses.replace(cfg, algorithm=server.algorithm)
        server_alg = make_server_algorithm(acfg, heat_spec=heat_spec,
                                           heat_counts=heat_counts,
                                           total=n_total)
    base_key = jax.random.PRNGKey(cfg.seed + 17)  # int8 stochastic rounding

    # ---- shared sub-plumbing ---------------------------------------------
    def batch_counts(heat: Dict) -> Dict:
        if static_heat:
            return heat_counts
        return {k[len("heat_"):]: v for k, v in heat.items()}

    def derive_flat_ids(data: Dict) -> Array:
        ids_size = sum(int(np.prod(data[k].shape)) for k in feature_keys)
        capacity = round_capacity(vocab, ids_size)
        if debug:
            sanitize.check_capacity(capacity, vocab)
        return batch_union_ids(data, feature_keys, capacity)

    def derive_cohort_ids(data: Dict) -> Array:
        feats = stacked_feature_ids(data, feature_keys)
        capacity = round_capacity(vocab, feats.shape[1])
        if debug:
            sanitize.check_capacity(capacity, vocab)
        return jax.vmap(lambda f: unique_ids_padded(f, capacity))(feats)

    def require_tables_for_ids():
        if not table_paths or len(vocabs) != 1:
            raise ValueError(
                "in-step sub-id derivation needs feature tables sharing one "
                f"axis-0 id space; found row counts {vocabs} — pass sub_ids "
                "explicitly (as FederatedTrainer does)")

    # ---- debug sanitizer (plan.debug_checks; checkify, compiled away
    # entirely when off) ----------------------------------------------------
    def _debug_check_ids(used_ids: Optional[Array], data: Dict) -> None:
        """Validate the round's sub-id unions against the RowSparse contract.

        Flat ids additionally get the largest-first drop-order check against
        the batch's own tokens; cohort ``(K, R)`` ids check it per client
        (checkify composes with vmap).
        """
        if not debug or used_ids is None or not vocab:
            return
        sanitize.check_union_ids(used_ids, vocab, name="sub_ids")
        if used_ids.ndim == 1:
            for k in feature_keys:
                sanitize.check_drop_order(used_ids, data[k], name="sub_ids")
        else:
            feats = stacked_feature_ids(data, feature_keys)

            def one(ids_row, feats_row):
                sanitize.check_drop_order(ids_row, feats_row, name="sub_ids")
                return jnp.zeros((), jnp.int32)

            jax.vmap(one)(used_ids, feats)

    def _debug_check_agg(agg) -> None:
        """Validate every aggregated RowSparse leaf at the server boundary."""
        if not debug:
            return
        for leaf in jax.tree.leaves(agg, is_leaf=is_rowsparse):
            if is_rowsparse(leaf):
                sanitize.check_rowsparse(leaf, name="agg")

    # ---- telemetry (in-jit observability; pure reads of existing values) --
    heat_space = paths[0][1][0] if paths else None

    def _sq(tree) -> Optional[Array]:
        """The telemetry's sum of squares of ``tree`` (``None`` when off)."""
        if not telemetry:
            return None
        with jax.named_scope(TELEMETRY):
            return tree_sq_sum(tree)

    @jax.named_scope(TELEMETRY)
    def _cohort_drop_tel(data: Dict, used_ids: Optional[Array]):
        """``(union ids, dropped, mass, per_client)`` from the round's ids.

        ``used_ids`` is what the step actually consumed: the per-client
        ``(K, R)`` sub-id stack or the flat ``(R,)`` cohort union. Drops are
        priced against the raw batch feature ids — exactly what
        ``unique_ids_padded``'s capacity contract silently discarded.
        """
        zi, zf = jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)
        if not (sparse and vocab) or used_ids is None:
            return None, zi, zf, None
        if used_ids.ndim == 2:
            feats = stacked_feature_ids(data, feature_keys)
            d_pc, m_pc = drop_stats(feats, used_ids, vocab)
            return (union_ids_vec(used_ids, vocab),
                    d_pc.sum(dtype=jnp.int32), m_pc.sum(),
                    d_pc.astype(jnp.int32))
        dropped, mass = drop_stats(flat_feature_ids(data, feature_keys),
                                   used_ids, vocab)
        return used_ids, dropped.astype(jnp.int32), mass, None

    @jax.named_scope(TELEMETRY)
    def _assemble_tel(union, dropped, mass, per_client, agg, counts,
                      pre_sq, post_sq, shard_union_sizes=None):
        union_size = ((union >= 0).sum(dtype=jnp.int32)
                      if union is not None else jnp.zeros((), jnp.int32))
        hv = counts.get(heat_space) if (counts and heat_space) else None
        hist = (heat_histogram(hv, union)
                if union is not None and hv is not None
                else jnp.zeros((HEAT_BUCKETS,), jnp.float32))
        dens = (union_size.astype(jnp.float32) / vocab if vocab
                else jnp.zeros((), jnp.float32))
        return RoundTelemetry(
            dropped_ids=dropped, dropped_mass=mass,
            dropped_per_client=per_client, union_size=union_size,
            agg_rows=tree_agg_rows(agg) if (sparse and agg is not None)
            else None,
            shard_union_sizes=shard_union_sizes,
            delta_norm_pre=jnp.sqrt(pre_sq),
            delta_norm_post=jnp.sqrt(post_sq),
            heat_hist=hist, density=dens)

    # ---- local step -------------------------------------------------------
    # run_local(params, data, sub_ids) -> (update, forward_loss|None,
    #                                      used_ids|None, data)
    if isinstance(local, FedSgdLocal):
        if sparse:
            table_path = table_paths[0]

            def run_local(params, data, sub_ids):
                data = pin_labels(data, feature_keys[0])
                if sub_ids is None:
                    require_tables_for_ids()
                    sub_ids = derive_flat_ids(data)
                loss, grads = submodel_value_and_grad(
                    loss_fn, params, data, table_path, feature_keys, sub_ids)
                update = _scale_tree_f32(unbox(grads), -cfg.lr)
                return update, loss, sub_ids, data
        else:
            nmb = max(local.microbatches, 1)

            def run_local(params, data, sub_ids):
                if nmb == 1:
                    loss, grads = jax.value_and_grad(loss_fn)(params, data)
                else:
                    # gradient accumulation: cohort split into microbatches
                    # so the live activation set stays within HBM at pod
                    # scale. The batch axis is keyed on the entry NAME: only
                    # "mrope_pos" carries a leading (3,) coordinate axis with
                    # batch on axis 1 — keying on shape would misroute any
                    # genuine batch-size-3 entry.
                    def split(k, x):
                        if x.ndim == 0:
                            return x
                        axis = 1 if k == "mrope_pos" else 0   # mrope (3,B,S)
                        b = x.shape[axis]
                        assert b % nmb == 0, (x.shape, nmb)
                        xs = jnp.moveaxis(x, axis, 0).reshape(
                            (nmb, b // nmb) + x.shape[:axis]
                            + x.shape[axis + 1:])
                        return xs

                    # mrope needs its leading 3-axis restored per microbatch
                    def restore(k, x):
                        if k == "mrope_pos":
                            return jnp.moveaxis(x, 1, 0)
                        return x

                    mb = {k: split(k, v) for k, v in data.items()}

                    def acc_step(carry, mbatch):
                        g_acc, l_acc = carry
                        mbatch = {k: restore(k, v) for k, v in mbatch.items()}
                        l, g = jax.value_and_grad(loss_fn)(params, mbatch)
                        g32 = jax.tree.map(lambda a, b: a + b.astype(a.dtype),
                                           g_acc, g)
                        return (g32, l_acc + l), None

                    g0 = jax.tree.map(
                        lambda p: jnp.zeros(p.shape, jnp.float32),
                        jax.tree.map(lambda x: x, params))
                    (gsum, lsum), _ = jax.lax.scan(
                        acc_step, (g0, jnp.zeros((), jnp.float32)), mb)
                    grads = tree_scale(gsum, 1.0 / nmb)
                    loss = lsum / nmb
                update = tree_scale(grads, -cfg.lr)
                return update, loss, None, data

    elif isinstance(local, ReplicatedLocal):
        local_train = make_local_trainer(loss_fn, cfg, prox_mu=local.prox_mu)

        def run_local(params, data, sub_ids):
            deltas = cohort_deltas(local_train, params, data)
            if sparse:
                if sub_ids is None:
                    require_tables_for_ids()
                    sub_ids = derive_cohort_ids(data)
                deltas = encode_delta_tree(deltas, heat_spec, sub_ids)
            return deltas, None, sub_ids, data

    elif isinstance(local, SubmodelReplicatedLocal):
        local_train = make_submodel_local_trainer(
            loss_fn, cfg, table_paths, feature_keys, prox_mu=local.prox_mu)

        def run_local(params, data, sub_ids):
            data = pin_labels(data, feature_keys[0])
            if sub_ids is None:
                sub_ids = derive_cohort_ids(data)
            deltas = cohort_submodel_deltas(local_train, params, data, sub_ids)
            return deltas, None, sub_ids, data

    else:
        raise TypeError(f"unknown LocalStep: {local!r}")
    run_local = jax.named_scope(LOCAL)(run_local)

    # ---- server apply (shared by the single-device and sharded paths) -----
    @jax.named_scope(APPLY)
    def apply_sparse(state, agg):
        """Apply an aggregated sparse-plane update (RowSparse or dense leaves,
        correction already fused)."""
        _debug_check_agg(agg)
        if server.stateless:
            plain = unbox(state.params)
            new_plain = _apply_plain(plain, agg, eta)
            return ServerState(boxed_like(new_plain, state.params),
                               state.opt, state.rounds + 1)
        # stateful server optimizers consume the dense mean delta;
        # densify once at the server boundary
        dense = boxed_like(decode_delta_tree(agg), state.params)
        return server_alg.apply(state, dense)

    @jax.named_scope(APPLY)
    def apply_dense(state, update, counts):
        """Apply a dense-transport cohort-mean update (correction pending)."""
        if server_alg is not None:
            return server_alg.apply(state, update)
        corrected = (correct_update_tree(update, heat_spec, counts, n_total)
                     if server.correct else update)
        # cast back to each param's dtype before the add: the microbatch
        # accumulator is f32, and bf16 params must not come back silently
        # promoted
        new_params = jax.tree.map(
            lambda p, c: p + c.astype(p.dtype) * eta, state.params, corrected)
        return ServerState(new_params, state.opt, state.rounds + 1)

    # ---- cohort-sharded execution (plan.sharding) -------------------------
    sharding = plan.sharding
    if sharding is not None:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        mesh, s_axis = sharding.mesh, sharding.axis
        ndev = sharding.num_shards
        if sparse and transport.int8:
            raise ValueError(
                "CohortSharding does not compose with int8 transport yet: "
                "the stochastic-rounding noise is drawn over the full cohort "
                "stack and would not reproduce the single-device stream "
                "per shard")
        if sparse and transport.topk and isinstance(local, FedSgdLocal):
            raise ValueError(
                "CohortSharding does not compose with top-k on the flat "
                "fused-gradient sparse path: top-k there selects rows of the "
                "whole-cohort union, which no per-shard selection reproduces "
                "— use a replicated local (per-client top-k shards exactly)")

        def _mask_clients(tree, wmask):
            """Zero padded clients' contributions (RowSparse-aware)."""

            def m(leaf):
                if is_rowsparse(leaf):
                    w = wmask.reshape((-1,) + (1,) * (leaf.rows.ndim - 1))
                    return RowSparse(leaf.ids,
                                     leaf.rows * w.astype(leaf.rows.dtype),
                                     leaf.num_rows)
                return leaf * wmask.reshape(
                    (-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)

            return jax.tree.map(m, tree, is_leaf=is_rowsparse)

        def _stacked_shard_body(params, data, sub_ids, wmask, counts,
                                k_real: int):
            """One shard's K/ndev clients: local steps, per-shard partial
            aggregation, cross-shard combine. Returns the REPLICATED global
            aggregate (identical on every shard) + loss / sub-row stats."""
            update, _, used_ids, data = run_local(params, data, sub_ids)
            _debug_check_ids(used_ids, data)  # checkify crosses shard_map
            raw = update
            with jax.named_scope(AGGREGATE):
                if sparse and transport.topk:
                    # per-client row selection shards exactly (no cohort
                    # state)
                    update = compress_delta_tree(update, topk=transport.topk)
                update = _mask_clients(update, wmask)
                scale = 1.0 / float(k_real)

                if sparse:
                    def agg_leaf(leaf, space):
                        if is_rowsparse(leaf):
                            h = (counts.get(space[0])
                                 if server.correct and space is not None
                                 else None)
                            part = aggregate_rowsparse_partial(
                                leaf, union_backend=transport.union_backend)
                            return combine_rowsparse_partials(
                                part, s_axis, ndev, h, n_total, scale,
                                combine=sharding.combine,
                                union_backend=transport.union_backend)
                        mean = jax.lax.psum(leaf.sum(axis=0), s_axis) * scale
                        if server.correct:
                            mean = correct_dense_leaf(mean, space, counts,
                                                      n_total)
                        return mean

                    agg = jax.tree.map(
                        agg_leaf, update, heat_spec.leaf_spaces,
                        is_leaf=lambda x: x is None or is_rowsparse(x))
                else:
                    if isinstance(local, SubmodelReplicatedLocal):
                        update = _densify_stacked(update)
                    agg = jax.tree.map(
                        lambda d: jax.lax.psum(d.sum(axis=0), s_axis) * scale,
                        update)

            with jax.named_scope(LOSS):
                first = jax.tree.map(lambda x: x[:, 0], data)
                losses = jax.vmap(lambda b: loss_fn(params, b))(first)
                loss = jax.lax.psum((losses * wmask).sum(), s_axis) / k_real
            with jax.named_scope(TELEMETRY):
                if sparse and used_ids is not None:
                    valid = (used_ids >= 0) & (wmask > 0)[:, None]
                    sub_rows = jax.lax.psum(valid.sum(), s_axis)
                else:
                    sub_rows = jnp.zeros((), jnp.int32)
                if not telemetry:
                    return agg, loss, sub_rows
                # pre/post-compression norms over the REAL clients only (pad
                # clients are cyclic repeats; masking keeps them out of both)
                pre_sq = jax.lax.psum(tree_sq_sum(_mask_clients(raw, wmask)),
                                      s_axis)
                post_sq = jax.lax.psum(tree_sq_sum(update), s_axis)
                tel = {"norm_pre_sq": pre_sq, "norm_post_sq": post_sq}
                if sparse:
                    masked = jnp.where((wmask > 0)[:, None], used_ids, -1)
                    tel["used_ids"] = masked
                    tel["shard_union"] = count_unique_ids(masked)[None]
                return agg, loss, sub_rows, tel

        def _flat_shard_body(params, data, sub_ids, counts):
            """One shard's B/ndev examples of the pooled cohort batch.

            Exactness contract (the standard data-parallel one): ``loss_fn``
            is a uniform mean over the batch axis, so the cohort gradient is
            the mean of equal-size shard gradients. A caller-provided
            ``sub_ids`` union is replicated to every shard (each shard's
            gradient support is a subset of it), exactly as the
            single-device step consumes it.
            """
            update, fwd_loss, used_ids, _ = run_local(params, data, sub_ids)
            _debug_check_ids(used_ids, data)  # checkify crosses shard_map
            with jax.named_scope(LOSS):
                loss = jax.lax.pmean(fwd_loss, s_axis)
            scale = 1.0 / float(ndev)
            if sparse:
                def fix(leaf, space):
                    if is_rowsparse(leaf):
                        h = (counts.get(space[0])
                             if server.correct and space is not None else None)
                        return combine_rowsparse_partials(
                            leaf, s_axis, ndev, h, n_total, scale,
                            combine=sharding.combine,
                            union_backend=transport.union_backend)
                    leaf = jax.lax.pmean(leaf, s_axis)
                    if server.correct:
                        leaf = correct_dense_leaf(leaf, space, counts, n_total)
                    return leaf

                with jax.named_scope(AGGREGATE):
                    agg = jax.tree.map(
                        fix, update, heat_spec.leaf_spaces,
                        is_leaf=lambda x: x is None or is_rowsparse(x))
                with jax.named_scope(TELEMETRY):
                    # the single-device union count: distinct ids across
                    # shards
                    sub_rows = count_unique_ids(
                        jax.lax.all_gather(used_ids, s_axis))
                out = (agg, loss, sub_rows)
            else:
                with jax.named_scope(AGGREGATE):
                    update = jax.tree.map(lambda g: jax.lax.pmean(g, s_axis),
                                          update)
                out = (update, loss, jnp.zeros((), jnp.int32))
            if not telemetry:
                return out
            # the flat path never compresses under sharding (topk/int8 are
            # rejected combos above), so pre == post: the L2 of the combined
            # replicated aggregate is the honest per-round figure here
            sq = _sq(out[0])
            tel = {"norm_pre_sq": sq, "norm_post_sq": sq}
            if sparse:
                with jax.named_scope(TELEMETRY):
                    tel["used_ids"] = used_ids[None]
                    # used_ids is already the cross-shard union (gathered
                    # above); out_spec P(axis) reassembles one count per
                    # device
                    # repro-lint: ok shard-missing-psum -- deliberately per-shard count of the already-gathered union
                    tel["shard_union"] = (used_ids >= 0).sum(
                        dtype=jnp.int32)[None]
            return out + (tel,)

        def _shard_out_specs():
            """out_specs of a shard body: (agg, loss, sub_rows[, telemetry]).

            Telemetry parts: psum'd norms are replicated (``P()``); the
            per-shard union size and the shard's used sub-ids keep their
            shard axis (``P(s_axis)``) so the host sees one value per device
            and the full reassembled id stack.
            """
            base = (P(), P(), P())
            if not telemetry:
                return base
            tspec = {"norm_pre_sq": P(), "norm_post_sq": P()}
            if sparse:
                tspec["used_ids"] = P(s_axis)
                tspec["shard_union"] = P(s_axis)
            return base + (tspec,)

        def sharded_cohort_update(params, data, counts, sub_ids):
            """Wrap the shard body in shard_map over the cohort axis.

            Stacked locals shard (and, for non-divisible cohorts, pad + mask)
            the client axis; flat locals shard the pooled batch axis. The
            returned aggregate is replicated — bitwise identical on every
            shard — so the server apply that follows needs no resharding.
            Returns ``(agg, loss, sub_rows, k_real, tel)`` with ``tel`` the
            shard-body telemetry parts (``None`` when telemetry is off).
            """
            ospecs = _shard_out_specs()
            if local.stacked:
                k_real = data[feature_keys[0]].shape[0]
                kp = -(-k_real // ndev) * ndev
                wmask = (jnp.arange(kp) < k_real).astype(jnp.float32)
                if kp != k_real:
                    # shard-major padding: repeat clients cyclically so every
                    # pad slot computes finite values, then mask them out of
                    # every reduction (scale stays 1/k_real)
                    idx = jnp.arange(kp) % k_real
                    data = jax.tree.map(lambda x: jnp.take(x, idx, axis=0),
                                        data)
                    if sub_ids is not None:
                        sub_ids = jnp.take(sub_ids, idx, axis=0)
                dspec = jax.tree.map(lambda _: P(s_axis), data)

                def body(p, d, si, w, c):
                    return _stacked_shard_body(p, d, si, w, c, k_real)

                if sub_ids is None:
                    fn = shard_map(
                        lambda p, d, w, c: body(p, d, None, w, c), mesh=mesh,
                        in_specs=(P(), dspec, P(s_axis), P()),
                        out_specs=ospecs, check_vma=False)
                    res = fn(params, data, wmask, counts)
                else:
                    fn = shard_map(
                        body, mesh=mesh,
                        in_specs=(P(), dspec, P(s_axis), P(s_axis), P()),
                        out_specs=ospecs, check_vma=False)
                    res = fn(params, data, sub_ids, wmask, counts)
                agg, loss, sub_rows = res[:3]
                return agg, loss, sub_rows, k_real, (res[3] if telemetry
                                                     else None)
            # flat pooled batch: shard the example axis
            bleaf = (feature_keys[0] if feature_keys[0] in data
                     else next(iter(data)))
            bsz = data[bleaf].shape[0]
            if bsz % ndev:
                raise ValueError(
                    f"flat cohort batch of {bsz} examples does not divide "
                    f"over {ndev} shards: pad the batch to a multiple of the "
                    "mesh axis, or use a replicated local (which pads and "
                    "masks per-client automatically)")
            nmb = max(getattr(local, "microbatches", 1), 1)
            if nmb > 1 and (bsz // ndev) % nmb:
                raise ValueError(
                    f"per-shard batch of {bsz // ndev} examples (batch {bsz} "
                    f"over {ndev} shards) does not divide into "
                    f"{nmb} microbatches — each shard runs its own gradient "
                    "accumulation, so B must be a multiple of ndev * "
                    "microbatches")

            def fspec(k, x):
                if getattr(x, "ndim", 0) == 0:
                    return P()
                # mrope carries a leading (3,) coordinate axis; batch on 1
                return P(None, s_axis) if k == "mrope_pos" else P(s_axis)

            dspec = {k: fspec(k, v) for k, v in data.items()}
            if sub_ids is None:
                fn = shard_map(
                    lambda p, d, c: _flat_shard_body(p, d, None, c),
                    mesh=mesh, in_specs=(P(), dspec, P()),
                    out_specs=ospecs, check_vma=False)
                res = fn(params, data, counts)
            else:
                fn = shard_map(_flat_shard_body, mesh=mesh,
                               in_specs=(P(), dspec, P(), P()),
                               out_specs=ospecs, check_vma=False)
                res = fn(params, data, sub_ids, counts)
            agg, loss, sub_rows = res[:3]
            return agg, loss, sub_rows, None, (res[3] if telemetry else None)

        def sharded_step(state: ServerState, batch: Dict,
                         sub_ids: Optional[Array] = None):
            params = state.params
            heat, data = split_heat_batch(batch)
            counts = batch_counts(heat)
            agg, loss, sub_rows, k_real, tel = sharded_cohort_update(
                params, data, counts, sub_ids)
            agg_tree = agg if sparse else None
            if sparse:
                new_state = apply_sparse(state, agg)
            else:
                if local.stacked and isinstance(local,
                                                SubmodelReplicatedLocal):
                    agg = boxed_like(agg, params)
                new_state = apply_dense(state, agg, counts)
            metrics = {"loss": loss}
            with jax.named_scope(TELEMETRY):
                if sparse and vocab:
                    denom = vocab if k_real is None else k_real * vocab
                    metrics["sub_rows"] = sub_rows
                    metrics["density"] = sub_rows / denom
                if telemetry:
                    used = None
                    if sparse and vocab:
                        u = tel["used_ids"]
                        # stacked: pad clients sit at the END of the
                        # reassembled (kp, R) stack (cyclic-repeat padding),
                        # so [:k_real] recovers the real cohort. Flat: one
                        # per-shard id vector per device — their union is
                        # the cohort union.
                        used = (u[:k_real] if k_real is not None
                                else union_ids_vec(u, vocab))
                    union, dropped, mass, per_client = _cohort_drop_tel(
                        data, used)
                    metrics["telemetry"] = _assemble_tel(
                        union, dropped, mass, per_client, agg_tree, counts,
                        tel["norm_pre_sq"], tel["norm_post_sq"],
                        shard_union_sizes=tel.get("shard_union"))
            return new_state, metrics

        return sharded_step

    # ---- the step ---------------------------------------------------------
    def step(state: ServerState, batch: Dict, sub_ids: Optional[Array] = None):
        params = state.params
        heat, data = split_heat_batch(batch)
        counts = batch_counts(heat)
        update, fwd_loss, used_ids, data = run_local(params, data, sub_ids)
        _debug_check_ids(used_ids, data)
        pre_sq = _sq(update)

        agg_tree = None
        if sparse:
            with jax.named_scope(AGGREGATE):
                if transport.topk or transport.int8:
                    key = (jax.random.fold_in(base_key, state.rounds)
                           if transport.int8 else None)
                    update = compress_delta_tree(update, topk=transport.topk,
                                                 int8=transport.int8, key=key)
                if local.stacked:
                    k = data[feature_keys[0]].shape[0]
                    agg = sparse_cohort_aggregate(
                        update, heat_spec, counts, n_total, k,
                        correct=server.correct,
                        union_backend=transport.union_backend)
                else:
                    def fix(leaf, space):
                        if is_rowsparse(leaf):
                            h = (counts.get(space[0])
                                 if server.correct and space is not None
                                 else None)
                            return correct_rowsparse(leaf, h, n_total)
                        if server.correct:
                            return correct_dense_leaf(leaf, space, counts,
                                                      n_total)
                        return leaf

                    agg = jax.tree.map(
                        fix, update, heat_spec.leaf_spaces,
                        is_leaf=lambda x: x is None or is_rowsparse(x))
            post_sq = _sq(update)
            agg_tree = agg
            new_state = apply_sparse(state, agg)
        else:
            post_sq = pre_sq          # dense transport: no wire compression
            with jax.named_scope(AGGREGATE):
                if isinstance(local, SubmodelReplicatedLocal):
                    # submodel replicas against a dense server transport:
                    # the born-sparse per-client deltas scatter back to
                    # dense stacks
                    update = _densify_stacked(update)
                if local.stacked:
                    update = jax.tree.map(lambda d: d.mean(axis=0), update)
                    if isinstance(local, SubmodelReplicatedLocal):
                        update = boxed_like(update, params)
            new_state = apply_dense(state, update, counts)

        with jax.named_scope(LOSS):
            if local.stacked:
                first = jax.tree.map(lambda x: x[:, 0], data)
                loss = jax.vmap(lambda b: loss_fn(params, b))(first).mean()
            else:
                loss = fwd_loss
        metrics = {"loss": loss}
        with jax.named_scope(TELEMETRY):
            if sparse and used_ids is not None and vocab:
                sub_rows = (used_ids >= 0).sum()
                denom = (vocab if used_ids.ndim == 1
                         else used_ids.shape[0] * vocab)
                metrics["sub_rows"] = sub_rows
                metrics["density"] = sub_rows / denom
            if telemetry:
                union, dropped, mass, per_client = _cohort_drop_tel(
                    data, used_ids)
                metrics["telemetry"] = _assemble_tel(
                    union, dropped, mass, per_client, agg_tree, counts,
                    pre_sq, post_sq)
        return new_state, metrics

    return step
