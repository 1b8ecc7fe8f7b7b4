"""Server orchestration of federated rounds (Algorithm 1, server process).

``FederatedTrainer`` runs the paper's full experimental protocol over a
``FederatedDataset``: samples K clients per round, dispatches local training,
aggregates deltas, applies the configured server algorithm, and tracks train
loss / test metrics. CentralSGD (the paper's non-federated reference) shares
the same interface.

Since the RoundPlan redesign the trainer no longer re-derives the execution
layout from ``FedConfig`` flags with its own branches: the flags resolve to a
``repro.federated.plan.RoundPlan`` (``plan_from_config``), the jitted round
step comes from the same ``build_round_step`` that backs ``make_round_step``,
and an explicit ``plan=`` argument overrides the flag resolution entirely —
one dispatch system, two entry points.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.pytree import tree_add, tree_scale
from repro.configs.base import FedConfig
from repro.core.algorithms import ServerState, make_server_algorithm
from repro.core.heat import (HeatStats, clamp_heat_estimate,
                             estimate_heat_randomized_response)
from repro.data.batching import pooled_batches, sample_cohort_batch
from repro.data.synthetic import FederatedDataset
from repro.federated.arrivals import ArrivalSim
from repro.federated.async_engine import (BufferedAsyncServerUpdate,
                                          build_async_engine)
from repro.federated.plan import (CohortSharding, RoundPlan,
                                  SubmodelReplicatedLocal, build_round_step,
                                  heat_spec_from_axes, plan_from_config,
                                  sparse_table_paths)
from repro.federated.metrics import accuracy, auc
from repro.sharding.logical import unbox
from repro.sparse.comm import CommStats, model_comm_meta
from repro.sparse.encode import tree_leaf_at
from repro.sparse.rowsparse import count_unique_ids, unique_ids_padded
from repro.telemetry import TraceSink
from repro.telemetry.round import (RoundTelemetry, split_rounds,
                                   telemetry_to_host)
from repro.telemetry.spans import (ACCOUNT, CALL, CHUNK, DISPATCH, SAMPLE,
                                   SUB_IDS, counters, host_pull, span)


@dataclass
class RoundRecord:
    round: int
    train_loss: float
    test_metric: float
    # comm accounting (sparse mode; zeros on the dense path)
    bytes_up: float = 0.0            # cumulative sparse-plane uplink bytes
    bytes_down: float = 0.0          # cumulative sparse-plane downlink bytes
    density: float = 1.0             # mean per-client submodel density so far
    wall_time: float = 0.0           # STEADY-STATE mean seconds/round since
                                     # the last record (compiling dispatches
                                     # excluded; blended mean only when every
                                     # dispatch of the stretch compiled)
    compile_time: float = 0.0        # seconds spent in compiling dispatches
                                     # since the last record (0 once warm);
                                     # a dispatch compiles when JAX reports
                                     # a backend compile or a persistent-
                                     # cache load during it


# ---------------------------------------------------------------------------
# jitted sub-id derivation (the server engine's cohort preprocessing)
# ---------------------------------------------------------------------------


def pow2_capacity(max_count: int, floor: int = 8) -> int:
    """Smallest power-of-two >= max(max_count, floor).

    Sub-id capacities are bucketed to powers of two so the jitted round step
    compiles at most O(log V) distinct variants over a whole training run —
    the invariant must never be broken by clamping to a non-pow2 table size
    (a capacity slightly above V only adds padding slots, which every sparse
    consumer drops).
    """
    cap = floor
    while cap < max_count:
        cap *= 2
    return cap


def _valid_ids(flat: jax.Array, num_features: int) -> jax.Array:
    """Ids outside ``[0, num_features)`` become -1 (the padding convention)."""
    flat = flat.astype(jnp.int32)
    return jnp.where((flat >= 0) & (flat < num_features), flat, -1)


@functools.partial(jax.jit, static_argnames=("num_features",))
def count_sub_ids(feats: jax.Array, num_features: int) -> jax.Array:
    """Per-client distinct-feature counts ``(K,)`` from stacked id leaves.

    ``feats``: ``(K, M)`` int feature ids, negatives are padding. The count
    is over distinct non-negative ids — the size of client k's submodel
    S(k), i.e. the number of valid slots ``derive_sub_ids`` will fill.
    Sort-based (``count_unique_ids``), so the per-client cost is O(M log M)
    in the client's own id count, never O(V) in the feature-space size.
    """

    def one(flat):
        return count_unique_ids(_valid_ids(flat, num_features))

    return jax.vmap(one)(feats)


@functools.partial(jax.jit, static_argnames=("num_features", "capacity"))
def derive_sub_ids(feats: jax.Array, num_features: int,
                   capacity: int) -> jax.Array:
    """Per-client sorted unique feature ids ``(K, capacity)``, -1 padded.

    The jitted replacement for the trainer's former host-side per-client
    ``np.unique`` loops, now sort-based (``unique_ids_padded`` under vmap):
    O(M log M) per client in its own id count M. The earlier bitmap-rank
    variant paid O(V) per client — a (V,) bitmap, cumsum and scatter — which
    at V=65k dominated the whole sharded round (~60 ms/round of host-shared
    work no mesh could parallelise). ``capacity`` must come from
    ``pow2_capacity`` of ``count_sub_ids(...).max()`` so the jit cache stays
    O(log V).
    """

    def one(flat):
        return unique_ids_padded(_valid_ids(flat, num_features), capacity)

    return jax.vmap(one)(feats)


class FederatedTrainer:
    """End-to-end federated training loop for the paper-scale models."""

    def __init__(self, ds: FederatedDataset, make_params: Callable,
                 loss_fn: Callable, cfg: FedConfig,
                 predict_fn: Optional[Callable] = None,
                 metric: str = "auc", rng_seed: int = 0,
                 plan: Optional[RoundPlan] = None,
                 mesh: Optional[Any] = None,
                 telemetry: bool = True,
                 sink: Optional[TraceSink] = None):
        """``mesh``: a device mesh (e.g. ``make_cohort_mesh()``) to shard the
        cohort axis of every round over its ``"data"`` axis. The host-side
        pipeline is untouched — cohorts are sampled from the same RNG stream
        and laid out shard-major (device d owns the contiguous client block
        d), so sharded rounds reproduce single-device rounds to 1e-5. Pass a
        plan with an explicit ``CohortSharding`` for a non-default axis or
        combine strategy.

        ``telemetry``: compute the in-jit :class:`RoundTelemetry` counters
        each round (pure reads — losses, parameters and the RNG stream are
        bit-identical either way) and collect them in ``telemetry_log``.
        ``sink``: a :class:`repro.telemetry.TraceSink` receiving structured
        round/record events (and the verbose reporting); an in-memory sink
        is created when omitted — pass ``TraceSink(path)`` to persist JSONL.
        """
        self.ds = ds
        self.cfg = cfg
        self.loss_fn = loss_fn
        self.predict_fn = predict_fn
        self.metric = metric
        self.np_rng = np.random.default_rng(cfg.seed + rng_seed)

        params = make_params(rng=jax.random.PRNGKey(cfg.seed))
        self.heat = self._resolve_heat(ds, cfg)
        heat_spec = heat_spec_from_axes(params)
        heat_counts = {"vocab": jnp.asarray(self.heat.counts, jnp.float32)}
        total = self.heat.total
        self._heat_spec = heat_spec
        self._heat_counts = heat_counts
        self.alg = make_server_algorithm(cfg, heat_spec=heat_spec,
                                         heat_counts=heat_counts, total=total)
        self.state = self.alg.init(params)
        self.history: List[RoundRecord] = []
        self.comm_log: List[CommStats] = []
        self._rounds_run = 0
        self._last_capacity: Optional[int] = None   # last sparse sub-id bucket
        self.plan: Optional[RoundPlan] = None
        self._sparse_local: Optional[str] = None
        self._sparse_paths: List = []
        self._is_sparse = False
        self.telemetry_enabled = bool(telemetry)
        self.sink = sink if sink is not None else TraceSink()
        self.telemetry_log: List[Dict[str, Any]] = []
        self._record_counts = counters()      # at the last record event
        # buffered-async engines, keyed by (server slot, telemetry flag);
        # the streaming-heat EMA persists across run_async calls
        self._async_engines: Dict[Any, Any] = {}
        self._async_heat_ema = None

        if cfg.algorithm == "central":
            if plan is not None:
                raise ValueError("central training takes no RoundPlan")
            if mesh is not None:
                raise ValueError("central training takes no cohort mesh")
            self._central_step = jax.jit(self._make_central_step())
            return

        self.plan = self._resolve_trainer_plan(params, plan)
        if mesh is not None:
            if (self.plan.sharding is not None
                    and self.plan.sharding.mesh is not mesh):
                raise ValueError(
                    "mesh= conflicts with the explicit plan's CohortSharding "
                    "— set the mesh on the plan only")
            if self.plan.sharding is None:
                self.plan = dataclasses.replace(
                    self.plan, sharding=CohortSharding(mesh))
        self._is_sparse = self.plan.transport.sparse
        round_step = build_round_step(self.plan, loss_fn, params, cfg,
                                      heat_counts=heat_counts, total=total,
                                      server_alg=self.alg,
                                      telemetry=self.telemetry_enabled)
        if self._is_sparse:
            # jit caches one trace per sub_ids capacity (kept to O(log V)
            # variants by pow2_capacity bucketing); ServerState buffers are
            # donated through the step so the table is updated in place.
            # Donation is skipped for cohort-sharded plans: donating the
            # replicated state through a shard_map program forces a full
            # buffer round-trip per call on the multi-device CPU backend
            # (measured ~20x per-round regression), defeating the sharding
            donate = () if self.plan.sharding is not None else (0,)
            self._comm_meta = model_comm_meta(unbox(params),
                                              set(self._sparse_paths))

            def engine(state, cohorts, sub_ids):
                # multi-round driver: scan the round step over stacked
                # cohorts so dispatch overhead amortises across rounds
                return jax.lax.scan(lambda s, xs: round_step(s, *xs), state,
                                    (cohorts, sub_ids))

            if self.plan.debug_checks:
                # the step emits checkify checks: functionalise + jit via
                # checked_jit. Donation is dropped — the checkify error
                # output aliases nothing, and debug mode is not a perf path.
                from repro.analysis.sanitize import checked_jit
                self._sparse_step = checked_jit(round_step)
                self._sparse_engine = checked_jit(engine)
            else:
                self._sparse_step = jax.jit(round_step,
                                            donate_argnums=donate)
                self._sparse_engine = jax.jit(engine, donate_argnums=donate)
        else:
            self._round_step = jax.jit(round_step)
        if self.plan.sharding is not None:
            # commit the server state replicated over the cohort mesh BEFORE
            # the first step: the executable then compiles for (and returns)
            # that layout, so threading the state through rounds never
            # reshards. (Compiling against the initial single-device layout
            # instead makes every later call copy the replicated output back
            # to one device — a measured ~6x per-round penalty.)
            self.state = jax.device_put(
                self.state,
                jax.sharding.NamedSharding(self.plan.sharding.mesh,
                                           jax.sharding.PartitionSpec()))

    # ------------------------------------------------------------------
    def _resolve_trainer_plan(self, params,
                              plan: Optional[RoundPlan]) -> RoundPlan:
        """Resolve FedConfig flags (or validate an explicit plan) against the
        model/dataset: which leaves ride the sparse plane, whether submodel
        replicas are gatherable, and which batch keys carry feature ids."""
        keys = [self.ds.feature_key]
        if self.ds.feature_key == "hist" and "target" in self.ds.client_data:
            keys.append("target")
        self._feature_batch_keys = keys
        ordered_paths = [p for p, _ in sparse_table_paths(self._heat_spec)]
        self._sparse_paths = ordered_paths
        plain = unbox(params)
        table_rows = [int(tree_leaf_at(plain, p).shape[0])
                      for p in ordered_paths]
        # gathered submodel replicas need every feature table keyed by the
        # dataset's id space (sub_ids index rows)
        gatherable = (bool(ordered_paths)
                      and all(r == self.ds.num_features for r in table_rows))
        if plan is None:
            plan = plan_from_config(self.cfg, feature_keys=tuple(keys),
                                    gatherable=gatherable)
        else:
            if plan.server.algorithm != self.cfg.algorithm:
                raise ValueError(
                    f"plan.server.algorithm={plan.server.algorithm!r} "
                    f"disagrees with cfg.algorithm={self.cfg.algorithm!r}: "
                    "the trainer's server state is built from the config")
            if not plan.local.stacked:
                raise ValueError(
                    f"{type(plan.local).__name__} consumes a flat pooled "
                    "batch, but FederatedTrainer samples stacked "
                    "(K, I, B, ...) cohorts with per-client sub_ids — drive "
                    "flat plans through make_round_step/build_round_step")
            # the dataset, not the caller, knows which batch keys carry
            # feature ids — rebind so submodel remapping stays correct
            plan = dataclasses.replace(plan, feature_keys=tuple(keys))
        submodel = isinstance(plan.local, SubmodelReplicatedLocal)
        if submodel and not gatherable:
            raise ValueError(
                "SubmodelReplicatedLocal (sparse_local='sparse_replicated') "
                f"needs axis-0 feature tables of {self.ds.num_features} rows; "
                f"found {table_rows}")
        if plan.transport.sparse:
            self._sparse_local = ("sparse_replicated" if submodel
                                  else "replicated")
        return plan

    # ------------------------------------------------------------------
    def _resolve_heat(self, ds: FederatedDataset, cfg: FedConfig) -> HeatStats:
        """Heat statistics under the configured estimator (App. F) and, when
        ``weighted``, the App. D.4 per-client weighting — *composed with* the
        estimator: weighted randomized response stays private (the weighting
        is applied to the noisy reported bits, never to raw client data);
        exact and secure_agg are exact by construction, so their weighted
        variant aggregates ``w_c`` per involving client directly."""
        key = ds.feature_key

        def client_ids(c):
            ids = ds.client_data[key][c].reshape(-1)
            ids = ids[ids >= 0]
            if key == "hist" and "target" in ds.client_data:
                t = ds.client_data["target"][c].reshape(-1)
                ids = np.concatenate([ids, t[t >= 0]])
            return np.unique(ids)

        w = ds.sample_counts.astype(np.float64) if cfg.weighted else None
        if cfg.heat_estimator == "randomized_response":
            ind = np.zeros((ds.num_clients, ds.num_features), np.int64)
            for c in range(ds.num_clients):
                ind[c, client_ids(c)] = 1
            est = estimate_heat_randomized_response(
                ind, cfg.rr_flip_prob, np.random.default_rng(cfg.seed),
                weights=w)
            total = float(ds.num_clients) if w is None else float(w.sum())
            # clamp into [min_count, total], NOT [0, total]: a noisy estimate
            # <= 0 for a genuinely hot feature would hit the counts > 0 /
            # h > 0 gates and silently zero that row's update every round
            counts = clamp_heat_estimate(est, total)
        elif cfg.weighted:
            # exact / secure_agg: sum involving clients' weights (App. D.4)
            counts = np.zeros(ds.num_features)
            for c in range(ds.num_clients):
                counts[client_ids(c)] += w[c]
            total = float(w.sum())
        else:  # exact; secure_agg is exact by construction, reuse the counts
            counts, total = ds.heat.counts, ds.heat.total
        return HeatStats(counts=np.asarray(counts, np.float64), total=float(total),
                         name="vocab")

    def _record_telemetry(self, tel, rnd: int,
                          comm: Optional[CommStats] = None) -> None:
        """Append one round's telemetry to ``telemetry_log`` and the sink.

        ``tel`` is the in-jit :class:`RoundTelemetry` (or an already-host
        dict split from a scan-stacked engine run); ``comm`` attaches the
        round's byte accounting under a ``"comm"`` sub-object (its
        ``round``/``density`` keys would collide with telemetry fields
        at the top level).
        """
        if tel is None:
            return
        if isinstance(tel, RoundTelemetry):
            tel = telemetry_to_host(tel)
        event = {"event": "round", "round": int(rnd), **tel}
        if comm is not None:
            event["comm"] = comm.as_dict()
        self.telemetry_log.append(event)
        self.sink.emit(event)

    def _sample_sparse_cohort(self):
        """One round's host work: sample the cohort and stack its feature ids.

        Returns ``(cohort_batch, feats)`` where ``feats`` is the ``(K, M)``
        concatenation of every feature-carrying leaf — the input the jitted
        ``count_sub_ids``/``derive_sub_ids`` pair consumes. This is the only
        per-round host-side work left on the sparse path.
        """
        cfg = self.cfg
        with span(SAMPLE):
            ids = self.np_rng.choice(self.ds.num_clients,
                                     size=cfg.clients_per_round,
                                     replace=False)
            cohort = sample_cohort_batch(self.ds, ids, cfg.local_iters,
                                         cfg.local_batch, self.np_rng)
            feats = np.concatenate(
                [np.asarray(cohort[k]).reshape(len(ids), -1)
                 for k in self._feature_batch_keys], axis=1)
        return cohort, feats

    def _cohort_sub_ids(self, feats: np.ndarray):
        """``(feats, valid_counts, capacity, sub_ids)`` of stacked feature
        ids ``(K, M)``: the ids on the device, the per-client counts pulled
        to the host (the one sync before the round can be dispatched), their
        pow2 bucket, and the sub-ids derived on the device at that
        capacity."""
        with span(SUB_IDS):
            feats = jnp.asarray(feats)
            valid_counts = host_pull(
                count_sub_ids(feats, self.ds.num_features), "count")
            # pow2 capacity bounds jit recompiles to O(log V) variants
            capacity = pow2_capacity(int(valid_counts.max()))
            sub_ids = derive_sub_ids(feats, self.ds.num_features, capacity)
        return feats, valid_counts, capacity, sub_ids

    def _log_sparse_comm(self, valid_counts: np.ndarray, capacity: int):
        """Comm accounting for one sparse round from per-client sub-id counts.

        The pricing itself lives on the plan's transport
        (``RowSparseTransport.round_comm``); this method feeds it the
        trainer's host-side metadata: the model's byte geometry, the round's
        sub-id counts, and whether the downlink ships gathered submodel
        buffers (submodel-replica local training) or the full table.
        """
        self.comm_log.append(self.plan.transport.round_comm(
            self._rounds_run, self._comm_meta, valid_counts,
            self.ds.num_features, capacity=capacity,
            submodel_downlink=self._sparse_local == "sparse_replicated",
            local_iters=self.cfg.local_iters))

    def _run_sparse_round(self) -> float:
        cohort, feats = self._sample_sparse_cohort()
        _, valid_counts, capacity, sub_ids = self._cohort_sub_ids(feats)
        with span(DISPATCH):
            cohort = {k: jnp.asarray(v) for k, v in cohort.items()}
            self.state, metrics = self._sparse_step(self.state, cohort, sub_ids)
        self._last_capacity = capacity
        with span(ACCOUNT):
            self._log_sparse_comm(valid_counts, capacity)
            self._record_telemetry(metrics.get("telemetry"),
                                   self._rounds_run, comm=self.comm_log[-1])
        return float(host_pull(metrics["loss"], "loss"))

    def run_rounds(self, n: int) -> List[float]:
        """Drive ``n`` rounds through the in-jit engine (one ``lax.scan``).

        Identical math and RNG stream to ``n`` successive ``run_round``
        calls — the host samples all ``n`` cohorts up front (consuming
        ``np_rng`` in the same order), sub-ids for every round are derived by
        one jitted call, and a single scan-compiled program advances the
        donated ``ServerState`` through all rounds, so per-round dispatch and
        host work amortise to ~zero. Falls back to the per-round loop for
        non-sparse configurations. Returns the per-round monitoring losses.

        One honest accounting difference vs the loop: the engine buckets ALL
        ``n`` rounds to one shared sub-id capacity, so in sparse_replicated
        mode the priced submodel download per round reflects that shared
        buffer, where the per-round loop prices each round's own (possibly
        smaller) bucket. Losses/params/uplink are identical either way.
        """
        if n <= 0:
            return []
        cfg = self.cfg
        if cfg.algorithm == "central" or not self._is_sparse:
            return [self.run_round() for _ in range(n)]
        k = cfg.clients_per_round
        with span(CALL, driver="run_rounds", first_round=self._rounds_run + 1,
                  rounds=n):
            cohorts, feats = [], []
            for _ in range(n):
                c, f = self._sample_sparse_cohort()
                cohorts.append(c)
                feats.append(f)
            _, valid_counts, capacity, sub_ids = self._cohort_sub_ids(
                np.concatenate(feats, axis=0))
            valid_counts = valid_counts.reshape(n, k)
            with span(DISPATCH):
                stacked = {key: jnp.asarray(np.stack([c[key] for c in cohorts]))
                           for key in cohorts[0]}
                sub_ids = sub_ids.reshape(n, k, capacity)
                self.state, metrics = self._sparse_engine(self.state, stacked, sub_ids)
            losses = host_pull(metrics["loss"], "loss")
            self._last_capacity = capacity
            with span(ACCOUNT):
                # telemetry rode the scan: each field gained a leading round
                # axis
                tel_events = (split_rounds(metrics["telemetry"], n)
                              if "telemetry" in metrics else [None] * n)
                for r in range(n):
                    self._rounds_run += 1
                    self._log_sparse_comm(valid_counts[r], capacity)
                    self._record_telemetry(tel_events[r], self._rounds_run,
                                           comm=self.comm_log[-1])
            return [float(l) for l in losses]

    def run_async(self, sim: ArrivalSim,
                  server: Optional[BufferedAsyncServerUpdate] = None
                  ) -> List[float]:
        """Drive a buffered-async run over ``sim``'s compiled event stream.

        The trainer samples ``sim.num_rounds`` dispatch waves of K clients
        from the SAME ``np_rng`` stream (and in the same order) as
        ``run_rounds(sim.num_rounds)``, stacks them as per-task data, and
        scans the :mod:`repro.federated.async_engine` event loop over the
        schedule in one jitted dispatch. ``server`` overrides the async
        server slot; by default the plan's algorithm runs with
        ``buffer_size = K`` — which on a zero-delay sim makes this call
        reproduce ``run_rounds`` losses/params/RNG exactly (the pinned
        degeneracy).

        Each buffer fire is one server version: it consumes one global round
        number, one comm-log entry (priced over the M arrivals it
        aggregated) and one telemetry event, exactly like a synchronous
        round. Returns the per-fire buffered monitoring losses
        (``sim`` arrivals that never complete a buffer are absorbed but not
        applied, matching the engine).
        """
        if self.plan is None or not self._is_sparse:
            raise ValueError("run_async needs a sparse federated plan "
                             "(RowSparseTransport)")
        if self.plan.sharding is not None:
            raise ValueError(
                "run_async does not compose with CohortSharding: the event "
                "stream is inherently sequential — run the synchronous "
                "engine on the mesh instead")
        cfg = self.cfg
        srv = (server if server is not None else BufferedAsyncServerUpdate(
            algorithm=self.plan.server.algorithm,
            buffer_size=cfg.clients_per_round))
        sch = sim.compile(cfg.clients_per_round, srv.buffer_size)
        with span(CALL, driver="run_async", first_round=self._rounds_run + 1,
                  rounds=sch.num_fires):
            return self._run_async(sim, srv, sch)

    def _run_async(self, sim: ArrivalSim, srv: BufferedAsyncServerUpdate,
                   sch) -> List[float]:
        cfg = self.cfg
        key = (srv, self.telemetry_enabled)
        if key not in self._async_engines:
            plan = dataclasses.replace(self.plan, server=srv)
            eng = build_async_engine(plan, self.loss_fn, self.state.params,
                                     cfg, heat_counts=self._heat_counts,
                                     total=self.heat.total,
                                     telemetry=self.telemetry_enabled)
            self._async_engines[key] = (eng, jax.jit(eng.run,
                                                     donate_argnums=(0,)))
        eng, run = self._async_engines[key]

        cohorts, feats = [], []
        for _ in range(sim.num_rounds):
            c, f = self._sample_sparse_cohort()
            cohorts.append(c)
            feats.append(f)
        flat_feats, valid_counts, capacity, sub_ids = self._cohort_sub_ids(
            np.concatenate(feats, axis=0))

        with span(DISPATCH):
            tasks = {key_: jnp.asarray(np.concatenate(
                [np.asarray(c[key_]) for c in cohorts], axis=0))
                for key_ in cohorts[0]}
            state0 = eng.init(self.state, num_slots=sch.num_slots,
                              capacity=capacity,
                              heat_ema=(self._async_heat_ema
                                        if srv.heat == "ema" else None))
            state, ys = run(state0, sch.event_arrays(), tasks, sub_ids,
                            flat_feats if self.telemetry_enabled else None)
        self.state = state.server
        if srv.heat == "ema":
            self._async_heat_ema = state.heat_ema
        self._last_capacity = capacity

        fired = np.flatnonzero(np.asarray(sch.fire))
        losses = host_pull(ys["loss"], "loss")[fired]
        with span(ACCOUNT):
            tel_events = (split_rounds(ys["telemetry"], sch.num_events)
                          if "telemetry" in ys else None)
            m = srv.buffer_size
            for f in range(sch.num_fires):
                self._rounds_run += 1
                arrived = sch.arrival_tasks[f * m:(f + 1) * m]
                self._log_sparse_comm(valid_counts[arrived], capacity)
                self._record_telemetry(
                    tel_events[fired[f]] if tel_events else None,
                    self._rounds_run, comm=self.comm_log[-1])
        return [float(l) for l in losses]

    def _make_central_step(self):
        def central_step(state: ServerState, batches):
            def step(p, batch):
                l, g = jax.value_and_grad(self.loss_fn)(p, batch)
                return tree_add(p, tree_scale(g, -self.cfg.lr)), l

            p, losses = jax.lax.scan(step, state.params, batches)
            return ServerState(p, state.opt, state.rounds + 1), losses.mean()

        return central_step

    # ------------------------------------------------------------------
    def run_round(self) -> float:
        with span(CALL, driver="run_round", first_round=self._rounds_run + 1,
                  rounds=1):
            self._rounds_run += 1
            if self.cfg.algorithm == "central":
                return self._run_central_round()
            if self._is_sparse:
                return self._run_sparse_round()
            return self._run_dense_round()

    def _run_central_round(self) -> float:
        cfg = self.cfg
        with span(SAMPLE):
            batches = pooled_batches(self.ds, cfg.local_iters,
                                     cfg.local_batch * cfg.clients_per_round,
                                     self.np_rng)
        with span(DISPATCH):
            batches = {k: jnp.asarray(v) for k, v in batches.items()}
            self.state, loss = self._central_step(self.state, batches)
        return float(host_pull(loss, "loss"))

    def _run_dense_round(self) -> float:
        cfg = self.cfg
        with span(SAMPLE):
            ids = self.np_rng.choice(self.ds.num_clients,
                                     size=cfg.clients_per_round,
                                     replace=False)
            cohort = sample_cohort_batch(self.ds, ids, cfg.local_iters,
                                         cfg.local_batch, self.np_rng)
        with span(DISPATCH):
            cohort = {k: jnp.asarray(v) for k, v in cohort.items()}
            self.state, metrics = self._round_step(self.state, cohort)
        with span(ACCOUNT):
            self._record_telemetry(metrics.get("telemetry"), self._rounds_run)
        return float(host_pull(metrics["loss"], "loss"))

    def evaluate(self) -> float:
        if self.predict_fn is None:
            return float("nan")
        scores = np.asarray(self.predict_fn(self.state.params, self.ds.test_data))
        labels = self.ds.test_data["label"]
        return auc(labels, scores) if self.metric == "auc" else accuracy(labels, scores)

    def train_loss(self, num_batches: int = 8, batch: int = 256) -> float:
        """Loss over a fixed random sample of the pooled training set."""
        rng = np.random.default_rng(123)
        batches = pooled_batches(self.ds, num_batches, batch, rng)
        tot = 0.0
        for i in range(num_batches):
            b = {k: jnp.asarray(v[i]) for k, v in batches.items()}
            tot += float(self.loss_fn(self.state.params, b))
        return tot / num_batches

    def comm_summary(self) -> Dict[str, float]:
        """Aggregate comm accounting over all sparse rounds so far."""
        from repro.federated.metrics import comm_summary
        return comm_summary(self.comm_log)

    def telemetry_summary(self) -> Dict[str, Any]:
        """Aggregate the per-round telemetry events collected so far."""
        from repro.federated.metrics import telemetry_summary
        return telemetry_summary(self.telemetry_log)

    def run(self, rounds: int, eval_every: int = 10, verbose: bool = False,
            engine: bool = False, profile_dir: Optional[str] = None):
        """Train for ``rounds`` rounds, evaluating every ``eval_every``.

        ``engine=True`` drives each between-evals stretch through
        ``run_rounds`` (the in-jit multi-round scan) instead of one
        ``run_round`` dispatch per round; results are identical to f32
        tolerance.

        Timing is attributed per dispatch: ``RoundRecord.wall_time`` is the
        steady-state mean seconds/round of the stretch (compiling dispatches
        excluded — falling back to the blended mean only when EVERY dispatch
        of the stretch compiled, so it is never zero), and the compile cost
        lands in ``RoundRecord.compile_time`` (zero once the jit caches are
        warm). A dispatch compiles when JAX's monitoring events report a
        backend compile or a persistent-cache load during it. Each
        ``record`` event of the sink also carries ``host_syncs`` and
        ``compiles``: the blocking pulls and the XLA compiles since the
        previous record.

        ``profile_dir``: wrap the whole call in a ``jax.profiler`` trace
        written under that directory. The trace holds the trainer's spans
        on the profiler's clock (``repro.telemetry.spans``): one
        ``fedsub.chunk`` per stretch (args ``first_round``, ``rounds``),
        inside it one ``fedsub.call`` per driver call, and inside that
        ``fedsub.sample`` per cohort, ``fedsub.sub_ids``,
        ``fedsub.dispatch``, ``fedsub.account`` and one ``fedsub.sync`` per
        blocking pull (arg ``what``). The device operations carry the round
        step's scopes (``fedsub.local``, ``fedsub.aggregate``,
        ``fedsub.apply``, ``fedsub.loss``, ``fedsub.telemetry``) in their
        HLO metadata. Open the directory in TensorBoard's profile plugin, or
        read the ``.xplane.pb`` under it with
        ``jax.profiler.ProfileData.from_file``.

        ``RoundRecord.round`` numbers continue from the trainer's global
        round counter, so repeated ``run()`` calls (or mixing ``run_round``
        with ``run``) append monotone history instead of colliding with it.
        """
        if profile_dir is not None:
            jax.profiler.start_trace(str(profile_dir))
        try:
            return self._run_chunks(rounds, eval_every, verbose, engine)
        finally:
            if profile_dir is not None:
                jax.profiler.stop_trace()

    def _run_chunks(self, rounds: int, eval_every: int, verbose: bool,
                    engine: bool):
        done = 0
        # the engine only exists on the sparse path; dense/central configs
        # fall back to per-round dispatches (where compile attribution is
        # per round, not per chunk)
        use_engine = (engine and self._is_sparse
                      and self.cfg.algorithm != "central")

        def executables() -> int:
            c = counters()
            return c["compiles"] + c["cache_loads"]

        while done < rounds:
            chunk = min(eval_every - done % eval_every, rounds - done)
            compile_s = 0.0
            steady: List[float] = []
            calls, per_call = (1, chunk) if use_engine else (chunk, 1)
            t0 = time.perf_counter()
            with span(CHUNK, first_round=self._rounds_run + 1, rounds=chunk):
                for _ in range(calls):
                    made = executables()
                    t1 = time.perf_counter()
                    if use_engine:
                        self.run_rounds(chunk)
                    else:
                        self.run_round()
                    dt = time.perf_counter() - t1
                    if executables() != made:
                        compile_s += dt
                    else:
                        steady.append(dt / per_call)
            total = time.perf_counter() - t0
            wall = sum(steady) / len(steady) if steady else total / chunk
            done += chunk
            if done % eval_every == 0 or done == rounds:
                metric = self.evaluate()
                tl = self.train_loss()
                rec = RoundRecord(self._rounds_run, tl, metric,
                                  wall_time=wall, compile_time=compile_s)
                if self.comm_log:
                    s = self.comm_summary()
                    rec.bytes_up = s["bytes_up_sparse"]
                    rec.bytes_down = s["bytes_down_sparse"]
                    rec.density = s["mean_density"]
                self.history.append(rec)
                now, last = counters(), self._record_counts
                self._record_counts = now
                self.sink.emit({
                    "event": "record", **dataclasses.asdict(rec),
                    "host_syncs": now["host_syncs"] - last["host_syncs"],
                    "compiles": now["compiles"] - last["compiles"]})
                if verbose:
                    self.sink.report(
                        f"[{self.cfg.algorithm}] round {self._rounds_run}: "
                        f"loss={self.history[-1].train_loss:.4f} "
                        f"{self.metric}={metric:.4f} "
                        f"({wall * 1e3:.1f} ms/round)")
        return self.history
