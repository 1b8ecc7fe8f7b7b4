"""Telemetry plane (ISSUE 6): in-jit RoundTelemetry + host-side sinks.

Three layers under test:

1. the pure counter helpers (``repro.telemetry.round``, plus the
   ``membership`` primitive they lean on);
2. the in-jit ``RoundTelemetry`` threaded through the three execution
   paths — plain round step, the ``lax.scan`` engine, and
   ``CohortSharding`` shard_map rounds — with the acceptance parity pin:
   enabling telemetry changes NO losses, parameters, or RNG draws;
3. the host side: ``TraceSink`` JSONL events, the compile/steady split
   surfaced as ``RoundRecord.compile_time`` (compiles from JAX's
   monitoring events), the logging-based verbose reporter, and
   ``run(profile_dir=...)``. The spans and counters have their own file,
   ``tests/test_spans.py``.

CI's forced-8-device step re-runs this file so the sharded cases see a
real multi-shard mesh; on one device they still exercise one shard.
"""
import dataclasses
import functools
import glob
import json
import logging
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import FedConfig
from repro.core.algorithms import ServerState
from repro.data import make_movielens_like
from repro.federated import (CohortSharding, FederatedTrainer, FedSgdLocal,
                             RoundPlan, RowSparseTransport, ServerUpdate,
                             SubmodelReplicatedLocal, make_round_step)
from repro.federated.plan import build_round_step
from repro.launch.mesh import make_cohort_mesh
from repro.models.recsys import lr_loss, make_lr_params
from repro.sharding.logical import Param, unbox
from repro.sparse.rowsparse import membership, unique_ids_padded
from repro.telemetry import (HEAT_BUCKETS, RoundTelemetry,
                             TraceSink, drop_stats, heat_histogram,
                             read_events, split_rounds, valid_feature_ids)

NDEV = len(jax.devices())
V, D, K, I, B, S = 32, 4, 4, 2, 2, 6


# ---------------------------------------------------------------------------
# tiny model shared by the plan-level tests
# ---------------------------------------------------------------------------


def _params():
    rng = jax.random.PRNGKey(0)
    emb = jax.random.normal(rng, (V, D)) * 0.1
    w = jax.random.normal(jax.random.fold_in(rng, 1), (D,)) * 0.1
    return {"emb": Param(emb, ("vocab", "d")), "w": Param(w, (None,))}


def _loss(params, batch):
    emb, w = params["emb"].value, params["w"].value
    x = jnp.take(emb, jnp.maximum(batch["tokens"], 0), axis=0).mean(axis=-2)
    return jnp.mean(((x @ w) - batch["label"]) ** 2)


def _cfg(k=K):
    return FedConfig(num_clients=16, clients_per_round=k, local_iters=I,
                     local_batch=B, lr=0.1, sparse=True)


def _batch(seed, shape):
    rng = np.random.default_rng(seed)
    return {"tokens": jnp.asarray(rng.integers(0, V, shape), jnp.int32),
            "label": jnp.asarray(rng.normal(size=shape[:-1]).astype(np.float32)),
            "heat_vocab": jnp.asarray(
                np.maximum(rng.integers(0, 10, V), 1).astype(np.float32))}


_MODE_SHAPES = {"fedsgd": (B * K, S), "sparse": (B * K, S),
                "replicated": (K, I, B, S), "sparse_replicated": (K, I, B, S)}


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(unbox(a)), jax.tree.leaves(unbox(b))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# counter helpers
# ---------------------------------------------------------------------------


def test_membership_matches_isin():
    rng = np.random.default_rng(3)
    ids = unique_ids_padded(jnp.asarray(rng.integers(0, V, 20), jnp.int32), 16)
    tokens = jnp.asarray(rng.integers(-1, V, 40), jnp.int32)
    valid = np.asarray(ids)[np.asarray(ids) >= 0]
    expect = np.isin(np.asarray(tokens), valid) & (np.asarray(tokens) >= 0)
    np.testing.assert_array_equal(np.asarray(membership(tokens, ids)), expect)


def test_membership_all_padding_ids():
    ids = jnp.full((8,), -1, jnp.int32)
    tokens = jnp.asarray([0, 3, -1, 7], jnp.int32)
    assert not np.asarray(membership(tokens, ids)).any()


def test_valid_feature_ids_clamps_out_of_range():
    ids = jnp.asarray([-5, -1, 0, V - 1, V, V + 7], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(valid_feature_ids(ids, V)), [-1, -1, 0, V - 1, -1, -1])


def test_drop_stats_exact_vs_host():
    rng = np.random.default_rng(7)
    feats = rng.integers(-1, V, (K, 24)).astype(np.int32)
    cap = 4
    sub = jax.vmap(lambda f: unique_ids_padded(f, cap))(jnp.asarray(feats))
    dropped, mass = drop_stats(jnp.asarray(feats), sub, V)
    for k in range(K):
        row = feats[k][feats[k] >= 0]
        kept = np.asarray(sub[k])[np.asarray(sub[k]) >= 0]
        assert int(dropped[k]) == max(len(np.unique(row)) - len(kept), 0)
        assert float(mass[k]) == float((~np.isin(row, kept)).sum())


def test_drop_stats_zero_when_fitting():
    rng = np.random.default_rng(8)
    feats = rng.integers(-1, V, (K, 24)).astype(np.int32)
    sub = jax.vmap(lambda f: unique_ids_padded(f, V))(jnp.asarray(feats))
    dropped, mass = drop_stats(jnp.asarray(feats), sub, V)
    assert int(np.asarray(dropped).sum()) == 0
    assert float(np.asarray(mass).sum()) == 0.0


def test_heat_histogram_log2_buckets():
    heat = jnp.asarray([1.0, 2.0, 3.0, 4.0, 100.0], jnp.float32)
    ids = jnp.asarray([0, 1, 2, 3, 4, -1, -1], jnp.int32)
    hist = np.asarray(heat_histogram(heat, ids, HEAT_BUCKETS))
    assert hist.shape == (HEAT_BUCKETS,)
    # h=1 -> bucket 0; h in {2,3} -> 1; h=4 -> 2; h=100 -> 6; pads dropped
    assert hist[0] == 1 and hist[1] == 2 and hist[2] == 1 and hist[6] == 1
    assert hist.sum() == 5


# ---------------------------------------------------------------------------
# host-side primitives: TraceSink
# ---------------------------------------------------------------------------


def test_trace_sink_jsonl_roundtrip(tmp_path):
    path = tmp_path / "trace.jsonl"
    with TraceSink(str(path)) as sink:
        sink.emit({"event": "round", "round": 1, "union_size": 7})
        sink.emit({"event": "record", "round": 1, "train_loss": 0.5})
        assert len(sink.events) == 2
    events = read_events(str(path))
    assert [e["event"] for e in events] == ["round", "record"]
    assert events[0]["union_size"] == 7


def test_trace_sink_json_safe_for_device_scalars(tmp_path):
    """Satellite pin: emitting a telemetry dict whose leaves are jnp / numpy
    scalars and 0-d arrays must write valid JSON (coerced via the default=
    serializer) and round-trip through read_events as plain Python."""
    path = tmp_path / "trace.jsonl"
    with TraceSink(str(path)) as sink:
        sink.emit({"event": "round", "round": jnp.asarray(3, jnp.int32),
                   "loss": jnp.float32(0.25),
                   "density": np.float64(0.5),
                   "union": np.asarray(7),                 # 0-d ndarray
                   "hist": jnp.arange(3, dtype=jnp.float32),
                   "nested": {"occupancy": jnp.asarray(2)}})
    (event,) = read_events(str(path))
    assert event["round"] == 3 and isinstance(event["round"], int)
    assert event["loss"] == pytest.approx(0.25)
    assert event["density"] == pytest.approx(0.5)
    assert event["union"] == 7
    assert event["hist"] == [0.0, 1.0, 2.0]
    assert event["nested"]["occupancy"] == 2
    # genuinely unserialisable junk still fails loudly
    with pytest.raises(TypeError):
        with TraceSink(str(tmp_path / "bad.jsonl")) as sink:
            sink.emit({"event": "round", "obj": object()})


def test_trace_sink_report_goes_through_logging(caplog):
    sink = TraceSink()
    with caplog.at_level(logging.INFO, logger="repro.telemetry"):
        sink.report("hello round")
    assert any("hello round" in r.message for r in caplog.records)
    assert all(r.name == "repro.telemetry" for r in caplog.records)


# ---------------------------------------------------------------------------
# parity pin: telemetry on/off is bit-identical (plain + scan + sharded)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", sorted(_MODE_SHAPES))
def test_round_step_parity_all_modes(mode):
    params = _params()
    batch = _batch(11, _MODE_SHAPES[mode])
    s0 = jax.jit(make_round_step(_loss, params, _cfg(), mode=mode))
    s1 = jax.jit(make_round_step(_loss, params, _cfg(), mode=mode,
                                 telemetry=True))
    p0, m0 = s0(params, batch)
    p1, m1 = s1(params, batch)
    assert "telemetry" not in m0
    _assert_trees_equal(p0, p1)
    np.testing.assert_array_equal(np.asarray(m0["loss"]),
                                  np.asarray(m1["loss"]))
    tel = m1["telemetry"]
    assert isinstance(tel, RoundTelemetry)
    assert int(tel.dropped_ids) == 0 and float(tel.dropped_mass) == 0.0
    assert 0.0 <= float(tel.density) <= 1.0
    if mode.startswith("sparse"):
        assert int(tel.union_size) > 0
        assert float(tel.heat_hist.sum()) == float(tel.union_size)
    assert float(tel.delta_norm_pre) > 0.0


def test_scan_engine_parity_and_stacking():
    """Telemetry rides the lax.scan: fields gain a leading round axis,
    split_rounds recovers per-round host events, losses stay identical."""
    n = 3
    params = _params()
    cfg = _cfg()
    plan = RoundPlan(SubmodelReplicatedLocal(), RowSparseTransport(),
                     ServerUpdate("fedsubavg"))
    batches = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[_batch(50 + r, (K, I, B, S)) for r in range(n)])
    feats = batches["tokens"].reshape(n * K, -1)
    sub = jax.vmap(lambda f: unique_ids_padded(f, V))(feats)
    sub = sub.reshape(n, K, V)

    def engine(telemetry):
        step = build_round_step(plan, _loss, params, cfg, telemetry=telemetry)
        return jax.jit(lambda s, bs, ids: jax.lax.scan(
            lambda c, xs: step(c, *xs), s, (bs, ids)))

    state = ServerState(params, (), jnp.zeros((), jnp.int32))
    s0, m0 = engine(False)(state, batches, sub)
    s1, m1 = engine(True)(state, batches, sub)
    _assert_trees_equal(s0.params, s1.params)
    np.testing.assert_array_equal(np.asarray(m0["loss"]),
                                  np.asarray(m1["loss"]))
    tel = m1["telemetry"]
    assert tel.union_size.shape == (n,)
    events = split_rounds(tel, n)
    assert len(events) == n
    assert all(e["dropped_ids"] == 0 for e in events)
    assert all(len(e["heat_hist"]) == HEAT_BUCKETS for e in events)


@pytest.mark.skipif(NDEV < 2, reason="needs a multi-device mesh")
def test_sharded_parity_on_off():
    params = _params()
    cfg = _cfg(k=NDEV)
    plan = RoundPlan(SubmodelReplicatedLocal(), RowSparseTransport(),
                     ServerUpdate("fedsubavg"),
                     sharding=CohortSharding(make_cohort_mesh()))
    batch = _batch(21, (NDEV, I, B, S))
    state = ServerState(params, (), jnp.zeros((), jnp.int32))
    s0, m0 = jax.jit(build_round_step(plan, _loss, params, cfg))(state, batch)
    s1, m1 = jax.jit(build_round_step(plan, _loss, params, cfg,
                                      telemetry=True))(state, batch)
    _assert_trees_equal(s0.params, s1.params)
    np.testing.assert_array_equal(np.asarray(m0["loss"]),
                                  np.asarray(m1["loss"]))
    tel = m1["telemetry"]
    assert tel.shard_union_sizes is not None
    assert tel.shard_union_sizes.shape == (NDEV,)
    assert int(tel.dropped_ids) == 0


# ---------------------------------------------------------------------------
# capacity-overflow accounting: exact counts on all three paths
# ---------------------------------------------------------------------------


def _expected_drops(feats, cap):
    """Host-side truth: per-client (distinct - kept, occurrence mass)."""
    dropped = mass = 0
    for row in np.asarray(feats):
        row = row[row >= 0]
        kept = np.asarray(unique_ids_padded(jnp.asarray(row), cap))
        kept = kept[kept >= 0]
        dropped += max(len(np.unique(row)) - len(kept), 0)
        mass += int((~np.isin(row, kept)).sum())
    return dropped, mass


def _overflow_case(k=K, seed=31, cap=4):
    batch = _batch(seed, (k, I, B, S))
    feats = batch["tokens"].reshape(k, -1)
    sub_small = jax.vmap(lambda f: unique_ids_padded(f, cap))(feats)
    sub_fit = jax.vmap(lambda f: unique_ids_padded(f, V))(feats)
    return batch, feats, sub_small, sub_fit


def test_overflow_exact_count_plain():
    params = _params()
    plan = RoundPlan(SubmodelReplicatedLocal(), RowSparseTransport(),
                     ServerUpdate("fedsubavg"))
    step = jax.jit(build_round_step(plan, _loss, params, _cfg(),
                                    telemetry=True))
    state = ServerState(params, (), jnp.zeros((), jnp.int32))
    batch, feats, sub_small, sub_fit = _overflow_case()
    exp_dropped, exp_mass = _expected_drops(feats, 4)
    assert exp_dropped > 0

    _, m = step(state, batch, sub_small)
    tel = m["telemetry"]
    assert int(tel.dropped_ids) == exp_dropped
    assert float(tel.dropped_mass) == float(exp_mass)
    assert int(np.asarray(tel.dropped_per_client).sum()) == exp_dropped

    _, m2 = step(state, batch, sub_fit)
    assert int(m2["telemetry"].dropped_ids) == 0
    assert float(m2["telemetry"].dropped_mass) == 0.0


def test_overflow_exact_count_scan_engine():
    n = 2
    params = _params()
    plan = RoundPlan(SubmodelReplicatedLocal(), RowSparseTransport(),
                     ServerUpdate("fedsubavg"))
    step = build_round_step(plan, _loss, params, _cfg(), telemetry=True)
    engine = jax.jit(lambda s, bs, ids: jax.lax.scan(
        lambda c, xs: step(c, *xs), s, (bs, ids)))
    cases = [_overflow_case(seed=60 + r) for r in range(n)]
    batches = jax.tree.map(lambda *xs: jnp.stack(xs), *[c[0] for c in cases])
    sub = jnp.stack([c[2] for c in cases])
    state = ServerState(params, (), jnp.zeros((), jnp.int32))
    _, m = engine(state, batches, sub)
    events = split_rounds(m["telemetry"], n)
    for r in range(n):
        exp_dropped, exp_mass = _expected_drops(cases[r][1], 4)
        assert events[r]["dropped_ids"] == exp_dropped
        assert events[r]["dropped_mass"] == float(exp_mass)


@pytest.mark.skipif(NDEV < 2, reason="needs a multi-device mesh")
def test_overflow_exact_count_sharded():
    """The 8-forced-CPU-device path of the acceptance criteria: a sharded
    round reports the same exact drop count as the host-side truth."""
    params = _params()
    cfg = _cfg(k=NDEV)
    plan = RoundPlan(SubmodelReplicatedLocal(), RowSparseTransport(),
                     ServerUpdate("fedsubavg"),
                     sharding=CohortSharding(make_cohort_mesh()))
    step = jax.jit(build_round_step(plan, _loss, params, cfg, telemetry=True))
    state = ServerState(params, (), jnp.zeros((), jnp.int32))
    batch, feats, sub_small, sub_fit = _overflow_case(k=NDEV, seed=77)
    exp_dropped, exp_mass = _expected_drops(feats, 4)
    assert exp_dropped > 0

    _, m = step(state, batch, sub_small)
    tel = m["telemetry"]
    assert int(tel.dropped_ids) == exp_dropped
    assert float(tel.dropped_mass) == float(exp_mass)
    assert int(np.asarray(tel.dropped_per_client).sum()) == exp_dropped

    _, m2 = step(state, batch, sub_fit)
    assert int(m2["telemetry"].dropped_ids) == 0


def test_topk_compression_shrinks_post_norm():
    """delta_norm_pre/post bracket the top-k transport: post < pre when the
    transport drops rows, equal when it keeps everything."""
    params = _params()
    batch = _batch(41, (B * K, S))
    state = ServerState(params, (), jnp.zeros((), jnp.int32))

    def norms(topk):
        plan = RoundPlan(FedSgdLocal(), RowSparseTransport(topk=topk),
                         ServerUpdate("fedsubavg"))
        step = jax.jit(build_round_step(plan, _loss, params, _cfg(),
                                        telemetry=True))
        _, m = step(state, batch)
        t = m["telemetry"]
        return float(t.delta_norm_pre), float(t.delta_norm_post)

    pre, post = norms(topk=2)
    assert 0.0 < post < pre
    pre0, post0 = norms(topk=0)
    assert post0 == pytest.approx(pre0, rel=1e-6)


# ---------------------------------------------------------------------------
# trainer integration: compile split, sinks, verbose logging, profiler
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def ds():
    return make_movielens_like(num_clients=40, num_items=40, mean_samples=15)


def _trainer(ds, sink=None, telemetry=True, sparse=True, engine_cfg=None):
    cfg = engine_cfg or FedConfig(
        num_clients=ds.num_clients, clients_per_round=6, local_iters=3,
        local_batch=4, lr=0.5, algorithm="fedsubavg", sparse=sparse)
    return FederatedTrainer(ds, functools.partial(make_lr_params,
                                                  ds.num_features),
                            lr_loss, cfg, predict_fn=None, sink=sink,
                            telemetry=telemetry)


def test_trainer_compile_time_split(ds):
    """Satellite 1: the first chunk carries the jit compile, later chunks
    (and a whole second ``run``) report compile_time == 0; wall_time is the
    steady-state mean and no longer blends the compile in."""
    tr = _trainer(ds)
    tr.run(4, eval_every=2)
    assert tr.history[0].compile_time > 0
    assert tr.history[1].compile_time == 0.0
    assert 0 < tr.history[1].wall_time < tr.history[0].compile_time
    tr.run(4, eval_every=2)
    assert all(r.compile_time == 0.0 for r in tr.history[2:])


def test_trainer_telemetry_log_and_summary(ds):
    tr = _trainer(ds)
    tr.run(4, eval_every=2)
    assert len(tr.telemetry_log) == 4
    ev = tr.telemetry_log[0]
    for key in ("round", "dropped_ids", "dropped_mass", "union_size",
                "delta_norm_pre", "delta_norm_post", "heat_hist", "density",
                "comm"):
        assert key in ev
    s = tr.telemetry_summary()
    assert s["rounds"] == 4 and s["dropped_ids"] == 0
    assert s["mean_union_size"] > 0 and 0 < s["mean_density"] <= 1
    assert len(s["heat_hist"]) == HEAT_BUCKETS


def test_trainer_jsonl_sink(tmp_path, ds):
    path = tmp_path / "rounds.jsonl"
    tr = _trainer(ds, sink=TraceSink(str(path)))
    tr.run(4, eval_every=2)
    tr.sink.close()
    events = read_events(str(path))
    kinds = {e["event"] for e in events}
    assert kinds == {"round", "record"}
    rounds = [e for e in events if e["event"] == "round"]
    assert len(rounds) == 4
    assert "density" in rounds[0]["comm"]      # CommStats merged, un-collided
    records = [e for e in events if e["event"] == "record"]
    assert {"wall_time", "compile_time", "train_loss", "host_syncs",
            "compiles"} <= set(records[0])
    # the first record's stretch compiled the round; every round pulled
    assert records[0]["compiles"] > 0
    assert all(r["host_syncs"] > 0 for r in records)
    # everything on the wire is plain JSON scalars/lists
    json.dumps(events)


def test_trainer_parity_loop_and_engine(ds):
    """Acceptance parity at the trainer level: identical per-round losses
    with telemetry on/off, on both the per-round loop and the scan engine."""
    l_on = [_trainer(ds, telemetry=True).run_round() for _ in range(1)]
    t_on, t_off = _trainer(ds, telemetry=True), _trainer(ds, telemetry=False)
    assert [t_on.run_round() for _ in range(3)] == \
           [t_off.run_round() for _ in range(3)]
    e_on, e_off = _trainer(ds, telemetry=True), _trainer(ds, telemetry=False)
    assert e_on.run_rounds(3) == e_off.run_rounds(3)
    assert len(e_on.telemetry_log) == 3
    assert len(e_off.telemetry_log) == 0
    assert l_on  # loop path above produced a real loss


def test_trainer_dense_path_telemetry(ds):
    cfg = FedConfig(num_clients=ds.num_clients, clients_per_round=6,
                    local_iters=3, local_batch=4, lr=0.5,
                    algorithm="fedsubavg", sparse=False)
    tr = _trainer(ds, engine_cfg=cfg)
    tr.run(2, eval_every=2)
    assert len(tr.telemetry_log) == 2
    ev = tr.telemetry_log[0]
    assert ev["dropped_ids"] == 0 and ev["delta_norm_pre"] > 0


def test_trainer_verbose_reports_through_logging(ds, caplog):
    """Satellite 2: the verbose path goes through the logging reporter (the
    old print content preserved), capturable via caplog."""
    tr = _trainer(ds)
    with caplog.at_level(logging.INFO, logger="repro.telemetry"):
        tr.run(2, eval_every=2, verbose=True)
    msgs = [r.message for r in caplog.records]
    assert any("[fedsubavg] round 2:" in m and "loss=" in m for m in msgs)


def test_trainer_profile_dir_smoke(tmp_path, ds):
    """Acceptance: jax.profiler trace files land under profile_dir."""
    pdir = tmp_path / "prof"
    tr = _trainer(ds)
    tr.run(2, eval_every=2, profile_dir=str(pdir))
    files = glob.glob(os.path.join(str(pdir), "**", "*.xplane.pb"),
                      recursive=True)
    assert files, f"no profiler traces under {pdir}"
