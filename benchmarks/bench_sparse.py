"""Sparse submodel update plane: aggregation backends + the server engine.

Three sections, all emitted to the CSV stream and to
``BENCH_sparse_engine.json`` (the artifact CI uploads):

1. dense vs row-sparse cohort aggregation (the PR-1 comparison): K client
   deltas over a (V, D) feature table, cohort-mean + FedSubAvg correction on
   both planes.
2. union-backend comparison for ``aggregate_rowsparse``: jnp-sort vs
   jnp-bitmap vs the ``union_segsum`` Pallas kernel across
   V in {65k, 262k} x density in {1%, 10%}. On CPU the kernel runs in
   interpret mode, which executes the kernel body in Python — honest but
   orders of magnitude off the compiled path — so off-TPU the pallas column
   is measured at a reduced proxy shape and labelled as such (nothing is
   silently dropped; the JSON carries the actual shape measured).
3. server engine: host-loop ``run_round`` x n vs the in-jit
   ``run_rounds(n)`` scan on a real ``FederatedTrainer`` (LSTM over a
   sent140-like corpus), wall-clock per round after warmup.

4. replicated local training: dense per-client replicas
   (``sparse_local="replicated"``, the K*V*D memory wall) vs gathered
   submodel replicas (``"sparse_replicated"``, K*capacity*D) — time per
   round and the analytic replica-memory curve at V in {65k, 262k}.

5. cohort-sharded rounds: the ``run_rounds`` engine driven single-device vs
   through ``CohortSharding`` meshes of every available power-of-two device
   count — per-round wall time vs device count, ``speedup_vs_1dev`` per
   mesh. Force virtual CPU devices with
   ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI smoke job
   does); with one visible device only the plain unsharded 1-device
   baseline is measured (no shard_map runs).

6. telemetry plane: the same fedsubavg sparse round with the in-jit
   ``RoundTelemetry`` counters off vs on — per-round wall time for both,
   the on/off overhead ratio, and the run-level counter summary (drop
   totals, mean union size / density). The telemetry-on trainer streams
   its round events through a ``TraceSink`` into ``BENCH_telemetry.jsonl``
   (CI uploads it as an artifact; ``check_regression`` validates the
   section's schema and that trainer-derived rounds report zero drops).

7. collective bytes: the hlo_audit oracle run as a benchmark — for each
   sharded sparse plan x combine, the HLO-measured per-kind collective bytes
   of one compiled round step, next to the analytic budget
   (``round_collective_budget``) and the contract/drift verdict. Bytes are
   static-shape-deterministic, so ``check_regression`` pins them against the
   committed baseline directly (no timing hermeticity needed): growth means
   a resharding or densified combine crept into the lowering. Needs a
   multi-device host (the forced-8 CI smoke job); skipped with a note on a
   single device.

8. buffered-async throughput: the event-stream engine (``run_async``) vs
   the synchronous barrier under a heavy-tailed log-normal delay
   distribution with injected stragglers. Two kinds of numbers: honest
   measured wall time per scanned event, and the seed-deterministic
   *modeled* makespans from the compiled schedule — clients absorbed per
   simulated time unit for both engines and their ratio (``sim_speedup``).
   The modeled ratio is machine-independent, so ``check_regression`` pins
   async > barrier directly against the committed baseline.

9. kernel roofline: achieved vs analytic bandwidth per union backend. The
   analytic bytes come from the kernel-contract plane — the pallas column is
   ``repro.analysis.kernel_audit.cost_model`` run on the ``pallas_call``
   captured out of the traced aggregate at the bench shape (so operand
   re-streaming, e.g. the heat table refetched per vocab block, is priced
   in), the jnp columns are documented closed forms over the same shapes.
   Analytic bytes/FLOPs are static-shape-deterministic, so
   ``check_regression`` pins them against the baseline directly (growth =
   re-streaming or a densified path crept in); achieved GB/s is honest
   measured wall time and stays machine-local (fresh-run sanity only).

``REPRO_BENCH_SMOKE=1`` shrinks every section to seconds of runtime (tiny V,
2 rounds, interpret-mode kernel) — the CI smoke job runs that on every PR so
the pallas backend, the scan engine and the sharded engine stay exercised.

Artifacts land under ``benchmarks/`` by default (``REPRO_BENCH_JSON`` /
``REPRO_BENCH_TELEMETRY_JSONL`` override) so bench runs never litter the
repo root.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import time_us
from repro.configs import FedConfig
from repro.core.aggregate import HeatSpec, correct_update_tree
from repro.data.synthetic import make_sent140_like
from repro.federated import (ArrivalSim, BufferedAsyncServerUpdate,
                             FederatedTrainer)
from repro.kernels import ops, ref
from repro.models.recsys import lstm_logits, lstm_loss, make_lstm_params
from repro.sparse import RowSparse, aggregate_rowsparse, tree_wire_bytes

import functools

SMOKE = os.environ.get("REPRO_BENCH_SMOKE") == "1"
_BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
JSON_PATH = os.environ.get(
    "REPRO_BENCH_JSON", os.path.join(_BENCH_DIR, "BENCH_sparse_engine.json"))


def _cohort(rng, k: int, v: int, r: int, d: int):
    ids = np.full((k, r), -1, np.int32)
    rows = np.zeros((k, r, d), np.float32)
    heat = np.zeros(v, np.float32)
    for i in range(k):
        sup = np.sort(rng.choice(v, size=r, replace=False))
        ids[i] = sup
        rows[i] = rng.normal(size=(r, d)).astype(np.float32)
        heat[sup] += 1
    return jnp.asarray(ids), jnp.asarray(rows), jnp.asarray(heat)


def _bench_dense_vs_sparse(rng, out, records):
    """Section 1: the dense plane vs the row-sparse plane (PR-1 comparison)."""
    k, d, total = (4, 8, 100.0) if SMOKE else (16, 64, 100.0)
    spec = HeatSpec({"emb": ("vocab", 0)})
    vs = (4_096,) if SMOKE else (65_536, 262_144)
    densities = (0.01, 0.10) if SMOKE else (0.001, 0.01, 0.05, 0.10)

    for v in vs:
        for density in densities:
            r = max(int(v * density), 1)
            ids, rows, heat = _cohort(rng, k, v, r, d)
            stacked = RowSparse(ids, rows, v)

            sparse_fn = jax.jit(
                lambda s: aggregate_rowsparse(s, heat, total, 1.0 / k))
            us_sparse = time_us(sparse_fn, stacked, iters=3)

            # dense baseline starts from already-densified per-client deltas
            dense_in = jax.vmap(lambda i_, r_: RowSparse(i_, r_, v).to_dense())(
                ids, rows)
            counts = {"vocab": heat}
            dense_fn = jax.jit(lambda dt: correct_update_tree(
                {"emb": dt.mean(axis=0)}, spec, counts, total)["emb"])
            us_dense = time_us(dense_fn, dense_in, iters=2)

            bytes_sparse = tree_wire_bytes({"emb": stacked})
            bytes_dense = float(k * v * d * 4)
            out.append((
                "sparse/aggregate", us_sparse,
                f"V={v};density={density};K={k};D={d};us_dense={us_dense:.0f};"
                f"speedup={us_dense / us_sparse:.2f}x;"
                f"bytes_sparse={bytes_sparse:.0f};bytes_dense={bytes_dense:.0f};"
                f"wire_ratio={bytes_dense / bytes_sparse:.1f}x"))
            records.append(dict(section="dense_vs_sparse", v=v, density=density,
                                k=k, d=d, us_sparse=us_sparse,
                                us_dense=us_dense))
            del dense_in


def _bench_union_backends(rng, out, records):
    """Section 2: jnp-sort vs jnp-bitmap vs pallas union backends."""
    on_tpu = jax.default_backend() == "tpu"
    k, d, total = (4, 8, 100.0) if SMOKE else (16, 64, 100.0)
    vs = (512,) if SMOKE else (65_536, 262_144)
    for v in vs:
        for density in (0.01, 0.10):
            r = max(int(v * density), 1)
            ids, rows, heat = _cohort(rng, k, v, r, d)
            stacked = RowSparse(ids, rows, v)
            row = dict(section="union_backends", v=v, density=density, k=k, d=d)
            for backend in ("sort", "bitmap") + (("pallas",) if on_tpu or SMOKE
                                                 else ()):
                fn = jax.jit(lambda s, _b=backend: aggregate_rowsparse(
                    s, heat, total, 1.0 / k, union_backend=_b))
                us = time_us(fn, stacked, iters=3)
                mode = ("compiled" if on_tpu else "interpret") \
                    if backend == "pallas" else "xla"
                out.append((f"sparse/union_{backend}", us,
                            f"V={v};density={density};K={k};D={d};mode={mode}"))
                row[f"us_{backend}"] = us
            records.append(row)
    if not (on_tpu or SMOKE):
        # off-TPU the interpreter cannot run the paper-scale shapes in
        # reasonable time; measure the kernel at a reduced proxy shape
        v, r = 2_048, 204
        ids, rows, heat = _cohort(rng, k, v, r, d)
        stacked = RowSparse(ids, rows, v)
        fn = jax.jit(lambda s: aggregate_rowsparse(s, heat, total, 1.0 / k,
                                                   union_backend="pallas"))
        us = time_us(fn, stacked, iters=2)
        out.append(("sparse/union_pallas", us,
                    f"V={v};density={r / v:.2f};K={k};D={d};mode=interpret;"
                    f"note=proxy_shape_cpu"))
        records.append(dict(section="union_backends", v=v, density=r / v,
                            k=k, d=d, us_pallas=us, proxy=True))


def _bench_engine(out, records):
    """Section 3: host-loop round driving vs the in-jit run_rounds scan."""
    if SMOKE:
        vocab, clients, kpr, n_rounds, mean_samples = 512, 16, 4, 2, 8
    else:
        vocab, clients, kpr, n_rounds, mean_samples = 262_144, 32, 8, 8, 25
    ds = make_sent140_like(num_clients=clients, vocab=vocab,
                           mean_samples=mean_samples, seq_len=24)
    cfg = FedConfig(num_clients=ds.num_clients, clients_per_round=kpr,
                    local_iters=2, local_batch=4, lr=0.3,
                    algorithm="fedsubavg", sparse=True)

    def make_trainer():
        return FederatedTrainer(
            ds, functools.partial(make_lstm_params, ds.num_features,
                                  emb_dim=16, hidden=32, layers=1),
            lstm_loss, cfg,
            predict_fn=lambda p, t: lstm_logits(
                p, jnp.asarray(t["tokens"]),
                (jnp.asarray(t["tokens"]) >= 0).astype(jnp.float32)))

    tr_loop = make_trainer()
    tr_loop.run_round()                                  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(n_rounds):
        tr_loop.run_round()
    us_loop = (time.perf_counter() - t0) / n_rounds * 1e6

    tr_scan = make_trainer()
    tr_scan.run_rounds(n_rounds)                         # warmup/compile
    t0 = time.perf_counter()
    tr_scan.run_rounds(n_rounds)
    us_scan = (time.perf_counter() - t0) / n_rounds * 1e6

    density = tr_loop.comm_summary()["mean_density"]
    out.append(("sparse/engine_host_loop", us_loop,
                f"V={vocab};K={kpr};rounds={n_rounds};density={density:.4f}"))
    out.append(("sparse/engine_in_jit", us_scan,
                f"V={vocab};K={kpr};rounds={n_rounds};density={density:.4f};"
                f"speedup={us_loop / us_scan:.2f}x"))
    records.append(dict(section="engine", v=vocab, k=kpr, rounds=n_rounds,
                        density=density, us_per_round_host_loop=us_loop,
                        us_per_round_in_jit=us_scan,
                        speedup=us_loop / us_scan))


def _bench_replicated(out, records):
    """Section 4: dense-replica vs gathered-submodel local training."""
    if SMOKE:
        shapes = ((512,),)
        clients, kpr, n_rounds, mean_samples, emb = 16, 4, 2, 8, 8
    else:
        shapes = ((65_536,), (262_144,))
        clients, kpr, n_rounds, mean_samples, emb = 32, 8, 4, 25, 16
    for (vocab,) in shapes:
        ds = make_sent140_like(num_clients=clients, vocab=vocab,
                               mean_samples=mean_samples, seq_len=24)

        def make_trainer(local_mode):
            cfg = FedConfig(num_clients=ds.num_clients, clients_per_round=kpr,
                            local_iters=2, local_batch=4, lr=0.3,
                            algorithm="fedsubavg", sparse=True,
                            sparse_local=local_mode)
            return FederatedTrainer(
                ds, functools.partial(make_lstm_params, ds.num_features,
                                      emb_dim=emb, hidden=32, layers=1),
                lstm_loss, cfg)

        row = dict(section="replicated", v=vocab, k=kpr, d=emb,
                   rounds=n_rounds)
        for local_mode in ("replicated", "sparse_replicated"):
            tr = make_trainer(local_mode)
            tr.run_round()                               # warmup/compile
            t0 = time.perf_counter()
            for _ in range(n_rounds):
                tr.run_round()
            us = (time.perf_counter() - t0) / n_rounds * 1e6
            # replica HBM for the feature table: K*V*D dense vs K*cap*D
            rows_per_client = (min(tr._last_capacity, ds.num_features)
                               if local_mode == "sparse_replicated"
                               else ds.num_features)
            replica_bytes = kpr * rows_per_client * emb * 4
            row[f"us_{local_mode}"] = us
            row[f"replica_bytes_{local_mode}"] = replica_bytes
            out.append((f"sparse/local_{local_mode}", us,
                        f"V={vocab};K={kpr};D={emb};I=2;"
                        f"replica_bytes={replica_bytes:.0f}"))
        row["speedup"] = row["us_replicated"] / row["us_sparse_replicated"]
        row["mem_ratio"] = (row["replica_bytes_replicated"]
                            / row["replica_bytes_sparse_replicated"])
        out.append(("sparse/local_mode_win", row["speedup"],
                    f"V={vocab};mem_ratio={row['mem_ratio']:.1f}x;"
                    f"speedup={row['speedup']:.2f}x"))
        records.append(row)


def _bench_sharded(out, records):
    """Section 5: cohort-sharded run_rounds engine vs single-device.

    The cohort is sized local-phase-heavy (I=4, B=8, hidden=64): sharding
    parallelises the K clients' local training, so the win grows with local
    compute and saturates at the physical core count; the replicated server
    apply and the collectives are the fixed sharded overhead the tiny smoke
    shapes expose (speedup < 1 there is expected and gated relatively).
    """
    from repro.launch.mesh import make_cohort_mesh

    if SMOKE:
        vocab, clients, kpr, n_rounds, mean_samples, emb, hid, li, lb = (
            512, 16, 8, 2, 8, 8, 32, 2, 4)
    else:
        vocab, clients, kpr, n_rounds, mean_samples, emb, hid, li, lb = (
            65_536, 32, 16, 8, 25, 16, 64, 4, 8)
    ds = make_sent140_like(num_clients=clients, vocab=vocab,
                           mean_samples=mean_samples, seq_len=24)
    cfg = FedConfig(num_clients=ds.num_clients, clients_per_round=kpr,
                    local_iters=li, local_batch=lb, lr=0.3,
                    algorithm="fedsubavg", sparse=True)

    def make_trainer(mesh):
        return FederatedTrainer(
            ds, functools.partial(make_lstm_params, ds.num_features,
                                  emb_dim=emb, hidden=hid, layers=1),
            lstm_loss, cfg, mesh=mesh)

    n_avail = len(jax.devices())
    ndevs = [n for n in (1, 2, 4, 8) if n <= n_avail]
    us_1dev = None
    for ndev in ndevs:
        mesh = None if ndev == 1 else make_cohort_mesh(ndev)
        tr = make_trainer(mesh)
        tr.run_rounds(n_rounds)                          # warmup/compile
        t0 = time.perf_counter()
        tr.run_rounds(n_rounds)
        us = (time.perf_counter() - t0) / n_rounds * 1e6
        if ndev == 1:
            us_1dev = us
        speedup = us_1dev / us
        out.append((f"sparse/sharded_engine_{ndev}dev", us,
                    f"V={vocab};K={kpr};rounds={n_rounds};ndev={ndev};"
                    f"speedup_vs_1dev={speedup:.2f}x"))
        records.append(dict(section="sharded", v=vocab, k=kpr,
                            rounds=n_rounds, ndev=ndev, us_per_round=us,
                            speedup_vs_1dev=speedup))


def _bench_telemetry(out, records):
    """Section 6: in-jit telemetry counters off vs on, plus the counters.

    Same fedsubavg sparse shapes as section 3. The counters are pure reads
    of values the round already computes, so the overhead ratio should hover
    near 1.0x; the JSONL sink receives one round event per dispatched round
    (warmup included) and lands wherever ``REPRO_BENCH_TELEMETRY_JSONL``
    points (default ``BENCH_telemetry.jsonl``).
    """
    from repro.telemetry import TraceSink

    if SMOKE:
        vocab, clients, kpr, n_rounds, mean_samples = 512, 16, 4, 2, 8
    else:
        vocab, clients, kpr, n_rounds, mean_samples = 65_536, 32, 8, 8, 25
    ds = make_sent140_like(num_clients=clients, vocab=vocab,
                           mean_samples=mean_samples, seq_len=24)
    cfg = FedConfig(num_clients=ds.num_clients, clients_per_round=kpr,
                    local_iters=2, local_batch=4, lr=0.3,
                    algorithm="fedsubavg", sparse=True)

    def make_trainer(telemetry, sink=None):
        return FederatedTrainer(
            ds, functools.partial(make_lstm_params, ds.num_features,
                                  emb_dim=16, hidden=32, layers=1),
            lstm_loss, cfg, telemetry=telemetry, sink=sink)

    tr_off = make_trainer(False)
    tr_off.run_round()                                   # warmup/compile
    t0 = time.perf_counter()
    for _ in range(n_rounds):
        tr_off.run_round()
    us_off = (time.perf_counter() - t0) / n_rounds * 1e6

    jsonl_path = os.environ.get(
        "REPRO_BENCH_TELEMETRY_JSONL",
        os.path.join(_BENCH_DIR, "BENCH_telemetry.jsonl"))
    with TraceSink(jsonl_path) as sink:
        tr_on = make_trainer(True, sink=sink)
        tr_on.run_round()                                # warmup/compile
        t0 = time.perf_counter()
        for _ in range(n_rounds):
            tr_on.run_round()
        us_on = (time.perf_counter() - t0) / n_rounds * 1e6
        n_events = len(sink.events)
    summary = tr_on.telemetry_summary()

    overhead = us_on / us_off
    out.append(("sparse/telemetry_off", us_off,
                f"V={vocab};K={kpr};rounds={n_rounds}"))
    out.append(("sparse/telemetry_on", us_on,
                f"V={vocab};K={kpr};rounds={n_rounds};"
                f"overhead={overhead:.2f}x;"
                f"dropped_ids={summary['dropped_ids']};"
                f"mean_union={summary['mean_union_size']:.1f};"
                f"jsonl={jsonl_path}"))
    records.append(dict(section="telemetry", v=vocab, k=kpr, rounds=n_rounds,
                        us_per_round_off=us_off, us_per_round_on=us_on,
                        overhead=overhead,
                        dropped_ids=summary["dropped_ids"],
                        dropped_mass=summary["dropped_mass"],
                        mean_union_size=summary["mean_union_size"],
                        mean_density=summary["mean_density"],
                        jsonl_events=n_events, jsonl=jsonl_path))


def _bench_collectives(out, records):
    """Section 7: HLO-measured combine bytes vs the analytic budget.

    Not a timing benchmark: collective byte totals are static-shape
    deterministic, so the records double as a regression pin — the
    committed baseline's bytes must not grow (a growth is a resharding or
    a densified combine, the class the hlo_audit CI gate catches one plan
    at a time; here the whole matrix lands in the bench artifact).
    """
    import dataclasses

    from repro.analysis.hlo_audit import (collective_contract, comm_drift,
                                          lower_round_step)
    from repro.federated import CohortSharding, resolve_plan
    from repro.launch.mesh import make_cohort_mesh

    ndev = len(jax.devices())
    if ndev < 2:
        out.append(("sparse/collectives_skipped", 0.0,
                    f"ndev={ndev};needs>=2;force_with=XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8"))
        return
    vocab, emb = (512, 8) if SMOKE else (65_536, 16)
    mesh = make_cohort_mesh()
    params = make_lstm_params(vocab, emb_dim=emb, hidden=8, layers=1,
                              rng=jax.random.PRNGKey(1))
    fed = FedConfig(num_clients=16, clients_per_round=3, local_iters=2,
                    lr=0.1, algorithm="fedsubavg")
    rng = np.random.default_rng(0)
    cohort_batch = {
        "tokens": jnp.asarray(rng.integers(-1, vocab, (3, 2, 2, 6)),
                              jnp.int32),
        "label": jnp.asarray(rng.integers(0, 2, (3, 2, 2)), jnp.int32),
        "heat_vocab": jnp.asarray(rng.integers(0, 6, vocab), jnp.float32)}
    flat_batch = {
        "tokens": jnp.asarray(rng.integers(0, vocab, (8, 8)), jnp.int32),
        "label": jnp.asarray(rng.integers(0, 2, 8), jnp.int32),
        "heat_vocab": jnp.asarray(rng.integers(0, 6, vocab), jnp.float32)}
    for mode in ("sparse", "sparse_replicated"):
        for combine in ("psum", "union"):
            plan = dataclasses.replace(
                resolve_plan(mode, fed),
                sharding=CohortSharding(mesh, combine=combine))
            batch = flat_batch if mode == "sparse" else cohort_batch
            compiled = lower_round_step(plan, lstm_loss, params, fed, batch)
            con = collective_contract(plan, lstm_loss, params, fed, batch,
                                      compiled=compiled)
            drift = comm_drift(plan, lstm_loss, params, fed, batch,
                               compiled=compiled)
            ok = con.ok and drift.ok
            ar = con.measured_by_op.get("all-reduce", 0)
            ag = con.measured_by_op.get("all-gather", 0)
            out.append((f"sparse/collectives_{mode}_{combine}",
                        float(ar + ag),
                        f"V={vocab};D={emb};ndev={ndev};all_reduce_B={ar};"
                        f"all_gather_B={ag};ok={ok}"))
            records.append(dict(
                section="collectives", mode=mode, combine=combine, v=vocab,
                emb=emb, ndev=ndev, ok=ok,
                all_reduce_bytes=ar, all_gather_bytes=ag,
                budget_all_reduce=con.budget_by_op.get("all-reduce", 0.0),
                budget_all_gather=con.budget_by_op.get("all-gather", 0.0),
                failures=con.failures + drift.failures))


def _bench_async(out, records):
    """Section 8: buffered-async engine vs the barrier under heavy tails.

    Heavy-tailed log-normal delays (sigma=1.5) with 10% injected 10x
    stragglers — the regime where the barrier engine serialises on its
    slowest client every round. ``us_per_event`` is honest measured wall
    time for the jitted event scan; the clients-per-simulated-unit columns
    come from the schedule's deterministic makespan model, so the async >
    barrier claim is machine-independent and baseline-pinnable.
    """
    if SMOKE:
        vocab, clients, kpr, n_rounds, mean_samples = 512, 16, 4, 4, 8
    else:
        vocab, clients, kpr, n_rounds, mean_samples = 65_536, 32, 8, 12, 25
    ds = make_sent140_like(num_clients=clients, vocab=vocab,
                           mean_samples=mean_samples, seq_len=24)
    cfg = FedConfig(num_clients=ds.num_clients, clients_per_round=kpr,
                    local_iters=2, local_batch=4, lr=0.3,
                    algorithm="fedsubavg", sparse=True)
    tr = FederatedTrainer(
        ds, functools.partial(make_lstm_params, ds.num_features,
                              emb_dim=16, hidden=32, layers=1),
        lstm_loss, cfg)
    sim = ArrivalSim(num_rounds=n_rounds, delay="lognormal", delay_scale=0.5,
                     lognormal_sigma=1.5, straggler_frac=0.1,
                     straggler_factor=10.0, seed=0)
    srv = BufferedAsyncServerUpdate(buffer_size=max(kpr // 2, 1),
                                    staleness="polynomial", heat="ema")
    sch = sim.compile(kpr, srv.buffer_size)

    tr.run_async(sim, server=srv)                        # warmup/compile
    t0 = time.perf_counter()
    tr.run_async(sim, server=srv)
    us_event = (time.perf_counter() - t0) / sch.num_events * 1e6

    barrier, asynchronous = sch.barrier_makespan(), sch.async_makespan()
    per_unit_barrier = sch.num_arrivals / barrier
    per_unit_async = sch.num_arrivals / asynchronous
    out.append(("sparse/async_event_scan", us_event,
                f"V={vocab};K={kpr};M={srv.buffer_size};"
                f"events={sch.num_events};fires={sch.num_fires}"))
    out.append(("sparse/async_sim_speedup", sch.sim_speedup(),
                f"barrier_makespan={barrier:.2f};"
                f"async_makespan={asynchronous:.2f};"
                f"clients_per_unit={per_unit_async:.3f}vs"
                f"{per_unit_barrier:.3f}"))
    records.append(dict(
        section="async", v=vocab, k=kpr, rounds=n_rounds,
        buffer=srv.buffer_size, events=sch.num_events, fires=sch.num_fires,
        arrivals=sch.num_arrivals, us_per_event=us_event,
        barrier_makespan=barrier, async_makespan=asynchronous,
        clients_per_unit_barrier=per_unit_barrier,
        clients_per_unit_async=per_unit_async,
        sim_speedup=sch.sim_speedup()))


def _ceil_log2(x: int) -> int:
    return max(int(x) - 1, 1).bit_length()


def _bench_kernel_roofline(rng, out, records):
    """Section 9: analytic bytes/FLOPs vs achieved bandwidth per backend.

    One record per (shape, union backend). ``analytic_bytes`` for the pallas
    backend is the kernel-audit cost model evaluated on the ``pallas_call``
    captured from the traced aggregate (re-streaming priced in via the grid
    x BlockSpec fetch counts); the jnp backends get closed forms: the
    payload movement every backend pays (stream ids + rows in, gather heat
    at the union, write the union out) plus the backend's union-structure
    cost — bitmap: mark/cumsum/nonzero passes over the (V,) bitmap plus the
    rank gather; sort: ~log2(T) read+write key passes plus the
    binary-search remap. Achieved GB/s divides the analytic bytes by
    measured wall time; off-TPU at full shapes the pallas interpreter would
    crawl, so that cell is analytic-only (``us`` absent, nothing silently
    dropped).
    """
    from repro.analysis import kernel_audit
    from repro.common.hw import HW

    on_tpu = jax.default_backend() == "tpu"
    k, d, total = (4, 8, 100.0) if SMOKE else (16, 64, 100.0)
    vs = (512,) if SMOKE else (65_536,)
    densities = (0.10,) if SMOKE else (0.01, 0.10)
    for v in vs:
        for density in densities:
            r = max(int(v * density), 1)
            ids, rows, heat = _cohort(rng, k, v, r, d)
            stacked = RowSparse(ids, rows, v)
            t = k * r
            cap = min(v, t)
            payload = (t + t * d) * 4 + cap * 4 + (cap + cap * d) * 4
            payload_flops = float(t * d + 2 * cap * d)
            analytic = {
                # (V,) bool mark written then read twice (cumsum, bounded
                # nonzero), (V,) i32 rank written, (T,) i32 rank gather
                "bitmap": payload + v * (1 + 2 + 4) + t * 4,
                # ~log2(T) read+write passes over the (T,) i32 keys, then a
                # log2(cap) binary-search remap per element
                "sort": payload + (2 * _ceil_log2(t) + _ceil_log2(cap)) * t * 4,
            }
            flops = {
                "bitmap": payload_flops + float(v),
                "sort": payload_flops + float(t * _ceil_log2(t)),
            }
            restream = {}
            caps = kernel_audit.capture_pallas_calls(
                lambda s: aggregate_rowsparse(s, heat, total, 1.0 / k,
                                              union_backend="pallas"),
                stacked)
            cost = kernel_audit.cost_model(caps[0], kernel="union_segsum")
            analytic["pallas"] = cost.bytes_touched
            flops["pallas"] = cost.flops
            restream["pallas"] = max(
                op["restream"] for op in cost.per_operand.values())

            for backend in ("sort", "bitmap", "pallas"):
                rec = dict(section="kernel_roofline", v=v, density=density,
                           k=k, d=d, backend=backend,
                           analytic_bytes=int(analytic[backend]),
                           analytic_flops=flops[backend],
                           intensity=flops[backend] / analytic[backend],
                           restream=restream.get(backend, 1.0))
                timed = backend != "pallas" or on_tpu or SMOKE
                tail = ""
                if timed:
                    fn = jax.jit(lambda s, _b=backend: aggregate_rowsparse(
                        s, heat, total, 1.0 / k, union_backend=_b))
                    us = time_us(fn, stacked, iters=3)
                    achieved = analytic[backend] / (us * 1e-6)
                    rec.update(us=us, achieved_gbps=achieved / 1e9,
                               hbm_frac=achieved / HW["hbm_bandwidth"])
                    tail = (f";achieved_GBps={achieved / 1e9:.2f}"
                            f";hbm_frac={achieved / HW['hbm_bandwidth']:.4f}")
                else:
                    rec["analytic_only"] = True
                    tail = ";note=analytic_only_off_tpu"
                out.append((f"sparse/roofline_{backend}", rec.get("us", 0.0),
                            f"V={v};density={density};K={k};D={d};"
                            f"analytic_B={rec['analytic_bytes']};"
                            f"restream={rec['restream']:.1f}x" + tail))
                records.append(rec)


def run():
    out = []
    records = []
    rng = np.random.default_rng(0)
    # production-shaped round: 16-client cohort, 64-wide embedding rows.
    # Dense cohort aggregation is then DRAM-bound on the cold rows nobody
    # touched — exactly the inefficiency the sparse plane removes.
    _bench_dense_vs_sparse(rng, out, records)
    _bench_union_backends(rng, out, records)
    _bench_engine(out, records)
    _bench_replicated(out, records)
    _bench_sharded(out, records)
    _bench_telemetry(out, records)
    _bench_collectives(out, records)
    _bench_async(out, records)
    _bench_kernel_roofline(rng, out, records)

    # Pallas kernel (dense-output TPU path) at a kernel-friendly shape
    k, d, total = (4, 8, 100.0) if SMOKE else (16, 64, 100.0)
    v, r = (256, 32) if SMOKE else (2_048, 256)
    ids, rows, heat = _cohort(rng, k, v, r, d)
    flat_ids, flat_rows = ids.reshape(-1), rows.reshape(k * r, d)
    us_kern = time_us(
        lambda: ops.rowsparse_scatter(flat_ids, flat_rows, heat, total, v,
                                      scale=1.0 / k, v_blk=512, t_blk=512),
        iters=2)
    us_ref = time_us(
        lambda: jax.jit(ref.rowsparse_scatter_ref,
                        static_argnames=("total", "vocab", "scale"))(
            flat_ids, flat_rows, heat, total, v, scale=1.0 / k), iters=2)
    mode = "compiled" if jax.default_backend() == "tpu" else "interpret"
    out.append(("sparse/rowsparse_scatter_kernel", us_kern,
                f"V={v};T={k * r};D={d};ref_us={us_ref:.0f};mode={mode}"))

    with open(JSON_PATH, "w") as f:
        json.dump({"backend": jax.default_backend(), "smoke": SMOKE,
                   "records": records}, f, indent=2)
    out.append(("sparse/engine_json", 0.0, f"path={JSON_PATH}"))
    return out
