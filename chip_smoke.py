"""Run the sparse FedSubAvg round on a TPU and check what comes out.

The main path, as a user drives it: ``FederatedTrainer`` with
``FedConfig(sparse=True, algorithm="fedsubavg")``, which resolves to
submodel-replica local steps, the row-sparse transport and the FedSubAvg
server update, whose union segment-sum runs the fused ``union_segsum``
Pallas kernel on a TPU. The model is the paper's Sent140 LSTM at its
published widths (embedding 25, hidden 100, two layers) over a Sent140-like
corpus with a 2^20-token vocabulary and 128 clients, 64 per round, all made
from ``--seed``. A few rounds run through ``run_round`` and a few through
the ``run_rounds`` scan engine, timed. The reference is the same rounds, same
seed, with the jnp ``bitmap`` union backend, both run at "highest" matmul
precision (see ``PARITY_PRECISION``); parameters must agree to 1e-5.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # only the four-chip CohortSharding check

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, or without the repo's ``src/`` beside it, the script exits
non-zero and prints no result. Everything runs in this one process, which
holds the chip.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: the repo's sparse parity tolerance (max absolute parameter difference)
PARITY_TOL = 1e-5
#: matmul precision of every run compared against another. At the TPU's
#: default precision the LSTM's matmuls round their inputs to bf16, so the
#: one-ulp differences two summation orders leave in a table row can flip a
#: rounding and grow to a few 1e-5 within rounds; at "highest" the model's
#: own arithmetic stays f32 and the comparison sees the aggregation alone.
PARITY_PRECISION = "highest"
#: the Sent140 LSTM's published widths (``make_lstm_params`` defaults)
MODEL = {"emb_dim": 25, "hidden": 100, "layers": 2}
VOCAB = 1 << 20
CLIENTS = 128
COHORT = 64
#: a vocabulary whose dense (V, 25) f32 table (1.6 MB) is under the 2 MiB
#: budget below which cohort-sharded rounds combine with a psum
PSUM_VOCAB = 1 << 14


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require_tpu() -> dict:
    """The device as JAX reports it; exits non-zero when it is not a TPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); this check runs only on the chip")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def make_config(num_clients: int, cohort: int, seed: int):
    from repro.configs import FedConfig
    return FedConfig(num_clients=num_clients, clients_per_round=cohort,
                     local_iters=4, local_batch=8, lr=0.3,
                     algorithm="fedsubavg", sparse=True, seed=seed)


def make_data(vocab: int, num_clients: int, seed: int):
    from repro.data import make_sent140_like
    return make_sent140_like(num_clients=num_clients, vocab=vocab, seed=seed)


def make_trainer(ds, cfg, *, union_backend: str | None = None, mesh=None,
                 model: dict = MODEL):
    """The trainer a user builds from ``cfg``. ``union_backend`` replaces
    the transport's ``"auto"`` pick (the reference run, or the kernel in
    interpret mode off the chip); ``mesh`` shards the cohort over it."""
    from repro.federated import FederatedTrainer, plan_from_config
    from repro.models.recsys import lstm_loss, make_lstm_params
    plan = None
    if union_backend is not None:
        plan = plan_from_config(cfg, feature_keys=(ds.feature_key,))
        plan = dataclasses.replace(plan, transport=dataclasses.replace(
            plan.transport, union_backend=union_backend))
    return FederatedTrainer(
        ds, functools.partial(make_lstm_params, ds.num_features, **model),
        lstm_loss, cfg, plan=plan, mesh=mesh)


def auto_union_backend(tr) -> str:
    """What ``union_backend="auto"`` resolves to for the trainer's last
    round: ``(K, capacity)`` stacked sub-ids over its embedding table."""
    from repro.sharding.logical import unbox
    from repro.sparse.aggregate import _resolve_backend
    k = tr.cfg.clients_per_round
    t = k * tr._last_capacity
    row_elems = unbox(tr.state.params)["embedding"].shape[1]
    return _resolve_backend("auto", tr.ds.num_features,
                            min(tr.ds.num_features, t), row_elems, t)


def compiled_round_text(tr, seed: int) -> str:
    """HLO of the trainer's compiled round step at its last round's shapes.

    The inputs come from a generator of their own, so the trainer's cohort
    stream is untouched; only their shapes matter.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.data.batching import sample_cohort_batch
    from repro.federated import derive_sub_ids
    cfg, ds = tr.cfg, tr.ds
    rng = np.random.default_rng(seed)
    ids = rng.choice(ds.num_clients, size=cfg.clients_per_round,
                     replace=False)
    cohort = sample_cohort_batch(ds, ids, cfg.local_iters, cfg.local_batch,
                                 rng)
    feats = jnp.asarray(cohort[ds.feature_key].reshape(len(ids), -1))
    sub_ids = derive_sub_ids(feats, ds.num_features, tr._last_capacity)
    cohort = {k: jnp.asarray(v) for k, v in cohort.items()}
    return tr._sparse_step.lower(tr.state, cohort, sub_ids).compile().as_text()


def max_param_diff(a, b) -> float:
    import jax
    import numpy as np

    from repro.sharding.logical import unbox
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(unbox(a.state.params)),
                               jax.tree.leaves(unbox(b.state.params))))


@contextlib.contextmanager
def compile_log():
    """Seconds of each XLA compile (or persistent-cache load) and the
    number of persistent-cache hits while the context is open."""
    import jax
    log = {"compile_s": [], "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            log["compile_s"].append(secs)

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            log["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield log
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def timed(tr, call) -> dict:
    """Wall seconds of ``call(tr)`` until the server state is on the device,
    with the compiles it triggered and the sub-id capacity it ran at (a new
    capacity bucket compiles a new program)."""
    import jax
    with compile_log() as log:
        t0 = time.perf_counter()
        call(tr)
        jax.block_until_ready(tr.state)
        secs = time.perf_counter() - t0
    return {"s": secs, "compiles": len(log["compile_s"]),
            "compile_s": sum(log["compile_s"]),
            "cache_hits": log["cache_hits"], "capacity": tr._last_capacity}


def summarize(calls: list, rounds: int) -> dict:
    """First-call seconds, total compile seconds, and the steady seconds per
    round over the calls that compiled nothing (None if every call did)."""
    steady = [c["s"] / rounds for c in calls if c["compiles"] == 0]
    return {"first_call_s": calls[0]["s"],
            "compile_s": sum(c["compile_s"] for c in calls),
            "compiles": sum(c["compiles"] for c in calls),
            "cache_hits": sum(c["cache_hits"] for c in calls),
            "capacities": [c["capacity"] for c in calls],
            "steady_calls": len(steady),
            "steady_s_per_round": (sum(steady) / len(steady) if steady
                                   else None)}


def drive(trainers: dict, step_rounds: int, engine_rounds: int) -> dict:
    """``step_rounds`` ``run_round`` calls, then ``run_rounds(engine_rounds)``
    three times, on every trainer in lockstep.

    Returns, per trainer and driver, the :func:`summarize` timings and, for
    a pair, the max parameter difference between the two after each call.
    """
    drivers = (("run_round", 1, step_rounds, lambda t: t.run_round()),
               ("run_rounds", engine_rounds, 3,
                lambda t: t.run_rounds(engine_rounds)))
    out, diffs = {}, []
    for driver, rounds, n_calls, call in drivers:
        calls = {name: [] for name in trainers}
        for _ in range(n_calls):
            for name, tr in trainers.items():
                calls[name].append(timed(tr, call))
            if len(trainers) == 2:
                diffs.append(max_param_diff(*trainers.values()))
        for name, cs in calls.items():
            out[f"{name}_{driver}"] = summarize(cs, rounds)
    if len(trainers) == 2:
        out["max_param_diff_per_call"] = diffs
    return out


def check_parity(diff: float, what: str) -> None:
    if not diff <= PARITY_TOL:
        raise SmokeFailure(f"{what}: max parameter difference {diff!r} "
                           f"exceeds {PARITY_TOL}")


def one_chip(device: dict, *, vocab: int, clients: int, cohort: int,
             seed: int, step_rounds: int = 4, engine_rounds: int = 3,
             union_backend: str | None = None, model: dict = MODEL,
             expect_kernel: bool = True) -> dict:
    """The one-chip phases: data; the user's trainer through both drivers,
    timed, with its backend pick and compiled kernel checked; then the
    kernel-backed trainer against the bitmap reference at
    :data:`PARITY_PRECISION`. Returns the two phases' results."""
    import jax
    t0 = time.perf_counter()
    ds = make_data(vocab, clients, seed)
    report("data", vocab=vocab, clients=clients,
           setup_s=time.perf_counter() - t0)
    cfg = make_config(clients, cohort, seed)
    rounds = step_rounds + 3 * engine_rounds

    tr = make_trainer(ds, cfg, union_backend=union_backend, model=model)
    res = drive({"kernel": tr}, step_rounds, engine_rounds)
    res["auto_union_backend"] = auto_union_backend(tr)
    res["tpu_custom_call_in_round"] = (
        "tpu_custom_call" in compiled_round_text(tr, seed + 1))
    report("rounds", device=device["kind"], plan=tr.plan.describe(),
           vocab=vocab, cohort=cohort, capacity=tr._last_capacity,
           rounds=rounds, **res)
    if expect_kernel:
        if res["auto_union_backend"] != "pallas":
            raise SmokeFailure("union_backend='auto' resolved to "
                               f"{res['auto_union_backend']!r}, not pallas")
        if not res["tpu_custom_call_in_round"]:
            raise SmokeFailure("the compiled round holds no tpu_custom_call")

    with jax.default_matmul_precision(PARITY_PRECISION):
        pair = {"kernel": make_trainer(ds, cfg, union_backend=union_backend,
                                       model=model),
                "reference": make_trainer(ds, cfg, union_backend="bitmap",
                                          model=model)}
        par = drive(pair, step_rounds, engine_rounds)
    report("parity", device=device["kind"], precision=PARITY_PRECISION,
           rounds=rounds, **par)
    check_parity(max(par["max_param_diff_per_call"]),
                 "kernel vs bitmap reference")
    return {"rounds": res, "parity": par}


def sharded(device: dict, *, vocab: int, clients: int, cohort: int,
            seed: int, chips: int, expect_combine: str, rounds: int = 3,
            model: dict = MODEL) -> dict:
    """``run_rounds`` with the cohort sharded over ``chips`` devices against
    the same rounds on one device, at :data:`PARITY_PRECISION`;
    ``expect_combine`` is the cross-shard combine the vocabulary must
    select."""
    import jax

    from repro.launch.mesh import make_cohort_mesh
    from repro.sparse.aggregate import pick_combine
    combine = pick_combine(vocab, model["emb_dim"])
    if combine != expect_combine:
        raise SmokeFailure(f"V={vocab} selects the {combine!r} combine, "
                           f"not {expect_combine!r}")
    if len(jax.devices()) < chips:
        raise SmokeFailure(f"{chips} devices needed, "
                           f"{len(jax.devices())} found")
    ds = make_data(vocab, clients, seed)
    cfg = make_config(clients, cohort, seed)
    res = {}
    with jax.default_matmul_precision(PARITY_PRECISION):
        one = make_trainer(ds, cfg, model=model)
        many = make_trainer(ds, cfg, mesh=make_cohort_mesh(chips),
                            model=model)
        for name, tr in (("one_device", one), ("sharded", many)):
            res[name] = summarize([timed(tr, lambda t: t.run_rounds(rounds))
                                   for _ in range(2)], rounds)
    res["max_param_diff"] = max_param_diff(one, many)
    report(f"sharded_{combine}", device=device["kind"], chips=chips,
           precision=PARITY_PRECISION, plan=many.plan.describe(),
           vocab=vocab, cohort=cohort, capacity=many._last_capacity,
           rounds=2 * rounds, **res)
    check_parity(res["max_param_diff"],
                 f"{chips} devices ({combine} combine) vs one")
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip CohortSharding check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    device = require_tpu()
    from repro.common.compile_cache import use_compile_cache
    report("setup", device=device, compile_cache=use_compile_cache())
    if args.chips == 1:
        one_chip(device, vocab=VOCAB, clients=CLIENTS, cohort=COHORT,
                 seed=args.seed)
    else:
        for vocab, combine in ((VOCAB, "union"), (PSUM_VOCAB, "psum")):
            sharded(device, vocab=vocab, clients=CLIENTS, cohort=COHORT,
                    seed=args.seed, chips=4, expect_combine=combine)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
