"""The round's spans and counters (``repro.telemetry.spans``).

Host spans are read back from a real ``jax.profiler`` trace with
``ProfileData``, as an operator would; the counters are checked against
the spans and without a profiler; the device scopes are read from the
lowered round step's HLO, on one device and on a four-device cohort mesh
(in a process of its own, since the device count is fixed when JAX
starts).
"""
import functools
import glob
import json
import os
import subprocess
import sys
import textwrap

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import FedConfig
from repro.core.algorithms import ServerState
from repro.data import make_movielens_like
from repro.federated import (CohortSharding, FederatedTrainer, RoundPlan,
                             RowSparseTransport, ServerUpdate,
                             SubmodelReplicatedLocal)
from repro.federated.arrivals import ArrivalSim
from repro.federated.plan import build_round_step
from repro.launch.mesh import make_cohort_mesh
from repro.models.recsys import lr_loss, make_lr_params
from repro.sharding.logical import Param
from repro.telemetry import RoundTelemetry, counters, host_pull, span
from repro.telemetry.spans import (ACCOUNT, AGGREGATE, APPLY, CALL, DISPATCH,
                                   LOCAL, LOSS, SAMPLE, SUB_IDS, SYNC,
                                   TELEMETRY)

SCOPES = (LOCAL, AGGREGATE, APPLY, LOSS, TELEMETRY)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: driver -> (one call, cohorts drawn by it, rounds it runs)
DRIVERS = {
    "run_round": (lambda tr: tr.run_round(), 1, 1),
    "run_rounds": (lambda tr: tr.run_rounds(3), 3, 3),
    "run_async": (lambda tr: tr.run_async(ArrivalSim(num_rounds=3)), 3, 3),
}


@pytest.fixture(scope="module")
def ds():
    return make_movielens_like(num_clients=40, num_items=40, mean_samples=15)


def _trainer(ds):
    cfg = FedConfig(num_clients=ds.num_clients, clients_per_round=6,
                    local_iters=2, local_batch=4, lr=0.5,
                    algorithm="fedsubavg", sparse=True)
    return FederatedTrainer(ds, functools.partial(make_lr_params,
                                                  ds.num_features),
                            lr_loss, cfg, predict_fn=None)


def _profiled_spans(tmp_path, fn):
    """Run ``fn`` under ``jax.profiler``; the ``fedsub.*`` host spans of
    the trace as ``(name, start_ns, end_ns, args)``, in start order."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name.split("#", 1)[0]
                if name.startswith("fedsub."):
                    out.append((name, e.start_ns, e.end_ns, dict(e.stats)))
    return sorted(out, key=lambda x: x[1])


def _tel_fields(tr) -> list:
    """The telemetry fields the last round pulled (the non-``None`` ones)."""
    last = tr.telemetry_log[-1]
    return [f for f in RoundTelemetry._fields if last[f] is not None]


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_driver_spans_under_the_profiler(tmp_path, ds, driver):
    call, cohorts, rounds = DRIVERS[driver]
    tr = _trainer(ds)
    call(tr)                              # compile outside the trace
    first = tr._rounds_run + 1
    before = counters()["host_syncs"]
    spans = _profiled_spans(tmp_path, lambda: call(tr))
    pulls = counters()["host_syncs"] - before
    names = [s[0] for s in spans]

    calls = [s for s in spans if s[0] == CALL]
    assert len(calls) == 1
    _, c0, c1, args = calls[0]
    assert args["driver"] == driver
    assert int(args["first_round"]) == first and int(args["rounds"]) == rounds
    # every other span of the call lies inside it
    assert all(c0 <= s <= e <= c1 for n, s, e, _ in spans if n != CALL)
    assert names.count(SAMPLE) == cohorts
    for one in (SUB_IDS, DISPATCH, ACCOUNT):
        assert names.count(one) == 1, one
    fields = _tel_fields(tr)
    assert names.count(SYNC) == pulls == 2 + len(fields)
    whats = sorted(s[3]["what"] for s in spans if s[0] == SYNC)
    assert whats == sorted(["count", "loss"]
                           + [f"telemetry.{f}" for f in fields])


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_sync_counter_counts_without_a_profiler(ds, driver):
    call, _, _ = DRIVERS[driver]
    tr = _trainer(ds)
    call(tr)
    before = counters()["host_syncs"]
    call(tr)
    assert counters()["host_syncs"] - before == 2 + len(_tel_fields(tr))


def test_host_pull_returns_the_array_and_counts_once():
    x = jnp.arange(6, dtype=jnp.float32).reshape(2, 3)
    before = counters()["host_syncs"]
    got = host_pull(x, "test")
    assert isinstance(got, np.ndarray)
    np.testing.assert_array_equal(got, np.arange(6).reshape(2, 3))
    assert counters()["host_syncs"] == before + 1


def test_compile_counter_follows_jax_compiles():
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.ones((7, 5)).block_until_ready()
    before = counters()["compiles"]
    f(x).block_until_ready()
    assert counters()["compiles"] == before + 1
    f(x).block_until_ready()                         # cached: no compile
    assert counters()["compiles"] == before + 1


def test_span_carries_its_args(tmp_path):
    def one():
        with span(CALL, driver="x", first_round=4):
            pass

    spans = _profiled_spans(tmp_path, one)
    assert spans and spans[0][0] == CALL
    args = spans[0][3]
    assert args["driver"] == "x" and int(args["first_round"]) == 4


# ---------------------------------------------------------------------------
# device scopes of the round step
# ---------------------------------------------------------------------------

V, D, I, B, S = 32, 4, 2, 2, 6


def _params():
    rng = jax.random.PRNGKey(0)
    return {"emb": Param(jax.random.normal(rng, (V, D)) * 0.1,
                         ("vocab", "d")),
            "w": Param(jax.random.normal(jax.random.fold_in(rng, 1),
                                         (D,)) * 0.1, (None,))}


def _loss(params, batch):
    emb, w = params["emb"].value, params["w"].value
    x = jnp.take(emb, jnp.maximum(batch["tokens"], 0), axis=0).mean(axis=-2)
    return jnp.mean(((x @ w) - batch["label"]) ** 2)


def lowered_scopes(ndev: int) -> dict:
    """Which phase scopes the lowered FedSubAvg round step names: plain for
    ``ndev == 1``, else over a ``CohortSharding`` mesh of ``ndev``."""
    params = _params()
    k = max(ndev, 2)
    cfg = FedConfig(num_clients=16, clients_per_round=k, local_iters=I,
                    local_batch=B, lr=0.1, sparse=True)
    sharding = (CohortSharding(make_cohort_mesh(ndev)) if ndev > 1
                else None)
    plan = RoundPlan(SubmodelReplicatedLocal(), RowSparseTransport(),
                     ServerUpdate("fedsubavg"), sharding=sharding)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, V, (k, I, B, S)),
                                   jnp.int32),
             "label": jnp.asarray(rng.normal(size=(k, I, B))
                                  .astype(np.float32)),
             "heat_vocab": jnp.ones((V,), jnp.float32)}
    state = ServerState(params, (), jnp.zeros((), jnp.int32))
    step = build_round_step(plan, _loss, params, cfg, telemetry=True)
    text = jax.jit(step).lower(state, batch).as_text(debug_info=True)
    return {s: s in text for s in SCOPES}


@pytest.mark.parametrize("ndev", [1, 4], ids=["plain", "sharded4"])
def test_round_step_hlo_names_every_phase_scope(ndev):
    if ndev == 1:
        found = lowered_scopes(1)
    else:
        code = textwrap.dedent(f"""
            import json, sys
            sys.path[:0] = [{os.path.join(REPO, 'tests')!r},
                            {os.path.join(REPO, 'src')!r}]
            from test_spans import lowered_scopes
            print(json.dumps(lowered_scopes({ndev})))
        """)
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " "
                              f"--xla_force_host_platform_device_count={ndev}"))
        p = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-3000:]
        found = json.loads(p.stdout.strip().splitlines()[-1])
    assert found == {s: True for s in SCOPES}
