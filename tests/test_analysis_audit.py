"""Compiled-artifact audits: no dense (V, D) intermediates on sparse
plans, donation actually aliases, and the jit cache never grows under
traced-hyperparameter sweeps."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_audit import (CompileCountError,
                                        DenseMaterializationError,
                                        assert_no_dense_intermediates,
                                        donation_aliased,
                                        find_dense_intermediates,
                                        jit_cache_guard)
from repro.configs.base import FedConfig
from repro.core.algorithms import ServerState
from repro.data import make_movielens_like
from repro.federated.plan import build_round_step, resolve_plan
from repro.federated.server import FederatedTrainer
from repro.federated.simulation import make_round_step
from repro.models.recsys import (lr_logits, lr_loss, lstm_loss,
                                 make_lr_params, make_lstm_params)
from repro.sparse.rowsparse import RowSparse

V, E = 65536, 4   # full-vocab scale: the audit traces, it never executes


@pytest.fixture(scope="module")
def params():
    return make_lstm_params(V, emb_dim=E, hidden=8, layers=1,
                            rng=jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def cfg():
    return FedConfig(num_clients=50, clients_per_round=4, lr=0.1,
                     server_lr=1.0, seed=0)


def _flat_batch():
    r = np.random.RandomState(0)
    return {"tokens": jnp.asarray(r.randint(0, V, (4, 8))),
            "label": jnp.asarray(r.randint(0, V, (4,))),
            "heat_vocab": jnp.ones((V,), jnp.float32)}


def _cohort_batch():
    r = np.random.RandomState(0)
    return {"tokens": jnp.asarray(r.randint(0, V, (3, 2, 2, 6))),
            "label": jnp.asarray(r.randint(0, V, (3, 2, 2))),
            "heat_vocab": jnp.ones((V,), jnp.float32)}


# ---------------------------------------------------------------------------
# dense-materialization detector
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,batch_fn", [("sparse", _flat_batch),
                                           ("sparse_replicated",
                                            _cohort_batch)])
def test_sparse_plans_have_no_dense_intermediates(params, cfg, mode,
                                                  batch_fn):
    """The paper's core claim, checked on the built artifact: a RowSparse
    round step never materialises a float (V, ...) array between the
    client gather and the server scatter-add."""
    step = make_round_step(lstm_loss, params, cfg, mode=mode)
    assert_no_dense_intermediates(step, params, batch_fn(), dim0=V)


def test_planted_densification_is_detected(params):
    """A pipeline that round-trips the delta through to_dense() must trip
    the detector (broadcast_in_dim of the (V, E) zeros)."""

    def bad_step(params, batch):
        toks = batch["tokens"].reshape(-1).astype(jnp.int32)
        ids = jnp.sort(toks)
        rows = jnp.ones((ids.shape[0], E), jnp.float32)
        dense = RowSparse(ids, rows, V).to_dense()       # the planted bug
        return params, dense.sum()

    with pytest.raises(DenseMaterializationError) as ei:
        assert_no_dense_intermediates(bad_step, params, _flat_batch(),
                                      dim0=V)
    assert any(h.shape == (V, E) for h in ei.value.hits)


def test_detector_ignores_int_id_workspaces():
    """O(V) int32/bool mark-scatter workspaces are the union machinery's
    accepted cost; only float row payloads count as densification."""

    def workspace(tokens):
        mark = jnp.zeros((V, 1), jnp.int32).at[tokens].add(1)
        return mark.sum()

    assert find_dense_intermediates(
        workspace, jnp.arange(8), dim0=V) == []


# ---------------------------------------------------------------------------
# donation aliasing
# ---------------------------------------------------------------------------


def test_round_step_donation_aliases(params, cfg):
    """The trainer donates ServerState through the sparse step; the lowered
    HLO must witness the aliasing (XLA drops impossible donations
    silently)."""
    plan = resolve_plan("sparse", cfg)
    step = build_round_step(plan, lstm_loss, params, cfg)
    state = ServerState(params, (), jnp.zeros((), jnp.int32))
    assert donation_aliased(step, state, _flat_batch(), donate_argnums=(0,))


def test_donation_aliased_negative():
    def f(x, y):
        return (x[:1] * y[:1]).sum()   # no output matches x's shape

    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")    # jax warns on the dropped buffer
        assert not donation_aliased(f, jnp.ones((8,)), jnp.ones((8,)),
                                    donate_argnums=(0,))


def test_donation_report_maps_buffers():
    """The report names which buffer aliased to which output."""
    rep = donation_aliased(lambda x: x * 2.0, jnp.ones((8,)),
                           donate_argnums=(0,))
    assert rep.aliasing == {0: 0}
    assert rep.num_donated == 1 and rep.dropped == 0


def test_donation_report_partially_dropped():
    """Regression for the substring-check blind spot: donate a 2-leaf tree
    where only one leaf is reusable.  The old `'tf.aliasing_output' in text`
    bool said True; the report must say one aliased, one dropped, and be
    falsy so asserts catch the partial drop."""
    def f(p):
        a, b = p
        return a * 2.0, jnp.sum(b)     # b's (4,) buffer has no (4,) output

    rep = donation_aliased(f, (jnp.ones((8,)), jnp.ones((4,))),
                           donate_argnums=(0,))
    assert rep.num_donated == 2
    assert rep.aliasing == {0: 0}      # only the (8,) leaf aliased
    assert rep.dropped == 1
    assert not rep


# ---------------------------------------------------------------------------
# jit_cache_guard
# ---------------------------------------------------------------------------


def test_cache_guard_passes_on_traced_sweep():
    j = jax.jit(lambda x, s: x * s)
    with jit_cache_guard(j):
        for s in (0.5, 1.5, 2.5):
            j(jnp.ones((4,)), s).block_until_ready()


def test_cache_guard_trips_on_recompiles():
    j = jax.jit(lambda x, n: x[:n], static_argnames=("n",))
    with pytest.raises(CompileCountError, match="compiled 2"):
        with jit_cache_guard(j, max_new_compiles=1):
            j(jnp.ones((8,)), 2).block_until_ready()
            j(jnp.ones((8,)), 3).block_until_ready()


def test_cache_guard_rejects_unjitted():
    with pytest.raises(TypeError, match="_cache_size"):
        with jit_cache_guard(lambda x: x):
            pass


def test_round_step_heat_sweep_compiles_once(params, cfg):
    """Heat is a traced batch input: scaling it (simulating popularity
    drift between rounds) must hit one compiled program."""
    step = jax.jit(make_round_step(lstm_loss, params, cfg, mode="sparse"))
    b = _flat_batch()
    with jit_cache_guard(step):
        for scale in (1.0, 2.0, 5.0, 0.25):
            bb = dict(b, heat_vocab=b["heat_vocab"] * scale)
            jax.block_until_ready(step(params, bb))


def test_trainer_engine_compiles_once_per_plan_shape():
    """The satellite pin: driving run_rounds repeatedly — int8 rounding key
    advancing with ServerState.rounds every round — compiles the engine
    exactly once per (n, capacity) dispatch variant."""
    ds = make_movielens_like(num_clients=40, num_items=40, mean_samples=15)
    cfg = FedConfig(num_clients=ds.num_clients, clients_per_round=6,
                    local_iters=2, local_batch=4, lr=0.5,
                    algorithm="fedsubavg", sparse=True, sparse_int8=True)
    tr = FederatedTrainer(
        ds, functools.partial(make_lr_params, ds.num_features), lr_loss, cfg,
        predict_fn=lambda p, t: lr_logits(p, jnp.asarray(t["features"])))
    engine_keys = set()
    for _ in range(3):
        tr.run_rounds(3)
        engine_keys.add((3, tr._last_capacity))
    assert tr._sparse_engine._cache_size() == len(engine_keys)
    # and re-driving the already-seen variants compiles nothing new
    with jit_cache_guard(tr._sparse_engine, max_new_compiles=0):
        tr.run_rounds(3)
        assert (3, tr._last_capacity) in engine_keys, \
            "new dispatch variant appeared; the guard below would be vacuous"
