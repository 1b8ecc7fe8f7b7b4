"""Compile the main path's kernels and one sparse round step for a TPU v5e.

Nothing runs here: the TPU compiler, installed with JAX, compiles for a
``v5e:2x2`` topology that is described and not attached, and refuses what
the chip would refuse — a block off XLA's tiling, an op Mosaic cannot
lower, more VMEM than the core has. Interpret mode catches none of these.
The shapes are the ones ``chip_smoke.py`` runs: a 2^20-row table, 64
clients whose submodels bucket to 256 ids (16,384 stacked rows) and the
Sent140 LSTM's embedding width 25.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import pytest

import jax
import jax.numpy as jnp

V = 1 << 20
K = 64
R = 256
T = K * R
D = 25


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        # the TPU library logs under /tmp unless told otherwise
        mp.setitem(os.environ, "TPU_LOG_DIR",
                   os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # an executable for a described chip cannot be read back from the
        # persistent cache; keep these compiles out of it
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


#: ``union_segsum``'s ``vmem_footprint`` under its former vocabulary-blocked
#: form (grid ``(V/1024, T/1024)``), at V = 2^20 and D = 25, by stacked rows
#: T (the union capacity is T): the engine round (64 x 256) and the
#: four-chip ``union`` combine's second pass (4 x 8,192).
_SWEEP_FOOTPRINT = {T: 10_526_732, 4 * 8192: 12_230_668}


@pytest.mark.parametrize("t", [T, 4 * 8192])
@pytest.mark.parametrize("matmul_precision", ["default", "highest"])
def test_union_segsum_compiles_for_v5e(one_chip, matmul_precision, t):
    """The kernel states the precision of each of its matmuls, so a caller's
    ``jax.default_matmul_precision`` cannot hand Mosaic a contraction it
    refuses (an fp32 contraction of bf16 operands). Its guard prices no
    more than the vocabulary-blocked kernel's did at the same shape, so
    every union that compiled before still fits."""
    from repro.kernels.union_segsum import fits_vmem, union_segsum, vmem_footprint

    assert vmem_footprint(t, D, t=t) <= _SWEEP_FOOTPRINT[t]
    assert fits_vmem(t, D, t=t)

    def fn(ids, rows, heat):
        return union_segsum(ids, rows, heat, 128.0, t, scale=1.0 / K,
                            interpret=False)

    with jax.default_matmul_precision(matmul_precision):
        text = _compiled_text(fn, _sds((t,), jnp.int32, one_chip),
                              _sds((t, D), jnp.float32, one_chip),
                              _sds((V,), jnp.float32, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d", [D, 64])
def test_rowsparse_scatter_compiles_for_v5e(one_chip, d):
    from repro.kernels.heat_scatter import rowsparse_scatter

    def fn(ids, rows, heat):
        return rowsparse_scatter(ids, rows, heat, 128.0, V, scale=1.0 / K,
                                 interpret=False)

    text = _compiled_text(fn, _sds((T,), jnp.int32, one_chip),
                          _sds((T, d), jnp.float32, one_chip),
                          _sds((V,), jnp.float32, one_chip))
    assert "tpu_custom_call" in text


def test_sparse_replicated_round_step_compiles_for_v5e(one_chip,
                                                       monkeypatch):
    """The trainer's round: submodel-replica local steps, the row-sparse
    transport and the FedSubAvg server, on per-client sub-ids. With the
    backend steered to TPU, ``union_backend="auto"`` picks the fused kernel
    and the compiled round holds it."""
    import importlib

    from repro.configs import FedConfig
    from repro.core.algorithms import ServerState
    from repro.federated import build_round_step, plan_from_config
    from repro.models.recsys import lstm_loss, make_lstm_params

    # code that asks on_tpu() sees the CPU here; ops binds its own copy
    hs = importlib.import_module("repro.kernels.heat_scatter")
    ops = importlib.import_module("repro.kernels.ops")
    monkeypatch.setattr(hs, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)

    i, b, s = 4, 8, 24
    cfg = FedConfig(num_clients=128, clients_per_round=K, local_iters=i,
                    local_batch=b, lr=0.3, algorithm="fedsubavg",
                    sparse=True)
    plan = plan_from_config(cfg)
    assert plan.describe() == ("SubmodelReplicatedLocal -> "
                               "RowSparseTransport -> ServerUpdate(fedsubavg)")
    params = make_lstm_params(V, abstract=True)
    step = build_round_step(plan, lstm_loss, params, cfg, telemetry=True)
    state = ServerState(
        jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), params),
        (), _sds((), jnp.int32, one_chip))
    batch = {"tokens": _sds((K, i, b, s), jnp.int32, one_chip),
             "label": _sds((K, i, b), jnp.int32, one_chip),
             "sample_mask": _sds((K, i, b), jnp.float32, one_chip),
             "heat_vocab": _sds((V,), jnp.float32, one_chip)}
    sub_ids = _sds((K, R), jnp.int32, one_chip)
    assert "tpu_custom_call" in _compiled_text(step, state, batch, sub_ids)
