"""The dense reference agrees with the trainer on tiny cells, with the
fused union kernel in interpret mode; the control and each fault the
one-chip cells can have turn ``correct`` false (the faults in
``test_bench_faults.py``)."""
import pytest

from tiny import SEED, run_tiny, tiny_cell

CELLS = ["sent140-lstm.engine-k64", "din-amazon.step-k128"]


@pytest.fixture
def kernel(monkeypatch):
    """``union_backend="auto"`` takes the fused kernel (interpret mode)."""
    from repro.sparse import aggregate
    real = aggregate._resolve_backend
    monkeypatch.setattr(aggregate, "_resolve_backend",
                        lambda b, *a: "pallas" if b == "auto" else real(b, *a))


@pytest.mark.parametrize("name", CELLS)
def test_reference_agrees_with_trainer(name, kernel):
    res, log = run_tiny(name)
    assert res["correct"], res["checks"]
    assert list(res["checks"]) == ["loss_gap", "update_gap", "change_gap"]
    assert list(res)[-1] == "checks"
    assert log.strip().splitlines()[-1].startswith("check change_gap ")
    want = {m["name"] for m in tiny_cell(name).end_to_end}
    assert set(res["metrics"]) == want
    assert "setup_s" in want and any(n.startswith("updates_per_s")
                                     for n in want)


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """A lower-precision reference in the program's place. On the chip the
    control runs under the TPU's ``high`` precision (``control.py``); a CPU
    ignores precision settings, so here every matmul's operands are
    rounded to bfloat16 (``precision.mm_bf16``)."""
    import jax

    from bench import check, harness
    from bench.precision import mm_bf16
    cell = tiny_cell(name)
    raw = harness.make_data(cell.config, SEED)
    s = harness.derived_seed(SEED)
    model = cell.model()
    init = jax.jit(lambda k: model.init_params(k, cell.config,
                                               raw["num_features"]))
    truth = harness.run_reference(cell, raw, s, init)
    ctl = harness.run_reference(cell, raw, s, init, mm=mm_bf16)
    ok, checks = check.judge(check.gaps(ctl, truth), cell.limits)
    assert not ok, checks


@pytest.mark.parametrize("name", CELLS)
def test_window_buckets_are_the_trainers(name):
    """Set-up predicts the sub-id bucket of every later call by replaying
    the trainer's cohort stream, and compiles each one first through the
    window's own call: the calls after it compile nothing, and the warm-up
    leaves the trainer where set-up had left it."""
    import jax
    import numpy as np

    from bench.harness import Setup
    from bench.timing import compile_log
    cell = tiny_cell(name)
    # a cohort this small meets a bucket after set-up's calls in both cells
    cell.traffic.update(clients=4, local_iters=1, local_batch=2)
    calls = cell.traffic["check_calls"] + 6
    cold = Setup(cell, SEED)
    with compile_log() as clog:
        for _ in range(6):
            cold.call()
            jax.block_until_ready(cold.tr.state)
    assert len({cap for _, cap in cold.plan(calls)}) > 1
    assert clog["compile_s"], "the tiny cell must meet a new bucket"
    want = jax.tree.leaves(cold.tr.state.params)
    cold.free()

    st = Setup(cell, SEED)
    st.warm(calls)
    with compile_log() as clog:
        for _ in range(6):
            st.call()
            jax.block_until_ready(st.tr.state)
    assert clog["compile_s"] == []
    for a, b in zip(jax.tree.leaves(st.tr.state.params), want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
