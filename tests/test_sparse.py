"""Sparse submodel update plane: representation, parity with the dense path,
kernels, compression, and the end-to-end sparse trainer/round-step modes.

Deliberately hypothesis-free (seeded sweeps) so the sparse plane keeps test
coverage even where hypothesis is not installed.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import FedConfig, get_smoke_config
from repro.core.aggregate import HeatSpec, correct_update_tree
from repro.data import make_amazon_like, make_movielens_like
from repro.federated import (FederatedTrainer, count_sub_ids, derive_sub_ids,
                             make_round_step, pow2_capacity, round_capacity)
from repro.kernels import ops, ref
from repro.models import build_model
from repro.models.recsys import (lr_logits, lr_loss, lstm_loss, make_lr_params,
                                 make_lstm_params)
from repro.sharding.logical import unbox
from repro.sparse import (RowSparse, aggregate_rowsparse, apply_rowsparse,
                          batch_union_ids, dequantize_rows, encode_delta_tree,
                          quantize_rows_int8, sparse_cohort_aggregate,
                          submodel_value_and_grad, topk_rows, tree_wire_bytes,
                          unique_ids_padded)


def _random_cohort(rng, k, v, d, max_rows):
    """Per-client supports incl. empty-ish clients; returns ids, dense deltas."""
    ids = np.full((k, max_rows), -1, np.int32)
    dense = np.zeros((k, v, d), np.float32)
    for i in range(k):
        n = int(rng.integers(1, max_rows + 1))
        sup = np.sort(rng.choice(v, size=n, replace=False))
        ids[i, :n] = sup
        dense[i, sup] = rng.normal(size=(n, d))
    return ids, dense


# ---------------------------------------------------------------------------
# representation
# ---------------------------------------------------------------------------


def test_rowsparse_roundtrip_and_jit(rng):
    v, d = 24, 3
    ids = jnp.asarray([1, 5, 7, -1, -1], jnp.int32)
    dense = jnp.asarray(rng.normal(size=(v, d)), jnp.float32)
    rs = RowSparse.from_dense(dense, ids)
    want = np.zeros((v, d), np.float32)
    for i in (1, 5, 7):
        want[i] = np.asarray(dense)[i]
    np.testing.assert_allclose(np.asarray(rs.to_dense()), want)
    # flows through jit/vmap as a pytree, aux data intact
    out = jax.jit(lambda r: r.scale(3.0))(rs)
    assert out.num_rows == v
    np.testing.assert_allclose(np.asarray(out.to_dense()), 3 * want, rtol=1e-6)
    stacked = jax.vmap(RowSparse.from_dense, in_axes=(None, 0))(
        dense, jnp.stack([ids, ids]))
    assert stacked.ids.shape == (2, 5)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_unique_ids_padded_matches_numpy(seed):
    rng = np.random.default_rng(seed)
    raw = rng.integers(-1, 40, size=64).astype(np.int32)
    cap = 48
    out = np.asarray(unique_ids_padded(jnp.asarray(raw), cap))
    want = np.unique(raw[raw >= 0])
    np.testing.assert_array_equal(out[: len(want)], want)
    assert np.all(out[len(want):] == -1)
    # capacity overflow drops the tail deterministically
    tight = np.asarray(unique_ids_padded(jnp.asarray(raw), 4))
    np.testing.assert_array_equal(tight, want[:4])


# ---------------------------------------------------------------------------
# sparse/dense aggregation parity (the ISSUE's property test)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
@pytest.mark.parametrize("union_backend", ["bitmap", "sort"])
def test_sparse_aggregation_matches_dense_correction(seed, union_backend):
    """Sparse encode + segment-sum + fused N/n_m == dense mean + correct,
    including cold rows (n_m = 0) and -1 padding ids."""
    rng = np.random.default_rng(seed)
    k, v, d = 5, 37, 3
    ids_np, dense = _random_cohort(rng, k, v, d, max_rows=11)
    heat = np.zeros(v, np.float64)
    for i in range(k):
        heat[ids_np[i][ids_np[i] >= 0]] += 1
    assert (heat == 0).any(), "want genuinely cold rows in this fixture"
    total = 20.0
    spec = HeatSpec({"emb": ("vocab", 0), "b": None})
    counts = {"vocab": jnp.asarray(heat, jnp.float32)}
    delta = {"emb": jnp.asarray(dense),
             "b": jnp.asarray(rng.normal(size=(k, 4)), jnp.float32)}

    enc = encode_delta_tree(delta, spec, jnp.asarray(ids_np))
    stacked = enc["emb"]
    agg = aggregate_rowsparse(stacked, counts["vocab"], total, 1.0 / k,
                              union_backend=union_backend)
    got = np.asarray(agg.to_dense())

    dense_mean = jax.tree.map(lambda x: x.mean(axis=0), delta)
    want = np.asarray(correct_update_tree(dense_mean, spec, counts, total)["emb"])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    # tree-level helper agrees too, and passes dense leaves through as means
    tree_agg = sparse_cohort_aggregate(enc, spec, counts, total, k)
    np.testing.assert_allclose(np.asarray(tree_agg["emb"].to_dense()), want,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(tree_agg["b"]),
                               np.asarray(dense_mean["b"]), rtol=1e-6)


def test_sparse_cohort_aggregate_corrects_trailing_axis_leaves(rng):
    """A vocab-spaced dense leaf (e.g. an LM head, vocab on axis 1) must get
    the same broadcast correction the dense server applies."""
    k, v, d = 3, 12, 4
    heat = np.array([0, 1, 2, 3, 0, 4, 1, 2, 3, 4, 1, 2], np.float64)
    spec = HeatSpec({"head": ("vocab", 1)})
    counts = {"vocab": jnp.asarray(heat, jnp.float32)}
    delta = {"head": jnp.asarray(rng.normal(size=(k, d, v)), jnp.float32)}
    agg = sparse_cohort_aggregate(delta, spec, counts, total=8.0,
                                  num_clients_in_cohort=k)
    dense_mean = jax.tree.map(lambda x: x.mean(axis=0), delta)
    want = correct_update_tree(dense_mean, spec, counts, 8.0)["head"]
    np.testing.assert_allclose(np.asarray(agg["head"]), np.asarray(want),
                               rtol=1e-5, atol=1e-7)


def test_apply_rowsparse_matches_dense_add(rng):
    v, d = 16, 2
    table = jnp.asarray(rng.normal(size=(v, d)), jnp.float32)
    ids = jnp.asarray([0, 3, 9, -1], jnp.int32)
    rows = jnp.asarray(rng.normal(size=(4, d)), jnp.float32)
    rows = rows * (np.asarray(ids) >= 0)[:, None]
    rs = RowSparse(ids, rows, v)
    got = apply_rowsparse(table, rs, 0.5)
    want = table + 0.5 * rs.to_dense()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


# ---------------------------------------------------------------------------
# generalized Pallas kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t,d,v,v_blk,t_blk", [
    (256, 8, 64, 16, 64),
    (500, 16, 96, 32, 128),      # non-multiple T exercises row padding
    (300, 8, 101, 32, 128),      # odd vocab exercises vocab padding
])
def test_rowsparse_scatter_kernel_vs_ref(rng, t, d, v, v_blk, t_blk):
    ids = jnp.asarray(rng.integers(-1, v, t), jnp.int32)
    rows = jnp.asarray(rng.normal(0, 1, (t, d)), jnp.float32)
    heat = jnp.asarray(rng.integers(0, 7, v), jnp.float32)
    out = ops.rowsparse_scatter(ids, rows, heat, 64.0, v, scale=0.125,
                                v_blk=v_blk, t_blk=t_blk)
    want = ref.rowsparse_scatter_ref(ids, rows, heat, 64.0, v, scale=0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_kernel_matches_sparse_aggregate(rng):
    """The Pallas dense-output path and the jnp union path agree."""
    k, v, d = 4, 64, 8
    ids_np, dense = _random_cohort(rng, k, v, d, max_rows=12)
    heat = jnp.asarray(np.maximum(rng.integers(0, 4, v), 0), jnp.float32)
    stacked = jax.vmap(RowSparse.from_dense)(jnp.asarray(dense),
                                             jnp.asarray(ids_np))
    from repro.sparse import aggregate_rowsparse_dense
    got_pl = aggregate_rowsparse_dense(stacked, heat, 32.0, scale=0.25,
                                       backend="pallas")
    got_jnp = aggregate_rowsparse_dense(stacked, heat, 32.0, scale=0.25,
                                        backend="jnp")
    np.testing.assert_allclose(np.asarray(got_pl), np.asarray(got_jnp),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# fused union_segsum kernel (the sparse server engine)
# ---------------------------------------------------------------------------


def _union_case(rng, case, v):
    """One cohort for the union kernel: ``(stacked, heat or None, cap)``.

    ``straddle`` draws 8 clients from 16 hot ids, so that sorted runs
    cross row-tile boundaries; ``over_cap`` sets the capacity 3 below the union size;
    ``all_pad`` holds all-pad clients over ``T = 65`` rows, not a multiple
    of any tile; ``big_ids`` draws ids above ``2^24``, where float32 is no
    longer exact; ``heat_none`` aggregates without the heat correction.
    """
    k, r, d = {"all_pad": (5, 13, 5), "straddle": (8, 12, 5)}.get(
        case, (4, 12, 5))
    base = (1 << 24) if case == "big_ids" else 0
    pool = np.arange(base, base + 16 if case == "straddle" else v)
    ids = np.full((k, r), -1, np.int32)
    for i in range(k):
        n = int(rng.integers(1, r + 1))
        ids[i, :n] = np.sort(rng.choice(pool, size=n, replace=False))
    if case == "all_pad":
        ids[[1, 3]] = -1
    rows = rng.normal(size=(k, r, d)).astype(np.float32)
    rows[ids < 0] = 0
    heat = np.zeros(v, np.float32)
    np.add.at(heat, ids[ids >= 0], 1.0)
    union_size = len(np.unique(ids[ids >= 0]))
    cap = union_size - 3 if case == "over_cap" else None
    stacked = RowSparse(jnp.asarray(ids), jnp.asarray(rows), v)
    return stacked, (None if case == "heat_none" else jnp.asarray(heat)), cap


def _straddles(ids, t_blk):
    """Whether some id's sorted run crosses a multiple of ``t_blk``."""
    flat = np.sort(ids[ids >= 0])
    starts = np.flatnonzero(np.r_[True, flat[1:] != flat[:-1]])
    ends = np.r_[starts[1:], len(flat)] - 1
    return bool(np.any(starts // t_blk != ends // t_blk))


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("v,t_blk,case", [
    (64, 16, "straddle"),
    (101, 32, "over_cap"),
    (37, 16, "all_pad"),
    ((1 << 24) + 48, 16, "big_ids"),
    (64, 8, "heat_none"),
])
def test_union_segsum_matches_jnp_backends(seed, v, t_blk, case):
    """The kernel's RowSparse output equals both jnp backends', at the
    default row tile and at a small one that splits the cohort."""
    from repro.kernels.union_segsum import union_segsum
    rng = np.random.default_rng(seed)
    stacked, heat, cap = _union_case(rng, case, v)
    ids_np = np.asarray(stacked.ids)
    if case == "straddle":
        assert _straddles(ids_np, t_blk)
    if case == "big_ids":
        assert ids_np.max() > (1 << 24) + 1
    total, scale = 24.0, 0.25
    want = {b: aggregate_rowsparse(stacked, heat, total, scale,
                                   union_capacity=cap, union_backend=b)
            for b in ("bitmap", "sort")}
    got = aggregate_rowsparse(stacked, heat, total, scale,
                              union_capacity=cap, union_backend="pallas")
    if case == "over_cap":
        assert int((got.ids >= 0).sum()) == got.capacity
    for b, w in want.items():
        np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(w.ids),
                                      err_msg=b)
        np.testing.assert_allclose(np.asarray(got.rows), np.asarray(w.rows),
                                   rtol=1e-5, atol=1e-5, err_msg=b)
    # direct kernel call with an explicit small row tile agrees too
    u_ids, u_rows = union_segsum(stacked.ids, stacked.rows, heat, total,
                                 got.capacity, scale=scale, t_blk=t_blk)
    np.testing.assert_array_equal(np.asarray(u_ids), np.asarray(got.ids))
    np.testing.assert_allclose(np.asarray(u_rows), np.asarray(got.rows),
                               rtol=1e-5, atol=1e-5)


def test_union_segsum_all_pad_clients_and_exact_cap(rng):
    """All-pad clients contribute nothing; cap == union size exactly fills
    every slot; cap < union drops the largest ids (sort-backend semantics)."""
    v, d = 40, 3
    ids = np.array([[3, 7, 11, -1], [-1, -1, -1, -1], [7, 20, -1, -1]],
                   np.int32)
    rows = rng.normal(size=(3, 4, d)).astype(np.float32)
    rows[ids < 0] = 0
    heat = jnp.asarray(rng.integers(1, 5, v), jnp.float32)
    stacked = RowSparse(jnp.asarray(ids), jnp.asarray(rows), v)
    union = {3, 7, 11, 20}
    for cap in (len(union), len(union) - 1, len(union) + 3):
        got = aggregate_rowsparse(stacked, heat, 10.0, 0.5,
                                  union_capacity=cap, union_backend="pallas")
        want = aggregate_rowsparse(stacked, heat, 10.0, 0.5,
                                   union_capacity=cap, union_backend="sort")
        np.testing.assert_array_equal(np.asarray(got.ids),
                                      np.asarray(want.ids))
        np.testing.assert_allclose(np.asarray(got.to_dense()),
                                   np.asarray(want.to_dense()),
                                   rtol=1e-5, atol=1e-6)
    lone = aggregate_rowsparse(
        RowSparse(jnp.asarray(ids[1:2]), jnp.asarray(rows[1:2]), v), heat,
        10.0, 1.0, union_backend="pallas")
    assert int((lone.ids >= 0).sum()) == 0
    np.testing.assert_array_equal(np.asarray(lone.to_dense()), 0)


def test_union_backend_auto_selection(monkeypatch):
    """'auto' resolves to a jnp backend off-TPU and to the fused kernel on
    TPU whenever the union fits VMEM (interpret vs compiled selection)."""
    import importlib
    hs_mod = importlib.import_module("repro.kernels.heat_scatter")
    from repro.sparse import aggregate as agg_mod
    assert agg_mod._resolve_backend("auto", 1000, 64, 8, 256) in ("bitmap",
                                                                  "sort")
    assert agg_mod._resolve_backend("pallas", 1000, 64, 8, 256) == "pallas"
    monkeypatch.setattr(hs_mod, "on_tpu", lambda: True)
    assert agg_mod._resolve_backend("auto", 1000, 64, 8, 256) == "pallas"
    # beyond the VMEM budget auto falls back to the jnp backends
    assert agg_mod._resolve_backend(
        "auto", 1 << 23, 1 << 22, 64, 1 << 22) == "sort"
    # huge feature spaces never auto-select the kernel (grid scales with V),
    # even when the union itself would fit VMEM
    assert agg_mod._resolve_backend(
        "auto", (1 << 22) + 1, 64, 8, 256) == "sort"
    # the kernel wrapper keys interpret mode off the same runtime check
    us_mod = importlib.import_module("repro.kernels.union_segsum")
    assert us_mod.fits_vmem(64, 8) and not us_mod.fits_vmem(1 << 22, 64)


def test_fits_vmem_uses_actual_block_sizes():
    """Regression: the budget guard prices the row tile the kernel runs
    with. A tile is clamped to the rows rounded up to the 1-D tile (a 4096
    tile over 64 rows runs as 1024), and never shrunk below the tile — a
    small cohort is padded up to the tile instead, so the tile keeps
    matching XLA's 1-D tiling on TPU. No term depends on the vocabulary."""
    from repro.kernels.union_segsum import (TILE_1D, _block_sizes, fits_vmem,
                                            union_segsum, vmem_footprint)
    cap, d = 1024, 64
    assert _block_sizes(64, 4096) == TILE_1D
    assert not fits_vmem(cap, d, t_blk=4096)
    assert fits_vmem(cap, d, t=64, t_blk=4096)
    # the default tile is the 1-D tile: never shrunk at small T
    assert _block_sizes(64, TILE_1D) == TILE_1D
    assert vmem_footprint(cap, d, t=64) == vmem_footprint(cap, d)
    # large extents keep the requested tile
    assert _block_sizes(1 << 15, 2048) == 2048
    # the one-hot is (t_blk, t_blk): the guard grows with the tile squared
    assert (vmem_footprint(cap, d, t=1 << 15, t_blk=2048)
            - vmem_footprint(cap, d, t=1 << 15)) > 3 * TILE_1D * TILE_1D * 4
    # tiles below the 1-D tile run in interpret mode only: the compiled
    # path refuses them instead of handing Mosaic a mismatched layout
    with pytest.raises(ValueError, match="1-D tile"):
        union_segsum(jnp.zeros((8,), jnp.int32), jnp.zeros((8, 2), jnp.float32),
                     None, 1.0, 8, t_blk=512, interpret=False)


def test_union_segsum_grid_dims_sequential(monkeypatch):
    """Regression: the one grid dim of union_segsum is order-dependent (a
    segment that straddles two row tiles adds into the resident output
    across them), so the compiled path must never declare it 'parallel' —
    reusing heat_scatter's vocab-parallel default would corrupt the sums on
    Megacore TPUs. The grid follows the rows, not the vocabulary."""
    import importlib
    hs_mod = importlib.import_module("repro.kernels.heat_scatter")
    us_mod = importlib.import_module("repro.kernels.union_segsum")
    assert us_mod._DIM_SEMANTICS == ("arbitrary",)
    cp = hs_mod._tpu_compiler_params(semantics=us_mod._DIM_SEMANTICS)
    assert isinstance(cp, us_mod.pltpu.CompilerParams)
    assert tuple(cp.dimension_semantics) == ("arbitrary",)
    # heat_scatter's own default (independent vocab blocks) is unchanged
    cp_hs = hs_mod._tpu_compiler_params()
    assert tuple(cp_hs.dimension_semantics) == ("parallel", "arbitrary")

    # and the compiled path actually requests those semantics: capture what
    # union_segsum hands to _tpu_compiler_params on interpret=False (the
    # kernel itself still executes via the interpreter on CPU)
    seen = {}

    real_params = us_mod._tpu_compiler_params

    def fake_params(semantics=("parallel", "arbitrary")):
        seen["semantics"] = tuple(semantics)
        return real_params(semantics=semantics)

    real_call = us_mod.pl.pallas_call

    def interpreted_call(*args, **kw):
        seen["interpret"] = kw.get("interpret")
        seen["compiler_params"] = kw.pop("compiler_params", None)
        seen.setdefault("grids", []).append(kw["grid"])
        kw["interpret"] = True
        return real_call(*args, **kw)

    monkeypatch.setattr(us_mod, "_tpu_compiler_params", fake_params)
    monkeypatch.setattr(us_mod.pl, "pallas_call", interpreted_call)
    ids = jnp.asarray([[0, 2, -1]], jnp.int32)
    rows = jnp.ones((1, 3, 4), jnp.float32)
    u, _ = us_mod.union_segsum(ids, rows, None, 4.0, 4, interpret=False)
    assert seen["interpret"] is False
    assert seen["semantics"] == us_mod._DIM_SEMANTICS
    assert tuple(seen["compiler_params"].dimension_semantics) == \
        us_mod._DIM_SEMANTICS
    assert sorted(np.asarray(u)[np.asarray(u) >= 0].tolist()) == [0, 2]
    # the grid follows the stacked rows: 2,500 of them run 3 row tiles
    many = jnp.arange(2500, dtype=jnp.int32) % 97
    us_mod.union_segsum(many, jnp.ones((2500, 4), jnp.float32), None, 4.0,
                        97, interpret=False)
    assert seen["grids"] == [(1,), (3,)]


def test_union_segsum_scalar_params_do_not_retrace(rng):
    """total/scale are traced scalar operands of the jitted kernel wrapper:
    sweeping them hits one compiled program (no per-value retrace) while
    still scaling the output."""
    from repro.kernels import ops
    v, d = 32, 4
    ids = jnp.asarray([[1, 5, 9, -1]], jnp.int32)
    rows = jnp.asarray(rng.normal(size=(1, 4, d)), jnp.float32)
    heat = jnp.ones((v,), jnp.float32)
    before = ops.union_segsum._cache_size()
    outs = [ops.union_segsum(ids, rows, heat, total, 8, scale=scale)
            for total, scale in ((2.0, 1.0), (4.0, 1.0), (4.0, 0.5))]
    assert ops.union_segsum._cache_size() - before <= 1
    r0, r1, r2 = (np.asarray(r) for _, r in outs)
    np.testing.assert_allclose(r1, 2 * r0, rtol=1e-6)
    np.testing.assert_allclose(r2, r0, rtol=1e-6)


# ---------------------------------------------------------------------------
# jitted sub-id derivation (server engine preprocessing)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_derive_sub_ids_matches_numpy_path(seed):
    """The jitted bitmap-rank derivation reproduces the old host-side
    per-client np.unique loop exactly (ids, padding, and counts)."""
    rng = np.random.default_rng(seed)
    k, m, v = 6, 40, 57
    feats = rng.integers(-1, v, (k, m)).astype(np.int32)
    feats[2] = -1                                    # an all-pad client
    counts = np.asarray(count_sub_ids(jnp.asarray(feats), v))
    capacity = pow2_capacity(int(counts.max()))
    got = np.asarray(derive_sub_ids(jnp.asarray(feats), v, capacity))
    for c in range(k):
        u = np.unique(feats[c])
        u = u[u >= 0]
        assert counts[c] == len(u)
        np.testing.assert_array_equal(got[c, : len(u)], u)
        assert np.all(got[c, len(u):] == -1)


def test_pow2_capacity_invariant():
    """Regression: capacities are pure powers of two (>= 8) so the jitted
    round step compiles O(log V) variants — the old trainer clamped the
    bucket to a non-pow2 table size, breaking the ladder."""
    assert pow2_capacity(0) == 8 and pow2_capacity(8) == 8
    for n in (3, 9, 70, 100, 1000):
        cap = pow2_capacity(n)
        assert cap >= max(n, 8) and (cap & (cap - 1)) == 0
    # the broken variant: min(pow2, V) with V=100 gave 100 for counts > 64
    assert pow2_capacity(70) == 128


def test_round_capacity_clamped_to_vocab():
    """Regression: rounding the union capacity up to a multiple of 8 must
    never allocate slots past the feature table (e.g. V=50257 -> 50264)."""
    assert round_capacity(50257, 10 ** 9) == 50257
    assert round_capacity(101, 1000) == 101
    cap = round_capacity(101, 50)
    assert cap == 56 and cap % 8 == 0          # rounding still applies
    assert round_capacity(8, 3) == 8


def test_simulation_sparse_mode_odd_vocab_runs():
    """End-to-end regression companion: a vocab that is not a multiple of 8
    with a batch large enough to trigger the clamp still runs exactly."""
    from repro.models.recsys import lstm_loss, make_lstm_params
    v = 41
    params = make_lstm_params(v, emb_dim=6, hidden=8, layers=1,
                              rng=jax.random.PRNGKey(1))
    fed = FedConfig(num_clients=16, clients_per_round=4, lr=0.1,
                    algorithm="fedsubavg")
    step = make_round_step(lstm_loss, params, fed, mode="sparse")
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, v, (8, 16)), jnp.int32),
             "label": jnp.asarray(rng.integers(0, 2, 8), jnp.int32),
             "heat_vocab": jnp.full((v,), 4.0)}
    new_params, metrics = jax.jit(step)(params, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["density"]) <= 1.0


# ---------------------------------------------------------------------------
# gather-before-backward encoder
# ---------------------------------------------------------------------------


def test_submodel_grads_match_dense_grads_lr(rng):
    v = 50
    params = make_lr_params(v, rng=jax.random.PRNGKey(0))
    params["w"].value = jnp.asarray(rng.normal(size=(v, 1)), jnp.float32)
    batch = {"features": jnp.asarray(rng.integers(-1, v, (6, 5)), jnp.int32),
             "label": jnp.asarray(rng.integers(0, 2, 6), jnp.int32)}
    ids = batch_union_ids(batch, ("features",), 32)
    loss_s, grads = submodel_value_and_grad(lr_loss, params, batch,
                                            ("w",), ("features",), ids)
    loss_d, dense_grads = jax.value_and_grad(lr_loss)(params, batch)
    np.testing.assert_allclose(float(loss_s), float(loss_d), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(grads["w"].to_dense()),
                               np.asarray(unbox(dense_grads)["w"]),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(unbox(grads["b"])),
                               np.asarray(unbox(dense_grads)["b"]), rtol=1e-6)


def test_submodel_grads_match_dense_grads_lstm(rng):
    v = 40
    params = make_lstm_params(v, emb_dim=6, hidden=8, layers=1,
                              rng=jax.random.PRNGKey(1))
    batch = {"tokens": jnp.asarray(rng.integers(-1, v, (4, 7)), jnp.int32),
             "label": jnp.asarray(rng.integers(0, 2, 4), jnp.int32)}
    ids = batch_union_ids(batch, ("tokens",), 32)
    loss_s, grads = submodel_value_and_grad(lstm_loss, params, batch,
                                            ("embedding",), ("tokens",), ids)
    loss_d, dense_grads = jax.value_and_grad(lstm_loss)(params, batch)
    np.testing.assert_allclose(float(loss_s), float(loss_d), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(grads["embedding"].to_dense()),
                               np.asarray(unbox(dense_grads)["embedding"]),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------


def test_topk_rows_keeps_largest(rng):
    v, r, d = 30, 8, 2
    ids = jnp.asarray([2, 4, 6, 8, 10, -1, -1, -1], jnp.int32)
    rows = np.zeros((r, d), np.float32)
    rows[:5] = rng.normal(size=(5, d))
    rs = RowSparse(ids, jnp.asarray(rows), v)
    out = topk_rows(rs, 3)
    norms = (rows ** 2).sum(-1)[:5]
    want_ids = np.sort(np.asarray(ids)[:5][np.argsort(norms)[-3:]])
    np.testing.assert_array_equal(np.asarray(out.ids), want_ids)
    # fewer valid rows than k -> padding survives as padding
    out2 = topk_rows(RowSparse(ids, jnp.asarray(rows), v), 7)
    assert int((out2.ids >= 0).sum()) == 5


def test_int8_stochastic_rounding_unbiased(rng):
    v, r, d = 20, 6, 4
    ids = jnp.asarray([1, 3, 5, 7, 9, -1], jnp.int32)
    rows = jnp.asarray(rng.normal(size=(r, d)), jnp.float32)
    rows = rows * (np.asarray(ids) >= 0)[:, None]
    rs = RowSparse(ids, rows, v)
    keys = jax.random.split(jax.random.PRNGKey(0), 400)
    dq = jax.vmap(lambda k: dequantize_rows(quantize_rows_int8(rs, k)).rows)(keys)
    mean = np.asarray(dq.mean(axis=0))
    scales = np.abs(np.asarray(rows)).max(-1, keepdims=True) / 127.0
    # unbiased: the Monte-Carlo mean approaches the true rows
    np.testing.assert_allclose(mean, np.asarray(rows),
                               atol=3 * float(scales.max()) / np.sqrt(400) * 4)
    # single-shot error bounded by one quantisation step
    one = np.asarray(dequantize_rows(quantize_rows_int8(rs, keys[0])).rows)
    assert np.all(np.abs(one - np.asarray(rows)) <= np.maximum(scales, 1e-6) + 1e-6)


# ---------------------------------------------------------------------------
# end-to-end: FederatedTrainer sparse mode == dense mode
# ---------------------------------------------------------------------------


def _make_trainer(ds, sparse, alg="fedsubavg", **kw):
    cfg = FedConfig(num_clients=ds.num_clients, clients_per_round=6,
                    local_iters=3, local_batch=4, lr=0.5, algorithm=alg,
                    sparse=sparse, **kw)
    mk = functools.partial(make_lr_params, ds.num_features)
    return FederatedTrainer(
        ds, mk, lr_loss, cfg,
        predict_fn=lambda p, t: lr_logits(p, jnp.asarray(t["features"])),
        metric="auc")


@pytest.fixture(scope="module")
def small_ds():
    return make_movielens_like(num_clients=40, num_items=40, mean_samples=15)


def test_trainer_sparse_matches_dense(small_ds):
    td = _make_trainer(small_ds, sparse=False)
    ts = _make_trainer(small_ds, sparse=True)
    losses_d = [td.run_round() for _ in range(8)]
    losses_s = [ts.run_round() for _ in range(8)]
    np.testing.assert_allclose(losses_s, losses_d, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(unbox(td.state.params)),
                    jax.tree.leaves(unbox(ts.state.params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_trainer_comm_accounting(small_ds):
    ts = _make_trainer(small_ds, sparse=True)
    ts.run(4, eval_every=4)
    assert len(ts.comm_log) == 4
    s = ts.comm_summary()
    assert 0 < s["mean_density"] < 1
    assert s["bytes_up_sparse"] < s["bytes_up_dense"]
    assert s["up_ratio"] > 1
    rec = ts.history[-1]
    assert rec.bytes_up > 0 and rec.density == pytest.approx(s["mean_density"])


def test_trainer_sparse_din_includes_targets():
    """DIN deltas are supported on hist AND target ids; parity must hold."""
    ds = make_amazon_like(num_clients=30, num_items=60, mean_samples=12)
    from repro.models.recsys import din_logits, din_loss, make_din_params
    def mk(sparse):
        cfg = FedConfig(num_clients=ds.num_clients, clients_per_round=5,
                        local_iters=2, local_batch=4, lr=0.3,
                        algorithm="fedsubavg", sparse=sparse)
        return FederatedTrainer(
            ds, functools.partial(make_din_params, ds.num_features), din_loss,
            cfg, predict_fn=lambda p, t: din_logits(p, jnp.asarray(t["hist"]),
                                                    jnp.asarray(t["target"])))
    ld = [mk(False).run_round() for _ in range(1)]
    td, ts = mk(False), mk(True)
    losses_d = [td.run_round() for _ in range(4)]
    losses_s = [ts.run_round() for _ in range(4)]
    np.testing.assert_allclose(losses_s, losses_d, rtol=1e-5, atol=1e-6)


def test_trainer_run_rounds_matches_run_round(small_ds):
    """The in-jit multi-round engine (one lax.scan) reproduces the per-round
    loop: same RNG stream, same losses, same parameters, same comm log."""
    tr_loop = _make_trainer(small_ds, sparse=True)
    tr_scan = _make_trainer(small_ds, sparse=True)
    losses_loop = [tr_loop.run_round() for _ in range(6)]
    losses_scan = tr_scan.run_rounds(6)
    np.testing.assert_allclose(losses_scan, losses_loop, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(unbox(tr_loop.state.params)),
                    jax.tree.leaves(unbox(tr_scan.state.params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    assert len(tr_scan.comm_log) == len(tr_loop.comm_log) == 6
    for cl, cs in zip(tr_loop.comm_log, tr_scan.comm_log):
        assert cs.bytes_up_sparse == pytest.approx(cl.bytes_up_sparse)
    # run(engine=True) drives the same engine and surfaces wall time
    tr_eng = _make_trainer(small_ds, sparse=True)
    tr_eng.run(4, eval_every=2, engine=True)
    assert tr_eng.history[-1].round == 4
    assert tr_eng.history[-1].wall_time > 0
    # engine composes with the compression variants
    tr_c = _make_trainer(small_ds, sparse=True, sparse_topk=6, sparse_int8=True)
    assert np.all(np.isfinite(tr_c.run_rounds(3)))


def test_trainer_run_rounds_dense_fallback(small_ds):
    """Non-sparse configs fall back to the per-round loop transparently."""
    tr = _make_trainer(small_ds, sparse=False)
    losses = tr.run_rounds(2)
    assert len(losses) == 2 and np.all(np.isfinite(losses))
    assert tr._rounds_run == 2


def test_trainer_sparse_compression_variants_run(small_ds):
    for kw in (dict(sparse_topk=6), dict(sparse_int8=True)):
        tr = _make_trainer(small_ds, sparse=True, **kw)
        losses = [tr.run_round() for _ in range(3)]
        assert np.all(np.isfinite(losses))


# ---------------------------------------------------------------------------
# sparse_replicated local mode (submodel replicas in the trainer)
# ---------------------------------------------------------------------------


def test_trainer_sparse_local_auto_resolves_to_submodel(small_ds):
    """With axis-0 feature tables spanning the dataset id space, "auto" picks
    gathered submodel replicas; forcing dense replicas still works."""
    tr = _make_trainer(small_ds, sparse=True)
    assert tr._sparse_local == "sparse_replicated"
    assert tr._sparse_paths == [("w",)]
    tr_dense = _make_trainer(small_ds, sparse=True, sparse_local="replicated")
    assert tr_dense._sparse_local == "replicated"
    with pytest.raises(ValueError, match="sparse_local"):
        _make_trainer(small_ds, sparse=True, sparse_local="bogus")


@pytest.mark.parametrize("alg", ["fedsubavg", "fedavg", "fedprox", "fedadam"])
def test_trainer_submodel_replicas_match_dense_replicas(small_ds, alg):
    """The gathered-submodel local trainer reproduces dense-replica local
    training to 1e-5 over a multi-round run (same RNG stream) for the sparse
    apply path AND the densify-at-boundary server optimizers."""
    tr_sub = _make_trainer(small_ds, sparse=True, alg=alg)
    tr_rep = _make_trainer(small_ds, sparse=True, alg=alg,
                           sparse_local="replicated")
    losses_sub = [tr_sub.run_round() for _ in range(5)]
    losses_rep = [tr_rep.run_round() for _ in range(5)]
    np.testing.assert_allclose(losses_sub, losses_rep, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(unbox(tr_sub.state.params)),
                    jax.tree.leaves(unbox(tr_rep.state.params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_trainer_submodel_engine_matches_loop(small_ds):
    """run_rounds (one lax.scan) on the submodel path == per-round loop."""
    tr_loop = _make_trainer(small_ds, sparse=True)
    tr_scan = _make_trainer(small_ds, sparse=True)
    losses_loop = [tr_loop.run_round() for _ in range(5)]
    losses_scan = tr_scan.run_rounds(5)
    np.testing.assert_allclose(losses_scan, losses_loop, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(unbox(tr_loop.state.params)),
                    jax.tree.leaves(unbox(tr_scan.state.params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_submodel_local_trainer_emits_rowsparse_at_capacity():
    """Deltas come out of local training already RowSparse on the client's
    sub_ids — (K, capacity) ids, (K, capacity, D) rows; no dense (K, V, D)."""
    from repro.federated import (cohort_submodel_deltas, derive_sub_ids,
                                 make_submodel_local_trainer, pow2_capacity)
    from repro.models.recsys import lstm_loss, make_lstm_params
    v, e, k, i, b, s = 64, 4, 3, 2, 2, 5
    params = make_lstm_params(v, emb_dim=e, hidden=6, layers=1,
                              rng=jax.random.PRNGKey(0))
    cfg = FedConfig(num_clients=8, clients_per_round=k, local_iters=i, lr=0.2)
    rng = np.random.default_rng(3)
    tokens = rng.integers(-1, v, (k, i, b, s)).astype(np.int32)
    batch = {"tokens": jnp.asarray(tokens),
             "label": jnp.asarray(rng.integers(0, 2, (k, i, b)), jnp.int32)}
    counts = np.asarray(count_sub_ids(jnp.asarray(tokens.reshape(k, -1)), v))
    capacity = pow2_capacity(int(counts.max()))
    sub_ids = derive_sub_ids(jnp.asarray(tokens.reshape(k, -1)), v, capacity)
    local = make_submodel_local_trainer(lstm_loss, cfg, [("embedding",)],
                                        ("tokens",))
    deltas = jax.jit(cohort_submodel_deltas, static_argnums=0)(
        local, params, batch, sub_ids)
    rs = deltas["embedding"]
    assert rs.ids.shape == (k, capacity)
    assert rs.rows.shape == (k, capacity, e)
    assert rs.num_rows == v
    # padding rows are exactly zero; support matches the client's sub_ids
    ids_np, rows_np = np.asarray(rs.ids), np.asarray(rs.rows)
    np.testing.assert_array_equal(ids_np, np.asarray(sub_ids))
    assert np.all(rows_np[ids_np < 0] == 0)
    assert np.any(rows_np[ids_np >= 0] != 0)


# ---------------------------------------------------------------------------
# satellite regressions: int8 keys, comm pricing, run() bookkeeping
# ---------------------------------------------------------------------------


def test_quantize_tree_int8_independent_per_leaf(rng):
    """Regression: two feature tables in one round must draw INDEPENDENT
    stochastic-rounding noise — the old server path reused one key for every
    tree leaf, correlating the quantization errors across tables."""
    from repro.sparse import quantize_tree_int8
    v, r, d = 30, 6, 4
    ids = jnp.asarray([0, 4, 8, 12, 16, -1], jnp.int32)
    rows = jnp.asarray(rng.normal(size=(r, d)), jnp.float32)
    rows = rows * (np.asarray(ids) >= 0)[:, None]
    rs = RowSparse(ids, rows, v)
    tree = {"a": rs, "b": RowSparse(ids, rows, v), "dense": jnp.ones((3,))}
    out = quantize_tree_int8(tree, jax.random.PRNGKey(0))
    # identical inputs, different leaves -> different rounding noise
    assert not np.array_equal(np.asarray(out["a"].q), np.asarray(out["b"].q))
    # dense leaves pass through untouched; same tree+key is deterministic
    np.testing.assert_array_equal(np.asarray(out["dense"]), np.ones(3))
    out2 = quantize_tree_int8(tree, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(out["a"].q), np.asarray(out2["a"].q))
    # both leaves still dequantize to within one quantization step
    from repro.sparse import dequantize_rows
    for k in ("a", "b"):
        dq = np.asarray(dequantize_rows(out[k]).rows)
        scales = np.abs(np.asarray(rows)).max(-1, keepdims=True) / 127.0
        assert np.all(np.abs(dq - np.asarray(rows))
                      <= np.maximum(scales, 1e-6) + 1e-6)


def test_trainer_int8_two_tables_draw_independent_noise():
    """End-to-end regression (fails pre-fix): a model with two identical
    feature tables receiving identical deltas must end the round with
    DIFFERENT tables under sparse_int8 — correlated rounding noise would
    keep them bit-identical forever."""
    from repro.sharding.logical import Param
    ds = make_movielens_like(num_clients=30, num_items=32, mean_samples=12)

    def mk(rng):
        w = 0.01 * jax.random.normal(rng, (ds.num_features, 2), jnp.float32)
        # equal values, distinct buffers (donation rejects aliased leaves)
        return {"wa": Param(w, ("vocab", "embed")),
                "wb": Param(w.copy(), ("vocab", "embed")),
                "b": Param(jnp.zeros((1,), jnp.float32), (None,))}

    def loss(params, batch):
        p = unbox(params)
        feats = batch["features"]
        valid = (feats >= 0).astype(jnp.float32)[..., None]
        va = p["wa"][jnp.maximum(feats, 0)] * valid
        vb = p["wb"][jnp.maximum(feats, 0)] * valid
        # asymmetric column weights keep the per-row delta elements at
        # DISTINCT magnitudes: only the row max quantizes exactly (+-127),
        # the rest genuinely draw stochastic-rounding noise
        cw = jnp.asarray([1.0, 0.61], jnp.float32)
        logit = ((va * cw).sum(axis=(-2, -1))
                 + (vb * cw).sum(axis=(-2, -1))) + p["b"][0]
        lab = batch["label"].astype(jnp.float32)
        per = jnp.maximum(logit, 0) - logit * lab + jnp.log1p(
            jnp.exp(-jnp.abs(logit)))
        m = batch.get("sample_mask", jnp.ones_like(per))
        return (per * m).sum() / jnp.maximum(m.sum(), 1.0)

    cfg = FedConfig(num_clients=ds.num_clients, clients_per_round=5,
                    local_iters=2, local_batch=4, lr=0.5,
                    algorithm="fedsubavg", sparse=True, sparse_int8=True)
    tr = FederatedTrainer(ds, mk, loss, cfg)
    tr.run_round()
    wa = np.asarray(unbox(tr.state.params)["wa"])
    wb = np.asarray(unbox(tr.state.params)["wb"])
    assert not np.array_equal(wa, wb), \
        "identical tables stayed identical: int8 noise is correlated"


def test_leaf_wire_bytes_containers(rng):
    """Regression: leaf_wire_bytes must price empty containers (0 bytes, not
    IndexError) and multi-leaf subtrees (sum, not first-leaf-only)."""
    from repro.sparse import leaf_wire_bytes
    from repro.sparse.compress import quantize_rows_int8 as q8
    v, r, d = 50, 5, 3
    ids = jnp.asarray([1, 7, 9, -1, -1], jnp.int32)
    rows = jnp.asarray(rng.normal(size=(r, d)), jnp.float32)
    rows = rows * (np.asarray(ids) >= 0)[:, None]
    rs = RowSparse(ids, rows, v)
    assert leaf_wire_bytes(rs) == 3 * (4 + d * 4)
    qr = q8(rs, jax.random.PRNGKey(0))
    assert leaf_wire_bytes(qr) == 3 * (4 + d + 4)
    arr = jnp.zeros((4, 6), jnp.float32)
    assert leaf_wire_bytes(arr) == 4 * 6 * 4
    # empty containers: 0 bytes (the old code crashed on leaves[0])
    assert leaf_wire_bytes([]) == 0.0
    assert leaf_wire_bytes({}) == 0.0
    assert leaf_wire_bytes(()) == 0.0
    # nested dict: the SUM of its leaves (old code priced only the first)
    nested = {"x": arr, "y": {"z": jnp.zeros((2, 2), jnp.float32), "rs": rs}}
    want = 4 * 6 * 4 + 2 * 2 * 4 + 3 * (4 + d * 4)
    assert leaf_wire_bytes(nested) == want
    assert tree_wire_bytes(nested) == want
    # scalar leaf
    assert leaf_wire_bytes(np.float32(1.0)) == 4.0


def test_trainer_downlink_priced_at_gathered_submodel(small_ds):
    """Honest downlink: submodel mode ships the gathered capacity-row buffer;
    dense-replica mode ships the full table. The dense baseline carries the
    local_iters factor (I model round-trips at I=1 to match one I-step round)."""
    tr = _make_trainer(small_ds, sparse=True)          # local_iters=3
    tr.run_round()
    c = tr.comm_log[-1]
    dense_bytes, static, row_payload, _ = tr._comm_meta
    k = tr.cfg.clients_per_round
    # dense baseline: K * model * I, both directions
    assert c.bytes_up_dense == pytest.approx(k * dense_bytes * 3)
    assert c.bytes_down_dense == pytest.approx(k * dense_bytes * 3)
    # downlink rows = the shared capacity bucket (clamped to the table size:
    # the pow2 padding past V is never materialised on the wire), same for
    # every client
    rows_down = (c.bytes_down_sparse - k * static) / (4 + row_payload)
    assert rows_down % k == 0
    per_client = int(rows_down / k)
    assert 8 <= per_client <= small_ds.num_features
    assert (per_client == small_ds.num_features
            or (per_client & (per_client - 1)) == 0)
    # density still reports the true submodel size, not the padded bucket
    assert 0 < c.density < 1
    # dense-replica local mode prices the full-table broadcast it performs:
    # the whole payload, but NO per-row id bytes (a contiguous table ships
    # no row indices) — so at local_iters=1 it would equal the dense model
    tr_rep = _make_trainer(small_ds, sparse=True, sparse_local="replicated")
    tr_rep.run_round()
    c_rep = tr_rep.comm_log[-1]
    want = k * static + k * small_ds.num_features * row_payload
    assert c_rep.bytes_down_sparse == pytest.approx(want)
    assert c_rep.bytes_down_sparse == pytest.approx(c_rep.bytes_down_dense / 3)
    assert c_rep.bytes_down_sparse > c.bytes_down_sparse
    # regression: when the pow2 bucket overshoots the table (clients touching
    # nearly all of V), the priced download clamps to the table size — the
    # submodel can never cost more wire than shipping the whole table
    over_cap = pow2_capacity(small_ds.num_features)       # > V by construction
    assert over_cap > small_ds.num_features
    tr._log_sparse_comm(np.full(k, small_ds.num_features - 1), over_cap)
    c_over = tr.comm_log[-1]
    assert c_over.bytes_down_sparse == pytest.approx(want)
    assert c_over.bytes_down_sparse <= c_rep.bytes_down_sparse


def test_run_round_numbers_continue_across_calls(small_ds):
    """Regression: a second run() (or mixing run_round with run) must append
    RoundRecords whose round numbers continue from the global counter instead
    of restarting at 0 and colliding with existing history."""
    tr = _make_trainer(small_ds, sparse=True)
    tr.run(4, eval_every=2)
    tr.run(4, eval_every=2)
    rounds = [r.round for r in tr.history]
    assert rounds == [2, 4, 6, 8]
    tr.run_round()
    tr.run(2, eval_every=2)
    rounds = [r.round for r in tr.history]
    assert rounds == [2, 4, 6, 8, 11]
    assert rounds == sorted(rounds) and len(set(rounds)) == len(rounds)
    assert tr._rounds_run == 11


# ---------------------------------------------------------------------------
# end-to-end: simulation.make_round_step sparse mode == fedsgd
# ---------------------------------------------------------------------------


def test_simulation_sparse_mode_matches_fedsgd():
    cfg = get_smoke_config("qwen2_5_14b").replace(dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    fed = FedConfig(num_clients=64, clients_per_round=4, lr=0.1,
                    algorithm="fedsubavg")
    heat = jnp.maximum(
        jax.random.randint(jax.random.PRNGKey(1), (cfg.vocab_size,), 0, 30)
        .astype(jnp.float32), 0)
    b, s = 4, 16
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (b, s), 0,
                                          cfg.vocab_size),
             "labels": jnp.ones((b, s), jnp.int32),
             "mask": jnp.ones((b, s), jnp.float32),
             "heat_vocab": heat}
    dense_step = jax.jit(make_round_step(api.loss, params, fed, mode="fedsgd"))
    sparse_step = jax.jit(make_round_step(api.loss, params, fed, mode="sparse"))
    pd_, md = dense_step(params, batch)
    ps_, ms = sparse_step(params, batch)
    np.testing.assert_allclose(float(ms["loss"]), float(md["loss"]), rtol=1e-6)
    assert 0 < float(ms["density"]) <= 1
    for a, b_ in zip(jax.tree.leaves(unbox(pd_)), jax.tree.leaves(unbox(ps_))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-5, atol=1e-5)


def test_simulation_sparse_mode_without_explicit_labels():
    """Regression: the LM losses derive next-token targets from
    batch["tokens"] when "labels" is absent; sparse mode must pin targets to
    the ORIGINAL ids before the submodel swap remaps tokens to row slots."""
    cfg = get_smoke_config("qwen2_5_14b").replace(dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    fed = FedConfig(num_clients=64, clients_per_round=4, lr=0.1,
                    algorithm="fedsubavg")
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(7), (4, 16), 0,
                                          cfg.vocab_size),
             "heat_vocab": jnp.full((cfg.vocab_size,), 5.0)}
    dense_step = jax.jit(make_round_step(api.loss, params, fed, mode="fedsgd"))
    sparse_step = jax.jit(make_round_step(api.loss, params, fed, mode="sparse"))
    pd_, md = dense_step(params, batch)
    ps_, ms = sparse_step(params, batch)
    np.testing.assert_allclose(float(ms["loss"]), float(md["loss"]), rtol=1e-6)
    for a, b_ in zip(jax.tree.leaves(unbox(pd_)), jax.tree.leaves(unbox(ps_))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-5, atol=1e-5)


def test_simulation_sparse_short_training_run_matches():
    """Losses over a short multi-round run agree to >= 1e-5 (ISSUE criterion)."""
    cfg = get_smoke_config("qwen2_5_14b").replace(dtype="float32")
    api = build_model(cfg)
    fed = FedConfig(num_clients=64, clients_per_round=4, lr=0.1,
                    algorithm="fedsubavg")
    heat = jnp.maximum(
        jax.random.randint(jax.random.PRNGKey(1), (cfg.vocab_size,), 0, 30)
        .astype(jnp.float32), 1)

    def run(mode):
        params = api.init(jax.random.PRNGKey(0))
        step = jax.jit(make_round_step(api.loss, params, fed, mode=mode))
        losses = []
        for r in range(4):
            key = jax.random.PRNGKey(100 + r)
            batch = {"tokens": jax.random.randint(key, (4, 16), 0, cfg.vocab_size),
                     "labels": jnp.ones((4, 16), jnp.int32),
                     "mask": jnp.ones((4, 16), jnp.float32),
                     "heat_vocab": heat}
            params, m = step(params, batch)
            losses.append(float(m["loss"]))
        return losses

    np.testing.assert_allclose(run("sparse"), run("fedsgd"), rtol=1e-5)


def test_wire_bytes_accounting(rng):
    v, d, r = 100, 8, 10
    ids = jnp.asarray(list(range(r)), jnp.int32)
    rs = RowSparse(ids, jnp.asarray(rng.normal(size=(r, d)), jnp.float32), v)
    assert tree_wire_bytes({"e": rs}) == r * (4 + d * 4)
    dense = jnp.zeros((v, d), jnp.float32)
    assert tree_wire_bytes({"e": dense}) == v * d * 4
    qr = quantize_rows_int8(rs, jax.random.PRNGKey(0))
    assert tree_wire_bytes({"e": qr}) == r * (4 + d + 4)
