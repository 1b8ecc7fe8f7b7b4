"""Kernel contract plane: the static Pallas VMEM/race/cost auditor.

Positive direction: every registered in-repo kernel passes all three
contracts and the registry covers every ``pallas_call`` site. Negative
direction: planted contract breakers — a carried-accumulator grid dim
declared ``"parallel"`` and an over-budget BlockSpec — must fail with their
named diagnostics, and a planted guard that under-reports its footprint or
mispredicts its block picks must be caught as drift. The cost model is
pinned on the number the plane exists to expose: ``union_segsum``'s grid
follows the rows, so it streams its slots and rows once (restream = 1).
"""
import dataclasses
import json
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import kernel_audit as ka
from repro.kernels.heat_scatter import _tpu_compiler_params
from repro.kernels.introspect import REGISTRY, GuardReport

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# positive: the in-repo kernels hold their contracts
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def reports():
    return {r.name: r for r in ka.audit_all()}


def test_all_registered_kernels_pass(reports):
    assert set(reports) == {"union_segsum", "rowsparse_scatter",
                            "flash_attention", "flash_decode"}
    for name, rep in reports.items():
        assert rep.ok, (name, rep.failures, rep.vmem.failures,
                        rep.race.failures)


def test_registry_covers_every_pallas_call_site():
    assert ka.registry_coverage() == []


def test_carried_dims_match_declared_semantics(reports):
    """The race detector recovers each kernel's true carried dims."""
    assert reports["union_segsum"].race.required == [0]
    assert reports["rowsparse_scatter"].race.required == [1]
    assert reports["flash_attention"].race.required == [2]
    assert reports["flash_decode"].race.required == [1]


def test_union_segsum_restream_priced(reports):
    """The grid is (nt,) over row tiles: every operand, the slots/rows
    stream among them, is fetched once (restream = 1)."""
    rep = reports["union_segsum"]
    assert len(rep.grid) == 1 and rep.grid[0] > 1
    per_op = rep.cost.per_operand
    assert max(op["restream"] for op in per_op.values()) == 1.0
    # the payload stream (slots: (T,) i32 and rows: (T, D) f32) is split
    # over the grid's tiles, and each tile is fetched once
    streamed = [op for op in per_op.values()
                if op["kind"] == "input" and op["fetches"] == rep.grid[0]]
    assert len(streamed) == 2
    assert all(op["restream"] == 1.0 for op in streamed)
    assert rep.cost.bytes_touched > 0 and rep.cost.flops > 0
    assert rep.cost.hbm_seconds > 0 and rep.cost.compute_seconds > 0


def test_vmem_guard_matches_structural(reports):
    """Guard >= structural footprint and block predictions match captures."""
    for name, rep in reports.items():
        assert rep.vmem.guard_bytes is not None
        assert rep.vmem.guard_bytes >= rep.vmem.structural_bytes, name
        assert rep.vmem.structural_bytes <= rep.vmem.budget_bytes, name


# ---------------------------------------------------------------------------
# the attention guards: fits_vmem must track the wrapper's block picks
# ---------------------------------------------------------------------------


def test_flash_attention_guard_tracks_block_picks():
    fa = sys.modules["repro.kernels.flash_attention"]
    # the clamp the wrapper applies is the clamp the guard prices
    assert fa._block_sizes(256, 256, 512, 512) == (256, 256)
    assert fa._block_sizes(2048, 2048, 512, 512) == (512, 512)
    assert fa._block_sizes(None, None, 512, 512) == (512, 512)
    assert fa.fits_vmem(128, sq=2048, sk=2048)
    # blowing up the k/v tiles must trip the budget
    assert not fa.fits_vmem(256, sq=1 << 16, sk=1 << 16,
                            blk_q=4096, blk_k=4096)
    # footprint is monotone in the clamped block sizes
    assert (fa.vmem_footprint(128, sq=256, sk=256)
            < fa.vmem_footprint(128, sq=2048, sk=2048))


def test_flash_decode_guard_tracks_block_picks():
    fd = sys.modules["repro.kernels.flash_decode"]
    assert fd._block_sizes(512, 1024) == 512
    assert fd._block_sizes(4096, 1024) == 1024
    assert fd._block_sizes(None, 1024) == 1024
    assert fd.fits_vmem(128, s=4096)
    assert not fd.fits_vmem(1024, s=1 << 16, blk_s=8192)
    assert (fd.vmem_footprint(128, s=512)
            < fd.vmem_footprint(128, s=4096))


# ---------------------------------------------------------------------------
# negative: planted contract breakers fail with named diagnostics
# ---------------------------------------------------------------------------


def _planted_race(semantics):
    """Grid (8,): scratch accumulator reset at i==0, accumulated every
    step, flushed at i==7 — grid dim 0 carries cross-program state."""
    n = 8

    def kernel(x_ref, o_ref, acc_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += x_ref[...]

        @pl.when(i == n - 1)
        def _flush():
            o_ref[...] = acc_ref[...]

    def fn(x):
        return pl.pallas_call(
            kernel,
            grid=(n,),
            in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((1, 128), jnp.float32),
            scratch_shapes=[pltpu.VMEM((1, 128), jnp.float32)],
            compiler_params=_tpu_compiler_params(semantics=semantics),
        )(x)

    return fn, (jax.ShapeDtypeStruct((n, 128), jnp.float32),)


def test_planted_parallel_carry_fails_race_contract():
    fn, args = _planted_race(("parallel",))
    (cap,) = ka.capture_pallas_calls(fn, *args)
    rep = ka.race_contract(cap, kernel="planted")
    assert not rep.ok
    assert rep.required == [0]
    assert any("[megacore-race]" in f and "'parallel'" in f
               and "grid dim 0" in f for f in rep.failures), rep.failures


def test_planted_carry_passes_when_declared_arbitrary():
    fn, args = _planted_race(("arbitrary",))
    (cap,) = ka.capture_pallas_calls(fn, *args)
    assert ka.race_contract(cap, kernel="planted").ok


def _planted_fat():
    """(2048, 1024) f32 blocks, double-buffered in and out: 32 MiB."""

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def fn(x):
        return pl.pallas_call(
            kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((2048, 1024), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((2048, 1024), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((8192, 1024), jnp.float32),
            compiler_params=_tpu_compiler_params(semantics=("arbitrary",)),
        )(x)

    return fn, (jax.ShapeDtypeStruct((8192, 1024), jnp.float32),)


def test_planted_overbudget_blockspec_fails_vmem_contract():
    fn, args = _planted_fat()
    (cap,) = ka.capture_pallas_calls(fn, *args)
    rep = ka.vmem_contract(cap, kernel="fat", budget=12 * 1024 * 1024)
    assert not rep.ok
    assert any("[vmem-budget]" in f and "exceeds" in f
               for f in rep.failures), rep.failures
    assert rep.structural_bytes == 2 * 2 * 2048 * 1024 * 4


def test_planted_guard_drift_is_caught():
    """A guard that lies about the kernel is drift, not a pass."""
    entry = next(e for e in REGISTRY if e.name == "union_segsum")

    # under-reporting guard: claims fewer bytes than the capture shows
    lying = dataclasses.replace(
        entry, guard=lambda: GuardReport(fits=True, footprint=1, blocks={}))
    rep = ka.audit_kernel(lying)
    assert any("[vmem-guard-underestimate]" in f
               for f in rep.vmem.failures), rep.vmem.failures

    # verdict drift: guard says the kernel does not fit although it does
    honest = entry.guard()
    pessimist = dataclasses.replace(
        entry, guard=lambda: dataclasses.replace(honest, fits=False))
    rep = ka.audit_kernel(pessimist)
    assert any("[vmem-guard-drift]" in f
               for f in rep.vmem.failures), rep.vmem.failures

    # block-pick drift: guard predicts a block shape the kernel never picks
    blocks = dict(honest.blocks)
    idx, shape = blocks["slot"]
    blocks["slot"] = (idx, (shape[0] * 2,))
    mispredict = dataclasses.replace(
        entry, guard=lambda: dataclasses.replace(honest, blocks=blocks))
    rep = ka.audit_kernel(mispredict)
    assert any("[block-pick-drift]" in f
               for f in rep.vmem.failures), rep.vmem.failures


def test_tiny_budget_fails_registered_kernel():
    entry = next(e for e in REGISTRY if e.name == "flash_decode")
    rep = ka.audit_kernel(entry, budget=1024)
    assert not rep.ok
    assert any("[vmem-budget]" in f for f in rep.vmem.failures)


# ---------------------------------------------------------------------------
# CLI: the CI gate
# ---------------------------------------------------------------------------


def test_cli_json_report(tmp_path):
    out = tmp_path / "kernel-audit.json"
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.kernel_audit",
         "--json", str(out)],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    rep = json.loads(out.read_text())
    assert rep["ok"] is True
    assert rep["coverage_failures"] == []
    names = [k["name"] for k in rep["kernels"]]
    assert names == ["union_segsum", "rowsparse_scatter",
                     "flash_attention", "flash_decode"]
    for k in rep["kernels"]:
        assert k["ok"] is True
        assert {"vmem", "race", "cost"} <= set(k)
        assert k["vmem"]["structural_bytes"] <= k["vmem"]["budget_bytes"]
        assert k["race"]["dimension_semantics"] is not None
        assert k["cost"]["bytes_touched"] > 0
