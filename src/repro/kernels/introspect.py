"""Audit registry: buildable traces + self-reported guards for every kernel.

The static auditor (``repro.analysis.kernel_audit``) needs two things per
Pallas kernel: (a) a way to *capture* the ``pallas_call`` — a traceable
callable plus representative abstract arguments, traced with
``interpret=False`` so the Mosaic ``dimension_semantics`` land in the jaxpr
(tracing needs no TPU; only lowering does) — and (b) the kernel's *own*
account of itself: the ``fits_vmem``/``vmem_footprint`` guard verdict and
the block shapes its ``_block_sizes`` helper predicts, at the same shapes.

The auditor compares (b) against what it reads out of (a). Because each
kernel module routes its runtime block picks through the same
``_block_sizes`` helper the guard uses, any drift between guard and kernel
(the PR-2 ``fits_vmem`` bug class) shows up here as a block-shape or
footprint mismatch — machine-checked for all kernels, not just
``union_segsum``.

Audit shapes are production-representative but fixed: large enough that no
block clamp degenerates (every default block size survives contact with the
shape) yet small enough that the trace is instant on CPU.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

# the package __init__ re-exports jitted ops under the same names as the
# modules, so attribute-style imports resolve to the functions; go through
# sys.modules to get the module objects themselves
import sys

import repro.kernels.flash_attention
import repro.kernels.flash_decode
import repro.kernels.heat_scatter
import repro.kernels.union_segsum

_fa = sys.modules["repro.kernels.flash_attention"]
_fd = sys.modules["repro.kernels.flash_decode"]
_hs = sys.modules["repro.kernels.heat_scatter"]
_us = sys.modules["repro.kernels.union_segsum"]


@dataclasses.dataclass(frozen=True)
class GuardReport:
    """What a kernel's own guard predicts at the audit shape."""
    fits: bool           # guard verdict at the audit shape
    footprint: int       # guard's analytic VMEM bytes
    #: operand name -> (index among the pallas_call's inputs+outputs block
    #: mappings, block shape the kernel's _block_sizes helper predicts)
    blocks: dict


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """One auditable kernel: how to capture it and what it claims."""
    name: str
    budget: int
    build: Callable      # () -> (fn, args) for jax.make_jaxpr(fn)(*args)
    guard: Callable      # () -> GuardReport at the same audit shape


# -- union_segsum -----------------------------------------------------------
# 16 clients x 656 ids over a 64k vocab, D=64, union capacity 8192: the grid
# (nt=11) has more than one tile and the row count is deliberately NOT a
# multiple of t_blk so the wrapper's padding path is part of the trace.
_US = dict(V=65536, K=16, R=656, D=64, CAP=8192)


def _build_union_segsum():
    c = _US
    args = (jax.ShapeDtypeStruct((c["K"], c["R"]), jnp.int32),
            jax.ShapeDtypeStruct((c["K"], c["R"], c["D"]), jnp.float32),
            jax.ShapeDtypeStruct((c["V"],), jnp.float32))

    def fn(ids, rows, heat):
        return _us.union_segsum(ids, rows, heat, 64.0, c["CAP"],
                                interpret=False)
    return fn, args


def _guard_union_segsum() -> GuardReport:
    c = _US
    t = c["K"] * c["R"]
    t_blk = _us._block_sizes(t, _us.DEFAULT_T_BLK)
    return GuardReport(
        fits=_us.fits_vmem(c["CAP"], c["D"], t=t),
        footprint=_us.vmem_footprint(c["CAP"], c["D"], t=t),
        blocks={"off": (0, (-(-t // t_blk),)),
                "slot": (1, (t_blk,)),
                "rows": (2, (t_blk, c["D"])),
                "out_rows": (3, (c["CAP"] + t_blk, c["D"]))},
    )


# -- rowsparse_scatter ------------------------------------------------------
# 8192 rows into a 64k vocab at D=64: grid (nv=64, nt=8).
_HS = dict(V=65536, T=8192, D=64)


def _build_rowsparse_scatter():
    c = _HS
    args = (jax.ShapeDtypeStruct((c["T"],), jnp.int32),
            jax.ShapeDtypeStruct((c["T"], c["D"]), jnp.float32),
            jax.ShapeDtypeStruct((c["V"],), jnp.float32))

    def fn(ids, rows, heat):
        return _hs.rowsparse_scatter(ids, rows, heat, 64.0, c["V"],
                                     interpret=False)
    return fn, args


def _guard_rowsparse_scatter() -> GuardReport:
    c = _HS
    v_blk, t_blk = _hs._block_sizes(c["V"], c["T"], _hs.DEFAULT_V_BLK,
                                    _hs.DEFAULT_T_BLK)
    return GuardReport(
        fits=_hs.fits_vmem(c["D"], vocab=c["V"], t=c["T"]),
        footprint=_hs.vmem_footprint(c["D"], vocab=c["V"], t=c["T"]),
        blocks={"ids": (1, (t_blk,)),
                "rows": (2, (t_blk, c["D"])),
                "heat": (3, (v_blk,)),
                "out": (4, (v_blk, c["D"]))},
    )


# -- flash_attention --------------------------------------------------------
# 1 sequence, 4 query heads over 2 KV heads (GQA), S=2048, hd=128:
# grid (b*h=4, nq=4, nk=4).
_FA = dict(B=1, H=4, KV=2, S=2048, HD=128)


def _build_flash_attention():
    c = _FA
    args = (jax.ShapeDtypeStruct((c["B"], c["S"], c["H"], c["HD"]),
                                 jnp.float32),
            jax.ShapeDtypeStruct((c["B"], c["S"], c["KV"], c["HD"]),
                                 jnp.float32),
            jax.ShapeDtypeStruct((c["B"], c["S"], c["KV"], c["HD"]),
                                 jnp.float32))

    def fn(q, k, v):
        return _fa.flash_attention(q, k, v, causal=True, interpret=False)
    return fn, args


def _guard_flash_attention() -> GuardReport:
    c = _FA
    blk_q, blk_k = _fa._block_sizes(c["S"], c["S"], 512, 512)
    return GuardReport(
        fits=_fa.fits_vmem(c["HD"], sq=c["S"], sk=c["S"]),
        footprint=_fa.vmem_footprint(c["HD"], sq=c["S"], sk=c["S"]),
        blocks={"q": (0, (1, blk_q, c["HD"])),
                "k": (1, (1, blk_k, c["HD"])),
                "v": (2, (1, blk_k, c["HD"])),
                "o": (3, (1, blk_q, c["HD"]))},
    )


# -- flash_decode -----------------------------------------------------------
# 2 sequences, 4 query heads over 2 KV heads, S=4096 cache, hd=128:
# grid (b*h=8, ns=4).
_FD = dict(B=2, H=4, KV=2, S=4096, HD=128)


def _build_flash_decode():
    c = _FD
    args = (jax.ShapeDtypeStruct((c["B"], c["H"], c["HD"]), jnp.float32),
            jax.ShapeDtypeStruct((c["B"], c["KV"], c["S"], c["HD"]),
                                 jnp.float32),
            jax.ShapeDtypeStruct((c["B"], c["KV"], c["S"], c["HD"]),
                                 jnp.float32),
            jax.ShapeDtypeStruct((c["S"],), jnp.int32))

    def fn(q, kc, vc, kpos):
        return _fd.flash_decode(q, kc, vc, kpos, c["S"] - 1, interpret=False)
    return fn, args


def _guard_flash_decode() -> GuardReport:
    c = _FD
    blk_s = _fd._block_sizes(c["S"], 1024)
    return GuardReport(
        fits=_fd.fits_vmem(c["HD"], s=c["S"]),
        footprint=_fd.vmem_footprint(c["HD"], s=c["S"]),
        blocks={"qpos": (0, (1,)),
                "q": (1, (1, 1, c["HD"])),
                "k": (2, (1, blk_s, c["HD"])),
                "v": (3, (1, blk_s, c["HD"])),
                "pos": (4, (blk_s,)),
                "o": (5, (1, 1, c["HD"]))},
    )


#: Every in-repo Pallas kernel, in audit order. The auditor iterates this;
#: a new kernel module ships by adding its entry here (the auditor's
#: coverage test counts pallas_call sites under repro.kernels and fails if
#: the registry falls behind).
REGISTRY = (
    KernelEntry("union_segsum", _us.VMEM_BUDGET,
                _build_union_segsum, _guard_union_segsum),
    KernelEntry("rowsparse_scatter", _hs.VMEM_BUDGET,
                _build_rowsparse_scatter, _guard_rowsparse_scatter),
    KernelEntry("flash_attention", _fa.VMEM_BUDGET,
                _build_flash_attention, _guard_flash_attention),
    KernelEntry("flash_decode", _fd.VMEM_BUDGET,
                _build_flash_decode, _guard_flash_decode),
)
