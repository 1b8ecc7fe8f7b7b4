"""The numbers that decide ``correct``, each against its limit.

The trainer's first calls are compared with the dense reference over the
same rounds:

- ``loss_gap``: the largest relative gap of a round's reported loss;
- ``update_gap``: the first call's change of the parameters, as the server
  applied it, by the worst leaf;
- ``change_gap``: the parameters' change after the last compared call, by
  the worst leaf.

A leaf's gap is ``| |a| - |r| |``, program norm against reference norm,
over the larger of the reference leaf's norm and the median leaf's.
Leaves whose reference first-call change is under a thousandth of the
median leaf's move by round-off alone and are left out of both.
"""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss_gap", "update_gap", "change_gap")
#: a leaf whose reference update is below this share of the median leaf's
#: is not compared
STILL = 1e-3


def _worst_leaf(prog, ref, keep) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    med = float(np.median(ref))
    gaps = np.abs(prog - ref) / np.maximum(ref, med)
    return float(np.max(gaps[keep])) if keep.any() else float("nan")


def gaps(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: ``losses`` (every compared round), ``first`` and
    ``last`` (per-leaf change norms after the first and the last call)."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    first = np.asarray(ref["first"], np.float64)
    keep = first >= STILL * np.median(first)
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "update_gap": _worst_leaf(prog["first"], ref["first"], keep),
        "change_gap": _worst_leaf(prog["last"], ref["last"], keep),
    }


def judge(numbers: dict, limits: dict) -> tuple:
    """``(correct, checks)``: every number finite and within its limit."""
    checks = {n: {"value": numbers[n], "limit": limits[n]} for n in NUMBERS}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
