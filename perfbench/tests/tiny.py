"""Cells cut to a size a CPU test run can hold, and runs of them."""
from __future__ import annotations

from bench.manifest import load_cell
from bench.peaks import PEAKS

#: the per-cell cut: few clients, a small id space, a small cohort
TINY = {"sent140-lstm": {"num_clients": 48, "vocab": 4096},
        "din-amazon": {"num_clients": 48, "num_items": 3000}}
SEED = 2**31 + 11


def tiny_cell(name: str, **kw):
    cell = load_cell(name, **kw)
    cell.config.update(TINY[cell.config["name"]])
    cell.traffic.update(clients=8)
    return cell


def run_tiny(name: str, monkeypatch=None, trace: bool = False, **load):
    """One run of the cut cell on the CPU (no chip required)."""
    import io

    from bench import harness
    if monkeypatch is not None:
        monkeypatch.setattr(harness, "peaks",
                            lambda kind: PEAKS["TPU v5 lite"])
    log = io.StringIO()
    res = harness.run_cell(name, SEED, 0.3, trace, require_chip=False,
                           cell=tiny_cell(name, **load), log=log)
    return res, log.getvalue()
