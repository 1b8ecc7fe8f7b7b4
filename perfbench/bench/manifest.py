"""Find a cell's configuration, traffic, limits and metric readers by name.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own under the benchmark's directory, named
after it; ``BENCHMARK.json`` names them:

- ``configs[].file``: the configuration (sizes, the reference model module
  under ``models/``, the program entry points it is run through);
- ``traffic/<traffic>.json``: the traffic mix (cohort, local steps, driver,
  rounds per call, chips, mesh, calls the reference follows);
- ``limits/<cell>.json``: the limit of each compared number;
- ``metrics/<metric>.py``: the reader of one per-layer metric (a metric
  split by cell, ``<metric>.<part>``, may share its base's reader).
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

#: the checkout's root: this file is ``perfbench/bench/manifest.py``
ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = Path(__file__).resolve().parents[1]


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list      # manifest entries of the metrics this cell reports
    per_layer: list
    bench_dir: Path

    def model(self):
        """The configuration's plain reference module (``models/<name>.py``)."""
        ref = self.config["reference"]
        return _module(self.bench_dir / "models" / f"{ref}.py",
                       f"bench_model_{ref}")

    def reader(self, metric: str):
        """The per-layer metric's reader module: ``metrics/<name>.py``, or
        for a metric split by cell, ``<base>.<part>`` with no file of its
        own, its base's ``metrics/<base>.py``."""
        path = self.bench_dir / "metrics" / f"{metric}.py"
        if not path.exists():
            path = self.bench_dir / "metrics" / f"{metric.split('.')[0]}.py"
        return _module(path, f"bench_metric_{metric.replace('.', '_')}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_json(root / conf["file"]),
        traffic=_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        limits=_json(bench_dir / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir)
