"""A configuration, a traffic mix and a per-layer metric are added as new
files plus manifest entries, with no edit to a file that is there."""
import json
import shutil
from pathlib import Path

from tiny import TINY, run_tiny

ROOT = Path(__file__).resolve().parents[2]

METRIC = '''"""Calls into the trainer per round of the traced window, from the
harness's own host spans."""


def read(ctx):
    calls = [h for h in ctx.trace.host if h[0] == "bench_call"]
    return len(calls) / ctx.rounds if calls and ctx.rounds else None
'''


def test_new_cell_from_new_files_only(tmp_path, monkeypatch):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    bench = tmp_path / "perfbench"
    shutil.copytree(ROOT / "perfbench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    conf = json.loads((bench / "configs" / "din-amazon.json").read_text())
    conf.update(name="din-wide", hidden=48)
    (bench / "configs" / "din-wide.json").write_text(json.dumps(conf))
    (bench / "traffic" / "step-k16.json").write_text(json.dumps(
        {"clients": 16, "local_iters": 2, "local_batch": 4,
         "driver": "run_round", "mesh": None, "check_calls": 2}))
    (bench / "metrics" / "calls_per_round.py").write_text(METRIC)
    (bench / "limits" / "din-wide.step-k16.json").write_text(json.dumps(
        {"loss_gap": 1e-4, "update_gap": 1e-4, "change_gap": 1e-4}))
    manifest = json.loads((tmp_path / "BENCHMARK.json").read_text())
    manifest["configs"].append(
        {"name": "din-wide", "source": "https://arxiv.org/abs/1706.06978",
         "file": "perfbench/configs/din-wide.json", "reduced": [],
         "why": "a throwaway configuration"})
    manifest["workloads"].append(
        {"name": "din-wide.step-k16", "config": "din-wide",
         "traffic": "step-k16", "chips": 1, "why": "a throwaway cell"})
    manifest["per_layer"].append(
        {"name": "calls_per_round", "unit": "1", "better": "lower",
         "source": "program_span", "layer": "driver",
         "moves": "updates_per_s", "workloads": ["din-wide.step-k16"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    assert all(p.read_bytes() == b for p, b in before.items())
    TINY["din-wide"] = TINY["din-amazon"]
    try:
        res, _ = run_tiny("din-wide.step-k16", monkeypatch, trace=True,
                          root=tmp_path, bench_dir=bench)
    finally:
        del TINY["din-wide"]
    assert res["correct"], res["checks"]
    assert res["metrics"]["calls_per_round"]["value"] == 1.0
