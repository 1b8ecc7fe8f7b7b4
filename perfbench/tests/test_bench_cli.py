"""The command refuses to print a result without a chip or without the
program beside it."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "din-amazon.step-k128", "--seed", str(2**31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, "perfbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            pass
    return True


def test_exits_nonzero_without_a_chip():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no accelerator" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)


def test_unknown_workload_is_refused():
    sys.path.insert(0, str(ROOT / "perfbench"))
    from bench.manifest import load_cell
    try:
        load_cell("no-such.cell")
    except KeyError as e:
        assert "no-such.cell" in str(e)
    else:
        raise AssertionError("an unknown cell loaded")
