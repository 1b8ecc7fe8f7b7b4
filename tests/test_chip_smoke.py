"""``chip_smoke.py`` off the chip: its phases at a tiny size, and its refusals.

The phases run on the CPU with the fused union kernel in interpret mode,
against the jnp ``bitmap`` reference, and the parity check must hold. The
entry point itself must refuse to run without a TPU, and must fail in a
directory that holds the script and nothing else of the repo. The
compile-cache placement is checked in child processes, since pointing the
cache somewhere is process-wide.
"""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
TINY = {"emb_dim": 4, "hidden": 8, "layers": 1}


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"),
               **extra)
    return env


def test_one_chip_phases_tiny_interpret(smoke):
    res = smoke.one_chip(CPU, vocab=3000, clients=12, cohort=4, seed=0,
                         step_rounds=2, engine_rounds=1,
                         union_backend="pallas", model=TINY,
                         expect_kernel=False)
    diffs = res["parity"]["max_param_diff_per_call"]
    assert len(diffs) == 2 + 3
    assert max(diffs) <= smoke.PARITY_TOL
    # off the chip "auto" takes a jnp backend and nothing lowers to Mosaic
    rounds = res["rounds"]
    assert rounds["auto_union_backend"] == "bitmap"
    assert rounds["tpu_custom_call_in_round"] is False
    for phase, key in (("rounds", "kernel_run_round"),
                       ("rounds", "kernel_run_rounds"),
                       ("parity", "reference_run_rounds")):
        timing = res[phase][key]
        assert timing["first_call_s"] > 0 and timing["compiles"] > 0


@pytest.mark.parametrize("vocab,combine", [(3000, "psum"),
                                           (1 << 18, "union")])
def test_sharded_phase_tiny(smoke, vocab, combine):
    res = smoke.sharded(CPU, vocab=vocab, clients=12, cohort=4, seed=0,
                        chips=1, expect_combine=combine, rounds=1,
                        model=TINY)
    assert res["max_param_diff"] <= smoke.PARITY_TOL


def test_sharded_phase_refuses_the_wrong_combine(smoke):
    with pytest.raises(smoke.SmokeFailure, match="selects the 'psum'"):
        smoke.sharded(CPU, vocab=3000, clients=12, cohort=4, seed=0,
                      chips=1, expect_combine="union", model=TINY)


def test_parity_check_fails_above_tolerance(smoke):
    smoke.check_parity(smoke.PARITY_TOL, "at the tolerance")
    for bad in (2 * smoke.PARITY_TOL, float("nan")):
        with pytest.raises(smoke.SmokeFailure, match="exceeds"):
            smoke.check_parity(bad, "planted")


def test_main_exits_nonzero_off_the_chip(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = _env()
    env.pop("PYTHONPATH")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.common.compile_cache import use_compile_cache
print(use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: jnp.sin(x) * 3.0)(1.0).block_until_ready()
"""


def _probe(compile_: bool, **env):
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(compile=compile_)],
        cwd=REPO, env=_env(**env), capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.split()


def test_compile_cache_lands_where_the_environment_says(tmp_path):
    from repro.common.compile_cache import DEFAULT_DIR

    def listing():
        return sorted(os.listdir(DEFAULT_DIR)) if DEFAULT_DIR.exists() else []

    before = listing()
    where = tmp_path / "cache"
    got = _probe(True, JAX_COMPILATION_CACHE_DIR=str(where),
                 JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert got == [str(where), str(where)]
    assert os.listdir(where)                 # the compile was written there
    assert listing() == before               # and nowhere in the checkout


def test_compile_cache_default_is_one_fixed_dir_in_the_checkout():
    from repro.common.compile_cache import DEFAULT_DIR
    assert DEFAULT_DIR == pathlib.Path(REPO) / ".jax_cache"
    first, second = _probe(False), _probe(False)
    assert first == second == [str(DEFAULT_DIR), str(DEFAULT_DIR)]
