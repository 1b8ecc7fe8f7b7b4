"""The jit-hygiene linter: every rule fires on its seeded fixture, the
allowlist works, the JSON report is machine-readable — and ``src/`` is
clean (the tier-1 static-analysis gate)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")

#: fixture file -> the one rule it seeds (each also carries safe variants
#: that must NOT fire)
_SEEDED = {
    "viol_traced_float.py": "traced-float",
    "viol_host_numpy.py": "host-numpy",
    "viol_static_argnames.py": "static-argnames-array",
    "viol_pallas_semantics.py": "pallas-dim-semantics",
    "viol_pallas_blockspec.py": "pallas-blockspec-misaligned",
    "viol_data_dep_shape.py": "data-dep-shape",
    "viol_donated_reuse.py": "donated-reuse",
    "viol_shard_full_aggregate.py": "shard-full-aggregate",
    "viol_shard_missing_psum.py": "shard-missing-psum",
}


# ---------------------------------------------------------------------------
# seeded violations: one fixture per rule, exactly one hit each
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fixture,rule", sorted(_SEEDED.items()))
def test_seeded_violation_fires(fixture, rule):
    path = os.path.join(FIXTURES, fixture)
    violations, suppressions, n = lint.lint_paths([path])
    assert n == 1
    assert not suppressions
    assert [v.rule for v in violations] == [rule], (
        f"{fixture} must trip exactly its seeded rule; got "
        f"{[(v.rule, v.line) for v in violations]}")
    # the violation anchors at (or within the statement of) the line the
    # fixture marks with a VIOLATION comment
    with open(path, encoding="utf-8") as f:
        marked = [i for i, ln in enumerate(f.read().splitlines(), 1)
                  if "VIOLATION" in ln]
    assert any(abs(violations[0].line - m) <= 2 for m in marked)


def test_compiler_params_without_semantics_fires():
    """``pltpu.CompilerParams`` must state the grid's semantics too."""
    src = textwrap.dedent("""
        from jax.experimental.pallas import tpu as pltpu

        BARE = pltpu.CompilerParams(vmem_limit_bytes=1 << 20)
        OK = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    """)
    violations, _ = lint.lint_source(src, "mod.py")
    assert [(v.rule, v.line) for v in violations] == [
        ("pallas-dim-semantics", 4)]
    assert "CompilerParams without dimension_semantics" in \
        violations[0].message


def test_donated_reuse_sees_statements_inside_with_blocks():
    """A donating call that rebinds its holder inside a ``with`` block is
    clean; a re-read after it, inside or after the block, still fires."""
    src = textwrap.dedent("""
        import contextlib
        import jax

        step = jax.jit(lambda s, b: s + b, donate_argnums=(0,))


        def safe(state, batch):
            with contextlib.nullcontext():
                state = step(state, batch)
            return state


        def bad(state, batch):
            with contextlib.nullcontext():
                new = step(state, batch)
            return new + state
    """)
    violations, _ = lint.lint_source(src, "mod.py")
    assert [(v.rule, v.line) for v in violations] == [("donated-reuse", 17)]


def test_cli_nonzero_on_fixtures_zero_on_clean(tmp_path):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    bad = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", FIXTURES],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert bad.returncode != 0
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    ok = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", str(clean)],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr


# ---------------------------------------------------------------------------
# allowlist syntax
# ---------------------------------------------------------------------------

_VIOLATING = """
import jax
import jax.numpy as jnp


@jax.jit
def f(x):
    return jnp.ones(()) * float(x){allow}
"""


def test_allowlist_with_reason_suppresses():
    src = _VIOLATING.format(
        allow="  # repro-lint: ok traced-float -- host-side scale knob")
    violations, suppressions = lint.lint_source(src, "mod.py")
    assert not violations
    assert [s.rule for s in suppressions] == ["traced-float"]
    assert "host-side" in suppressions[0].reason


def test_allowlist_comment_line_above_suppresses():
    src = textwrap.dedent("""
        import jax
        import jax.numpy as jnp


        @jax.jit
        def f(x):
            # repro-lint: ok traced-float -- reason spanning
            # a second comment line
            return jnp.ones(()) * float(x)
    """)
    violations, suppressions = lint.lint_source(src, "mod.py")
    assert not violations
    assert len(suppressions) == 1


def test_bare_allowlist_is_itself_a_violation():
    src = _VIOLATING.format(allow="  # repro-lint: ok traced-float")
    violations, _ = lint.lint_source(src, "mod.py")
    assert [v.rule for v in violations] == ["bare-allowlist"]


def test_unknown_rule_in_allowlist_flagged():
    src = _VIOLATING.format(
        allow="  # repro-lint: ok no-such-rule -- whatever")
    violations, _ = lint.lint_source(src, "mod.py")
    assert "bare-allowlist" in {v.rule for v in violations}
    assert "traced-float" in {v.rule for v in violations}


def test_wildcard_allowlist():
    src = _VIOLATING.format(allow="  # repro-lint: ok * -- prototype code")
    violations, suppressions = lint.lint_source(src, "mod.py")
    assert not violations and len(suppressions) == 1


# ---------------------------------------------------------------------------
# machine-readable report
# ---------------------------------------------------------------------------


def test_json_report_schema(tmp_path):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = tmp_path / "report.json"
    subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", FIXTURES,
         "--json", str(out)],
        cwd=REPO, env=env, capture_output=True, text=True)
    rep = json.loads(out.read_text())
    assert rep["tool"] == "repro.analysis.lint"
    assert rep["ok"] is False
    assert rep["files_scanned"] == len(_SEEDED)
    assert set(rep["rules"]) == set(lint.RULES)
    got = {(v["rule"], os.path.basename(v["path"])) for v in rep["violations"]}
    assert got == {(r, f) for f, r in _SEEDED.items()}
    for v in rep["violations"]:
        assert {"rule", "path", "line", "col", "message"} <= set(v)


# ---------------------------------------------------------------------------
# the gate: the repo's own source is clean
# ---------------------------------------------------------------------------


def test_repo_source_is_lint_clean():
    """Tier-1 CI gate: zero violations over src/, and every suppression is
    explained (carries a reason)."""
    violations, suppressions, n = lint.lint_paths([os.path.join(REPO, "src")])
    assert n > 50, "lint walked suspiciously few files"
    assert not violations, "\n".join(str(v) for v in violations)
    for s in suppressions:
        assert s.reason and s.reason.strip(), f"unexplained suppression: {s}"
