"""Where JAX keeps its persistent compilation cache.

The entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.train``) call :func:`use_compile_cache` once, before their
first compile. Importing this module changes nothing, and library code and
tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the root of the checkout (this file is ``src/repro/common/...``)
CHECKOUT = Path(__file__).resolve().parents[3]
#: the cache directory used when the environment names none; one fixed
#: path, so each run finds what the last one compiled (listed in .gitignore)
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and wins:
    nothing is set here. Otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
