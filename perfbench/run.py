"""Run one benchmark cell once and print its result as the last line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, in one process that holds the cell's
chips. The cell, its configuration, traffic mix, limits and per-layer
metric readers are found by name from ``BENCHMARK.json`` (see
``bench/manifest.py``). With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the profiler
and the result carries its per-layer metrics and a breakdown. Either way
the run checks the trainer against the dense reference: the numbers
compared, each beside its limit, end standard error and close the result
line under ``checks``.

Without an accelerator, with fewer chips than the cell asks for, or
without the program's ``src/`` beside the benchmark, it exits non-zero and
prints no result. JAX's persistent compilation cache is the program's
(``repro.common.compile_cache``): ``$JAX_COMPILATION_CACHE_DIR`` where it
is set, else ``.jax_cache/`` of the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    t_start = time.perf_counter()   # set-up counts from here
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # cache every program, however quick to compile
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit("run.py: the program's src/repro is not in this checkout")
    sys.path[:0] = [str(ROOT / "perfbench"), str(ROOT / "src")]
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    from bench.harness import NoChip, run_cell
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start)
    except NoChip as e:
        sys.exit(f"run.py: {e}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
