"""Benchmark driver: one suite per paper table/figure + kernels + roofline.

Prints ``name,us_per_call,derived`` CSV (one line per measurement).
"""
from __future__ import annotations

import sys
import time
import traceback

SUITES = (
    "benchmarks.bench_fig2",
    "benchmarks.bench_table1",
    "benchmarks.bench_conditioning",
    "benchmarks.bench_kernels",
    "benchmarks.bench_sparse",
    "benchmarks.bench_table2",
    "benchmarks.bench_table3",
    "benchmarks.bench_roofline",
)


def main() -> None:
    import importlib

    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    failures = 0
    for modname in SUITES:
        if only and only not in modname:
            continue
        try:
            mod = importlib.import_module(modname)
            for name, us, derived in mod.run():
                print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception as e:
            failures += 1
            traceback.print_exc()
            print(f"{modname},nan,ERROR:{type(e).__name__}", flush=True)
    if failures:
        raise SystemExit(f"{failures} benchmark suites failed")


if __name__ == "__main__":
    main()
