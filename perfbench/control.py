"""Readings that set a cell's limits: the program's, the control's, faults'.

    python3 perfbench/control.py --workload <name> --seeds 1 2 3 [--program]

For each seed, the dense reference at the configuration's precision is the
truth, and each of these is compared with it by the same numbers that
decide ``correct`` (``bench/check.py``):

- ``control``: the reference under the matmul precision one step below
  the stated one (``bench/precision.BELOW``: ``high`` for ``highest``), put
  in the program's place;
- ``half_batch``, ``token`` and, on several chips, ``no_exchange``: the
  reference with that fault planted (``bench/reference.py``);
- with ``--program``: the trainer's own first calls, as a run makes them in
  set-up (no measured window: training's readings need none).

One JSON line per seed and reading. Runs at the cell's own size, in one
process that holds the cell's chips; the benchmark's runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell, seed: int, program: bool) -> list:
    import jax

    from bench import check, harness
    from bench.precision import BELOW
    out = []
    prec = cell.config["matmul_precision"]
    with jax.default_matmul_precision(prec):
        prog = None
        if program:
            st = harness.Setup(cell, seed)
            raw, s, init, prog = st.raw, st.s, st.init, st.prog
            st.free()
            del st
        else:
            raw = harness.make_data(cell.config, seed)
            s = harness.derived_seed(seed)
            model = cell.model()
            vocab = raw["num_features"]
            init = jax.jit(lambda key: model.init_params(key, cell.config,
                                                         vocab))
        truth = harness.run_reference(cell, raw, s, init)
        variants = {"half_batch": {"fault": "half_batch"},
                    "token": {"fault": "token"}}
        if cell.chips > 1:
            variants["no_exchange"] = {"fault": "no_exchange"}
        got = {k: harness.run_reference(cell, raw, s, init, **kw)
               for k, kw in variants.items()}
        if prog is not None:
            got["program"] = prog
    with jax.default_matmul_precision(BELOW[prec]):
        got["control"] = harness.run_reference(cell, raw, s, init)
    for k, v in got.items():
        out.append({"seed": seed, "reading": k, **check.gaps(v, truth)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "perfbench"), str(ROOT / "src")]
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    from bench.harness import NoChip, require_chips
    from bench.manifest import load_cell
    cell = load_cell(args.workload)
    try:
        # the reference runs on one chip; only the program needs the cell's
        require_chips(cell.chips if args.program else 1)
    except NoChip as e:
        sys.exit(f"control.py: {e}")
    for seed in args.seeds:
        for line in readings(cell, seed, args.program):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
