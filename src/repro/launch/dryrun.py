import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST be the first statements in this module —
# before any other import — since jax locks the device count on first init.

_DOC = """Multi-pod dry-run: prove every (architecture x input shape x mesh) lowers,
compiles, fits, and extract the roofline inputs — on 512 placeholder host
devices (the two lines above MUST precede any jax import; jax locks the
device count at first init, which is why this env var is set here and only
here, never in conftest/pyproject).

For each combo we lower + compile the real step function:
    train_4k              -> federated round_step (FedSubAvg, fedsgd mode)
    prefill_32k           -> serve prefill
    decode_32k, long_500k -> serve decode_step (1 token vs seq_len KV cache)
and record ``memory_analysis`` (fits?), ``cost_analysis`` (FLOPs / bytes),
and the collective inventory parsed from optimized HLO (loop-aware, see
repro.launch.hlo). Results land in JSON consumed by benchmarks/roofline.py
and EXPERIMENTS.md.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x22b \
        --shape train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] \
        --out results/dryrun
"""

import argparse  # noqa: E402
import gc
import json
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, SHAPES, FedConfig, get_config
from repro.federated.simulation import make_round_step
from repro.launch.hlo import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.shardings import (shard_batch_sds, shard_cache_sds,
                                    shard_params_sds)
from repro.models import build_model
from repro.sharding.context import clear_rules, param_shardings, set_rules
from repro.sharding.rules import make_rules


def pick_remat_groups(num_layers: int, target: int) -> int:
    """Largest-benefit divisor of L for two-level remat: minimise G + L/G
    among divisors near the target (residual memory ~ (G + L/G) activations)."""
    divisors = [g for g in range(2, num_layers) if num_layers % g == 0]
    if not divisors:
        return 0
    return min(divisors, key=lambda g: (g + num_layers // g, abs(g - target)))


def shape_applicable(cfg, shape_name: str) -> Optional[str]:
    """None if applicable, else the reason for the documented skip."""
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        return ("long_500k requires a sub-quadratic path; "
                f"{cfg.name} is full-attention (see DESIGN.md shape coverage)")
    return None


def choose_layout(cfg, hbm_budget_gib: float = 6.0) -> str:
    """auto layout: weight-stationary TP when the model-axis shard of the
    parameters fits comfortably; FSDP (d_model over data) otherwise.

    TP keeps weights resident (collectives = per-layer activation psums);
    FSDP re-gathers weights per layer — cheaper memory, far more collective
    bytes (see EXPERIMENTS.md §Perf iteration 6).
    """
    shard_gib = cfg.param_counts()["total"] * 2 / 16 / 2**30
    return "tp" if shard_gib <= hbm_budget_gib else "fsdp"


def build_combo(arch: str, shape_name: str, mesh, multi_pod: bool,
                expert_parallel: bool = False, seq_shard_decode: bool = True,
                query_chunk: int = 256, kv_chunk: int = 512,
                microbatches: int = 8, remat_groups: int = 8,
                layout: str = "fsdp"):
    """Returns (fn, args, out_shardings?) ready to lower under the mesh."""
    cfg = get_config(arch)
    # attention chunking is a launch-time memory/perf knob (see §Perf):
    # scores live set per device = B_dev * H * q_chunk * kv_chunk * 4B
    if remat_groups:
        cfg = cfg.replace(remat_groups=pick_remat_groups(cfg.num_layers, remat_groups))
    cfg = cfg.replace(query_chunk=query_chunk, kv_chunk=kv_chunk)
    if cfg.is_moe and SHAPES[shape_name].kind != "train":
        # scan the MoE dispatch in token chunks for serving: the (E, C, d)
        # dispatch buffers otherwise scale with the full 1M-token prefill
        # (47.8 -> 9.1 GiB for mixtral prefill_32k). Kept OFF for training:
        # measured +50% collective bytes through the chunk-scan backward
        # (§Perf pair C addendum).
        cfg = cfg.replace(moe_token_chunk=8192)
    sc = SHAPES[shape_name]
    api = build_model(cfg)
    rules = make_rules(sc.kind, multi_pod=multi_pod,
                       expert_parallel=expert_parallel,
                       seq_shard_decode=seq_shard_decode)
    if layout == "auto":
        layout = choose_layout(cfg)
    if layout == "fsdp":
        # FSDP: shard the d_model dimension of weights across the data axis so
        # 100B+ configs fit HBM (baseline layout; see EXPERIMENTS.md)
        rules = dict(rules, embed=("data",))
    # attention activation head sharding only when the head counts divide the
    # model axis — partial-head layouts force per-chunk all-reduces (§Perf)
    mdl = mesh.shape["model"]
    rules = dict(rules,
                 heads_act=("model",) if cfg.num_heads % mdl == 0 else None,
                 kv_act=("model",) if (cfg.num_kv_heads % mdl == 0
                                       and cfg.num_heads % mdl == 0) else None)
    set_rules(mesh, rules)

    abstract = api.abstract_params()
    params_sds = shard_params_sds(mesh, rules, abstract)
    batch_sds = shard_batch_sds(mesh, rules, api.input_specs(shape_name))
    # out_shardings mirror the (divisibility-fitted) input shardings
    from repro.sharding.logical import is_param
    p_shardings = jax.tree.map(
        lambda p: p.value.sharding if is_param(p) else p.sharding,
        params_sds, is_leaf=is_param)

    if sc.kind == "train":
        fed = FedConfig(num_clients=1_000_000, clients_per_round=sc.global_batch,
                        local_iters=1, lr=1e-2, algorithm="fedsubavg",
                        microbatches=microbatches)
        step = make_round_step(api.loss, abstract, fed, mode="fedsgd", correct=True)
        fn = jax.jit(step, out_shardings=(p_shardings, None))
        args = (params_sds, batch_sds)
    elif sc.kind == "prefill":
        cache = api.init_cache(sc.global_batch, sc.seq_len, abstract=True)
        cache_sds = shard_cache_sds(mesh, rules, cache)
        # donate the cache: serving updates it in place every step
        fn = jax.jit(api.prefill, donate_argnums=(2,))
        args = (params_sds, batch_sds, cache_sds)
    else:  # decode
        cache = api.init_cache(sc.global_batch, sc.seq_len, abstract=True)
        cache_sds = shard_cache_sds(mesh, rules, cache)
        fn = jax.jit(api.decode_step, donate_argnums=(1,))
        args = (params_sds, cache_sds, batch_sds)
    return cfg, fn, args


def run_one(arch: str, shape_name: str, multi_pod: bool = False,
            keep_hlo: bool = False, **build_kw) -> Dict:
    cfg = get_config(arch)
    reason = shape_applicable(cfg, shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    base = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
            "multi_pod": multi_pod}
    if reason:
        return dict(base, status="skipped", reason=reason)

    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        cfg, fn, args = build_combo(arch, shape_name, mesh, multi_pod, **build_kw)
        t0 = time.time()
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        mem_info = {}
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            mem_info[k] = int(getattr(mem, k, 0) or 0)
        cost = compiled.cost_analysis()
        hlo_text = compiled.as_text()
        col = analyze_hlo(hlo_text)
        result = dict(
            base,
            status="ok",
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory=mem_info,
            flops=float(cost.get("flops", 0.0)),
            bytes_accessed=float(cost.get("bytes accessed", 0.0)),
            collectives=col.summary(),
            num_devices=mesh.devices.size,
            params_total=cfg.param_counts()["total"],
            params_active=cfg.param_counts()["active"],
        )
        if keep_hlo:
            result["hlo_len"] = len(hlo_text)
        del compiled, lowered, fn
        gc.collect()
        return result
    except Exception as e:
        return dict(base, status="error", error=f"{type(e).__name__}: {e}",
                    trace=traceback.format_exc()[-2000:])
    finally:
        clear_rules()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--expert-parallel", action="store_true")
    ap.add_argument("--no-seq-shard", action="store_true",
                    help="disable decode KV seq sharding (baseline ablation)")
    ap.add_argument("--layout", default="fsdp", choices=["fsdp", "tp", "auto"])
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    combos = []
    arches = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    pods = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    for a in arches:
        for s in shapes:
            for mp in pods:
                combos.append((a, s, mp))

    results = []
    for a, s, mp in combos:
        r = run_one(a, s, multi_pod=mp, expert_parallel=args.expert_parallel,
                    seq_shard_decode=not args.no_seq_shard, layout=args.layout,
                    microbatches=args.microbatches)
        status = r["status"]
        extra = ""
        if status == "ok":
            per_dev_gb = (r["memory"]["argument_size_in_bytes"]
                          + r["memory"]["temp_size_in_bytes"]) / 2**30
            extra = (f"compile={r['compile_s']}s mem/dev={per_dev_gb:.2f}GiB "
                     f"flops={r['flops']:.3e} coll={r['collectives']['total_collective_bytes']:.3e}B")
        elif status == "error":
            extra = r["error"][:160]
        else:
            extra = r["reason"][:80]
        print(f"[{r['mesh']}] {a:28s} {s:12s} {status:8s} {extra}", flush=True)
        results.append(r)

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        path = args.out if args.out.endswith(".json") else args.out + ".json"
        with open(path, "w") as f:
            json.dump(results, f, indent=1)
        print("wrote", path)

    n_err = sum(1 for r in results if r["status"] == "error")
    if n_err:
        raise SystemExit(f"{n_err} combos failed")


if __name__ == "__main__":
    main()
