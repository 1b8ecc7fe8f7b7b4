"""Production federated-training launcher.

Builds the mesh (host-sized by default, production 16x16 / 2x16x16 under
--fake-devices for rehearsal), installs sharding rules, constructs the
FedSubAvg round step for the chosen architecture and runs rounds over a
federated corpus. On the real pod this same entry point runs per host under
the usual multi-host jax.distributed bring-up.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2_5_14b \
        --scale tiny --rounds 50
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.common.compile_cache import use_compile_cache
from repro.configs import FedConfig, get_config, get_smoke_config
from repro.data import make_lm_federated
from repro.federated import make_round_step
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.sharding.context import set_rules
from repro.sharding.rules import make_rules
from repro.common.pytree import tree_size

SCALES = {
    # overrides applied to the arch config for CPU-runnable scales
    "tiny": dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
                 head_dim=32, d_ff=256, vocab_size=2048, dtype="float32",
                 query_chunk=64, kv_chunk=64, num_patches=8, encoder_seq=64,
                 encoder_layers=2, mrope_sections=(4, 6, 6)),
    "100m": dict(num_layers=8, d_model=512, num_heads=8, num_kv_heads=4,
                 head_dim=64, d_ff=1408, vocab_size=8192, dtype="float32",
                 query_chunk=128, kv_chunk=128, num_patches=16, encoder_seq=128,
                 encoder_layers=8, mrope_sections=(8, 12, 12)),
    "full": {},
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_5_14b")
    ap.add_argument("--scale", default="tiny", choices=list(SCALES))
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--clients", type=int, default=128)
    ap.add_argument("--cohort", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--algorithm", default="fedsubavg")
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if SCALES[args.scale]:
        cfg = cfg.replace(**SCALES[args.scale])

    mesh = make_host_mesh()
    set_rules(mesh, make_rules("train"))

    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    print(f"arch={cfg.name} scale={args.scale} params={tree_size(params)/1e6:.1f}M "
          f"mesh={dict(mesh.shape)}")

    ds = make_lm_federated(num_clients=args.clients, vocab=cfg.vocab_size,
                           seq_len=args.seq, samples_per_client=4)
    fed = FedConfig(num_clients=ds.num_clients, clients_per_round=args.cohort,
                    lr=args.lr, algorithm=args.algorithm)
    step = jax.jit(make_round_step(api.loss, params, fed, mode="fedsgd",
                                   correct=args.algorithm == "fedsubavg"))
    heat = jnp.asarray(ds.heat.counts, jnp.float32)
    rng = np.random.default_rng(0)
    t0 = time.time()
    for r in range(args.rounds):
        ids = rng.choice(ds.num_clients, size=args.cohort, replace=False)
        sample = rng.integers(0, ds.client_data["tokens"].shape[1], args.cohort)
        toks = ds.client_data["tokens"][ids, sample]
        params, metrics = step(params, {"tokens": jnp.asarray(toks),
                                        "heat_vocab": heat})
        if (r + 1) % 10 == 0:
            # repro-lint: ok traced-float -- host driver loop; the loss sync
            # happens once per 10 rounds for progress reporting
            print(f"round {r+1:4d} loss={float(metrics['loss']):.4f} "
                  f"{(time.time()-t0)/(r+1):.2f}s/round", flush=True)
    if args.ckpt:
        save_checkpoint(args.ckpt, params, step=args.rounds,
                        extra={"arch": cfg.name})
        print("checkpoint:", args.ckpt)


if __name__ == "__main__":
    main()
