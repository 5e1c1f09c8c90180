"""Production training launcher.

    # real cluster (TPU pods): full config on the production mesh
    python -m repro.launch.train --arch granite-3-2b --mesh single

    # this container (1 CPU device): reduced config, same code path
    python -m repro.launch.train --arch granite-3-2b --mesh cpu --steps 50

Flags demonstrate the distributed-optimization features:
  --accum N           gradient-accumulation microbatching (compute/comm overlap)
  --no-fsdp           disable ZeRO-style param sharding over "data"
(int8 error-feedback gradient reduction lives in train/grad_compress.py,
validated in tests/test_grad_compress.py for the cross-pod reduce.)
"""
from __future__ import annotations

import argparse
import os


from repro.ckpt.checkpoint import CheckpointManager
from repro.configs import ARCH_IDS, get_config, get_rule_overrides
from repro.data.pipeline import for_model
from repro.launch.mesh import build_rules, make_production_mesh
from repro.models.layers import set_logical_rules
from repro.train.train_loop import train
from repro.utils import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b", choices=list(ARCH_IDS))
    ap.add_argument("--mesh", default="cpu", choices=["cpu", "single", "multi"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--no-fsdp", action="store_true")
    args = ap.parse_args()

    if args.mesh == "cpu":
        cfg = get_config(args.arch).smoke_config()
        seq = 64 if args.seq is None else args.seq
        batch = 8 if args.batch is None else args.batch
        ctx = None
    else:
        cfg = get_config(args.arch)
        seq = 4096 if args.seq is None else args.seq
        batch = 256 if args.batch is None else args.batch
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
        rules = build_rules(get_rule_overrides(args.arch),
                            multi_pod=(args.mesh == "multi"),
                            batch_size=batch)
        if args.no_fsdp:
            rules["embed"] = None
        set_logical_rules(rules)
        ctx = jax.set_mesh(mesh)

    # XLA flags a real run would set for collective/compute overlap
    os.environ.setdefault(
        "LIBTPU_INIT_ARGS",
        "--xla_tpu_enable_async_collective_fusion=true "
        "--xla_tpu_enable_latency_hiding_scheduler=true")

    pipe = for_model(cfg, seq_len=seq, global_batch=batch, mode="markov")
    mgr = CheckpointManager(os.path.join(args.ckpt_dir, cfg.name))
    if ctx is not None:
        with ctx:
            train(cfg, pipe, steps=args.steps, lr=args.lr, accum=args.accum,
                  ckpt_manager=mgr, ckpt_every=args.ckpt_every)
    else:
        train(cfg, pipe, steps=args.steps, lr=args.lr, accum=args.accum,
              ckpt_manager=mgr, ckpt_every=args.ckpt_every)


if __name__ == "__main__":
    main()
