"""Serving launcher: batched prefill + greedy decode, reporting tokens/s.

    python -m repro.launch.serve --arch granite-3-2b --batch 4 --new 32
(CPU container → smoke config; on TPU pods the full config + production mesh.)
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.data.pipeline import for_model
from repro.models import transformer as T
from repro.serve.engine import ServeEngine
from repro.utils import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b", choices=list(ARCH_IDS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new", type=int, default=32)
    args = ap.parse_args()

    cfg = get_config(args.arch).smoke_config()
    if not cfg.has_decode:
        raise SystemExit(f"{args.arch} is encoder-only: no decode path")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    pipe = for_model(cfg, seq_len=args.prompt_len, global_batch=args.batch)
    inputs = {k: v for k, v in pipe.batch_at(0).items() if k != "labels"}

    engine = ServeEngine(cfg, params,
                         max_seq=args.prompt_len + args.new
                         + cfg.n_prefix_embeds)
    t0 = time.time()
    out = engine.generate(inputs, n_new=args.new)
    dt = time.time() - t0
    toks = args.batch * args.new
    print(f"arch={cfg.name} generated {out.shape} in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s, incl. compile)")
    print("sample:", np.asarray(out[0][:16]))


if __name__ == "__main__":
    main()
