"""LM decode serving: `ServeEngine` (batched prefill + greedy decode over
the model zoo), the `make_serve_step` the decode dry-run cells lower, and
a launcher reporting tokens/s:

    python -m repro.launch.serve --arch granite-3-2b --batch 4 --new 32
(CPU container → smoke config; on TPU pods the full config + production mesh.)
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.data.pipeline import for_model
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.utils import enable_compile_cache


def make_serve_step(cfg: ModelConfig):
    """fn(params, token (B,1), caches, index) → (next_token (B,1), caches)."""

    def serve_step(params, token, caches, index):
        logits, caches = T.decode_step(params, token, caches, index, cfg)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return nxt[:, None], caches

    return serve_step


def make_prefill_step(cfg: ModelConfig, max_seq: int):
    def prefill_step(params, inputs):
        return T.prefill(params, inputs, cfg, max_seq=max_seq)
    return prefill_step


class ServeEngine:
    """Minimal batched greedy-decoding engine (CPU-runnable at smoke scale)."""

    def __init__(self, cfg: ModelConfig, params, max_seq: int = 256):
        self.cfg = cfg
        self.params = params
        self.max_seq = max_seq
        self._prefill = jax.jit(make_prefill_step(cfg, max_seq))
        self._step = jax.jit(make_serve_step(cfg), donate_argnums=(2,))

    def generate(self, inputs: dict, n_new: int):
        """inputs: {"tokens": (B, S)} (+ patches for vlm). Greedy decode."""
        logits, caches = self._prefill(self.params, inputs)
        tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
        prefix = (self.cfg.n_prefix_embeds
                  if self.cfg.frontend == "vision" else 0)
        start = inputs["tokens"].shape[1] + prefix
        out = [tok]
        for i in range(n_new - 1):
            tok, caches = self._step(self.params, tok, caches,
                                     jnp.asarray(start + i, jnp.int32))
            out.append(tok)
        return jnp.concatenate(out, axis=1)


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
