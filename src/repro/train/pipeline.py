"""Pipeline parallelism over the "pod" axis — GPipe-style microbatch
pipelining expressed with shard_map + lax.ppermute.

Each pod is one stage holding half the layer groups. All stages run the
same program; activations flow stage→stage through a differentiable
ppermute (its transpose is the reverse permute, so jax.grad generates the
reverse pipeline automatically). The schedule is the classic loop-pipeline:
steps = M + n_stages − 1; stage s works on microbatch t − s at step t, with
validity masks for the fill/drain bubbles.

This is the optional `--pipeline` path (DESIGN.md §6): the cross-pod
traffic per step is one (micro_B, S, d) activation instead of the full
gradient all-reduce, which is the right trade when inter-pod bandwidth is
the binding constraint. Validated bit-for-bit against the non-pipelined
model in tests/test_pipeline.py.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.layers import rmsnorm, softmax_xent


def stack_stage_params(params, cfg: ModelConfig, n_stages: int = 2):
    """Split the group stack into per-stage halves and stack EVERYTHING over
    a leading stage dim (each stage receives its own slice via shard_map).
    Non-group params (embed/head/final_norm) are replicated per stage; only
    stage 0 uses embed, only the last stage uses head/final_norm."""
    G = cfg.n_groups
    assert G % n_stages == 0
    per = G // n_stages

    def split_groups(a):
        return a.reshape((n_stages, per) + a.shape[1:])

    stacked = {
        "groups": jax.tree.map(split_groups, params["groups"]),
        "final_norm": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_stages,) + a.shape),
            params["final_norm"]),
        "head": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_stages,) + a.shape),
            params["head"]),
        "embed": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_stages,) + a.shape),
            params["embed"]),
    }
    return stacked


def make_pipelined_loss(cfg: ModelConfig, mesh, n_stages: int = 2,
                        stage_axis: str = "pod"):
    """Returns fn(stage_params, batch) → mean loss.

    batch tokens/labels: (M, micro_B, S) — M microbatches.
    """

    def stage_forward(gp, x):
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1], dtype=jnp.int32), x.shape[:2])

        def body(xc, g):
            xc, _ = T._apply_group(g, xc, positions, cfg, "causal",
                                   None, None)
            return xc, 0

        x, _ = jax.lax.scan(body, x, gp)
        return x

    def pipelined(stage_params, tokens, labels):
        # inside shard_map: leading stage dim is 1 — squeeze it
        sp = jax.tree.map(lambda a: a[0], stage_params)
        stage = jax.lax.axis_index(stage_axis)
        M, mb, S = tokens.shape
        steps = M + n_stages - 1
        d = cfg.d_model
        dt = jnp.dtype(cfg.compute_dtype)

        def step(carry, t):
            recv, loss_sum, n_loss = carry
            # stage 0 ingests microbatch t (clamped; masked when invalid)
            tok_t = jax.lax.dynamic_index_in_dim(
                tokens, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            x0 = T.embed(sp["embed"], tok_t, cfg)
            x_in = jnp.where(stage == 0, x0.astype(dt), recv.astype(dt))
            y = stage_forward(sp["groups"], x_in)
            # last stage: loss for microbatch t-(n_stages-1)
            mb_idx = t - (n_stages - 1)
            lbl = jax.lax.dynamic_index_in_dim(
                labels, jnp.clip(mb_idx, 0, M - 1), 0, keepdims=False)
            h = rmsnorm(sp["final_norm"], y, cfg.norm_eps)
            logits = jnp.einsum("bsd,dv->bsv", h,
                                sp["head"]["w"].astype(h.dtype))
            losses = softmax_xent(logits, lbl, cfg.vocab_size)
            valid = ((stage == n_stages - 1) & (mb_idx >= 0)
                     & (mb_idx < M)).astype(jnp.float32)
            loss_sum = loss_sum + valid * jnp.mean(losses)
            n_loss = n_loss + valid
            # hand activations to the next stage (cyclic; last→0 is unused)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            sent = jax.lax.ppermute(y, stage_axis, perm)
            return (sent, loss_sum, n_loss), None

        init = (jnp.zeros((mb, S, d), dt), jnp.zeros(()), jnp.zeros(()))
        (_, loss_sum, n_loss), _ = jax.lax.scan(
            step, init, jnp.arange(steps))
        # share the last stage's mean loss with everyone
        total = jax.lax.psum(loss_sum, stage_axis)
        count = jax.lax.psum(n_loss, stage_axis)
        return total / jnp.maximum(count, 1.0)

    return jax.shard_map(
        pipelined, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(stage_axis), {"groups": 0,
                                                         "final_norm": 0,
                                                         "head": 0,
                                                         "embed": 0}),
                  P(), P()),
        out_specs=P(), check_vma=False)


def pipelined_loss_and_grad(cfg: ModelConfig, mesh, stage_params, tokens,
                            labels, n_stages: int = 2):
    fn = make_pipelined_loss(cfg, mesh, n_stages=n_stages)

    def wrapped(sp):
        return fn(sp, tokens, labels)

    return jax.value_and_grad(wrapped)(stage_params)
