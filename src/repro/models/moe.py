"""Mixture-of-Experts MLP — sort-based (permutation) dispatch with explicit
expert parallelism.

Two execution paths:

- `_moe_dense` (no mesh / tests): single-device sort-scatter-compute-combine.
- `_moe_shardmap` (mesh active): expert parallelism done EXPLICITLY with
  shard_map. Activations are replicated over the "model" axis (they're
  sharded over batch→data only), so each model shard already holds every
  local token: it routes, keeps only the slots belonging to its E/ep local
  experts, runs its expert GEMMs, and contributes a partial output — merged
  by ONE psum per MoE layer (the same collective cost as a Megatron TP MLP;
  no all-to-all, no token send buffers).

  Why not GSPMD-auto: the global argsort/scatter in the dense path makes the
  partitioner materialize all-gathered token buffers (measured: 41 GiB peak
  and a 289 s collective term for qwen3-moe train_4k — see EXPERIMENTS.md
  §Perf iteration 1). The shard_map version is the production path.

Dispatch: tokens' top-k expert slots are stable-sorted by expert id; each
expert processes a fixed capacity C = ceil(T*k/E * capacity_factor) slots
(overflow dropped, standard practice). Everything is static-shaped.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import get_logical_rules, shard
from repro.models.params import ParamDef


def moe_def(cfg) -> dict:
    # expert dim carries the EP ("model") axis; the per-expert ff dim uses
    # its own logical name ("expert_mlp" → unsharded) since a mesh axis can
    # appear at most once per tensor. The router is replicated (d×E is tiny
    # and every shard needs the full routing decision).
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    return {
        "router": ParamDef((d, e), ("embed", None)),
        "wi": ParamDef((e, d, 2, f), ("expert", "embed", None, "expert_mlp")),
        "wo": ParamDef((e, f, d), ("expert", "expert_mlp", "embed")),
    }


def _route(router, xt, k):
    """Top-k routing with renormalized gates. xt: (T, d)."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        router.astype(jnp.float32))
    gates, eidx = jax.lax.top_k(logits, k)
    return jax.nn.softmax(gates, axis=-1), eidx


def _expert_compute(p, xe, dt):
    """(E?, cap, d) → (E?, cap, d) through the gated expert MLP."""
    h = jnp.einsum("ecd,edgf->ecgf", xe, p["wi"].astype(dt))
    h = jax.nn.silu(h[:, :, 0, :]) * h[:, :, 1, :]
    return jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(dt))


def _dispatch_compute_combine(p, xt, gates, eidx, e_lo, E_local, cap, dt):
    """Sort slots by (local) expert, capacity-drop, compute, scatter-add.

    e_lo/E_local select this shard's expert range ([0, E) on 1 device).
    """
    T, d = xt.shape
    k = eidx.shape[1]
    flat_e = eidx.reshape(-1)
    flat_g = gates.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    le = flat_e - e_lo
    mine = (le >= 0) & (le < E_local)
    le = jnp.where(mine, le, E_local)                  # trash bucket
    order = jnp.argsort(le, stable=True)
    se, sg, stok = le[order], flat_g[order], flat_t[order]
    counts = jnp.bincount(se, length=E_local + 1)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(T * k) - starts[se]
    keep = (pos_in_e < cap) & (se < E_local)
    slot = jnp.where(keep, se * cap + pos_in_e, E_local * cap)

    buf = jnp.zeros((E_local * cap + 1, d), dt).at[slot].set(
        xt[stok].astype(dt))
    ye = _expert_compute(p, buf[:E_local * cap].reshape(E_local, cap, d), dt)
    yflat = ye.reshape(E_local * cap, d)
    yslot = jnp.where(keep[:, None],
                      yflat[jnp.minimum(slot, E_local * cap - 1)], 0.0)
    return jnp.zeros((T, d), dt).at[stok].add(yslot * sg[:, None].astype(dt))


def _moe_dense(p, x, cfg):
    dt = x.dtype
    B, S, d = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.experts_per_token
    cap = int((T * k * cfg.capacity_factor) // E + 1)
    xt = x.reshape(T, d)
    gates, eidx = _route(p["router"], xt, k)
    out = _dispatch_compute_combine(p, xt, gates, eidx, 0, E, cap, dt)
    return out.reshape(B, S, d)


def _moe_shardmap(p, x, cfg, mesh, rules):
    dt = x.dtype
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    exp_ax = rules["expert"]
    ep = mesh.shape[exp_ax]
    assert E % ep == 0, (E, ep)
    E_local = E // ep
    batch_ax = rules.get("batch")

    in_specs = (
        {"router": P(), "wi": P(exp_ax), "wo": P(exp_ax)},
        P(batch_ax, None, None),
    )
    out_specs = P(batch_ax, None, None)

    def body(pp, xs):
        Bl, Sl, _ = xs.shape
        T = Bl * Sl
        cap = int((T * k * cfg.capacity_factor) // E + 1)
        xt = xs.reshape(T, d)
        gates, eidx = _route(pp["router"], xt, k)
        e_lo = jax.lax.axis_index(exp_ax) * E_local
        out = _dispatch_compute_combine(pp, xt, gates, eidx, e_lo, E_local,
                                        cap, dt)
        out = jax.lax.psum(out, exp_ax)
        return out.reshape(Bl, Sl, d)

    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(p, x)


def moe_mlp(p, x, cfg):
    """x: (B, S, d) → (B, S, d)."""
    rules = get_logical_rules()
    if rules.get("expert"):
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is not None and not mesh.empty and rules["expert"] in mesh.shape:
            out = _moe_shardmap(p, x, cfg, mesh, rules)
            return shard(out, "batch", None, "act_embed")
    return shard(_moe_dense(p, x, cfg), "batch", None, "act_embed")


def moe_aux_loss(p, x, cfg):
    """Load-balancing auxiliary loss (Switch-style): E * sum_e f_e * p_e."""
    T = x.shape[0] * x.shape[1]
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32)).reshape(T, -1)
    probs = jax.nn.softmax(logits, axis=-1)
    _, eidx = jax.lax.top_k(logits, cfg.experts_per_token)
    f = jnp.mean(jax.nn.one_hot(eidx, cfg.n_experts).sum(1), axis=0)
    pbar = jnp.mean(probs, axis=0)
    return cfg.n_experts * jnp.sum(f * pbar)
