"""Model assembly: pattern-grouped blocks, scanned over the group axis.

Params layout: {"embed": ..., "head": ..., "final_norm": ...,
                "groups": {pos{i}_{name}: leaf_stacked_over_groups}}
HLO size is O(len(block_pattern)), independent of depth — an 88-layer model
lowers as fast as a 2-layer one (see DESIGN.md §6).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import params as prm
from repro.models.attention import (attention_block, attn_def, init_cache_def,
                                    KVCache)
from repro.models.config import ModelConfig
from repro.models.layers import (embed, embed_def, head_def, mlp, mlp_def,
                                 rmsnorm, rmsnorm_def, shard, softmax_xent)
from repro.models.moe import moe_def, moe_mlp
from repro.models.ssm import (mamba_block, mamba_def, mamba_state_def,
                              mlstm_block, mlstm_def, mlstm_state_def,
                              slstm_block, slstm_def, slstm_state_def)

MIXER_DEFS = {"attn": attn_def, "mamba": mamba_def,
              "mlstm": mlstm_def, "slstm": slstm_def}
STATE_DEFS = {"attn": init_cache_def, "mamba": lambda c, b: mamba_state_def(c, b),
              "mlstm": lambda c, b: mlstm_state_def(c, b),
              "slstm": lambda c, b: slstm_state_def(c, b)}


def _has_mlp(cfg: ModelConfig, pos: int) -> bool:
    return cfg.mlp != "none" and (cfg.d_ff > 0 or pos in cfg.moe_positions)


def group_defs(cfg: ModelConfig) -> Dict[str, Any]:
    """Param defs for ONE group (one pass of block_pattern)."""
    defs: Dict[str, Any] = {}
    for i, kind in enumerate(cfg.block_pattern):
        defs[f"pos{i}_norm1"] = rmsnorm_def(cfg.d_model)
        defs[f"pos{i}_{kind}"] = MIXER_DEFS[kind](cfg)
        if _has_mlp(cfg, i):
            defs[f"pos{i}_norm2"] = rmsnorm_def(cfg.d_model)
            if i in cfg.moe_positions:
                defs[f"pos{i}_moe"] = moe_def(cfg)
            else:
                defs[f"pos{i}_mlp"] = mlp_def(cfg, cfg.d_ff)
    return defs


def model_defs(cfg: ModelConfig) -> Dict[str, Any]:
    g = group_defs(cfg)
    stacked = jax.tree.map(
        lambda d: prm.ParamDef((cfg.n_groups,) + d.shape, (None,) + d.axes,
                               d.init, d.scale),
        g, is_leaf=lambda x: isinstance(x, prm.ParamDef))
    defs = {"groups": stacked, "final_norm": rmsnorm_def(cfg.d_model)}
    if cfg.frontend != "audio":
        defs["embed"] = embed_def(cfg)
    defs["head"] = head_def(cfg)
    if cfg.frontend == "audio":
        defs["in_proj"] = {"w": prm.ParamDef(
            (cfg.d_model, cfg.d_model), ("embed", None))}
    return defs


def abstract_params(cfg: ModelConfig):
    return prm.abstract(model_defs(cfg), dtype=jnp.dtype(cfg.param_dtype))


def init_params(key, cfg: ModelConfig):
    return prm.init(key, model_defs(cfg), dtype=jnp.dtype(cfg.param_dtype))


def param_pspecs(cfg: ModelConfig, rules: dict):
    return prm.pspecs(model_defs(cfg), rules)


# ----------------------------------------------------------------- caches

def cache_defs(cfg: ModelConfig, batch: int, max_seq: int):
    """Decode-state ShapeDtypeStructs, stacked over groups."""
    out = {}
    for i, kind in enumerate(cfg.block_pattern):
        if kind == "attn":
            st = init_cache_def(cfg, batch, max_seq)
        else:
            st = STATE_DEFS[kind](cfg, batch)
        out[f"pos{i}_{kind}"] = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((cfg.n_groups,) + s.shape, s.dtype), st)
    return out


def cache_pspecs(cfg: ModelConfig, batch: int, max_seq: int, rules: dict):
    """PartitionSpecs for the decode cache (KV seq-sharded; states sharded
    on their wide dim)."""
    from jax.sharding import PartitionSpec as P

    def kv_spec(_):
        return P(None, rules.get("batch"), rules.get("kv_seq"), None, None)

    out = {}
    for i, kind in enumerate(cfg.block_pattern):
        if kind == "attn":
            st = init_cache_def(cfg, batch, max_seq)
            out[f"pos{i}_{kind}"] = jax.tree.map(kv_spec, st)
        elif kind == "mamba":
            st = mamba_state_def(cfg, batch)
            out[f"pos{i}_{kind}"] = type(st)(
                P(None, rules.get("batch"), None, rules.get("mlp")),
                P(None, rules.get("batch"), rules.get("mlp"), None))
        elif kind == "mlstm":
            st = mlstm_state_def(cfg, batch)
            out[f"pos{i}_{kind}"] = type(st)(
                P(None, rules.get("batch"), rules.get("heads"), rules.get("head"), None),
                P(None, rules.get("batch"), rules.get("heads"), None),
                P(None, rules.get("batch"), rules.get("heads")))
        else:  # slstm — (head × block) sub-heads sharded over "shead"
            st = slstm_state_def(cfg, batch)
            out[f"pos{i}_{kind}"] = jax.tree.map(
                lambda s: P(None, rules.get("batch"), rules.get("shead"),
                            None), st)
    return out


# ---------------------------------------------------------------- forward

def _cast_big_params(groups, cfg: ModelConfig):
    """Cast large stacked weight tensors to the compute dtype BEFORE the
    group scan (§Perf H-cast): otherwise the per-iteration FSDP all-gather /
    HBM read moves fp32 master weights — measured 2× the necessary weight
    traffic on mistral-123b train and xlstm train. Small leaves (norm
    scales, gates, SSM A/conv) stay fp32 for precision."""
    dt = jnp.dtype(cfg.compute_dtype)
    if dt == jnp.float32:
        return groups
    out = jax.tree.map(
        lambda a: a.astype(dt)
        if (a.dtype == jnp.float32 and a.ndim >= 3 and a.size > 1_000_000)
        else a, groups)
    # Without the barrier XLA undoes the optimization: it keeps the fp32
    # buffer and rematerializes the (cheap) convert inside the scan body,
    # re-reading fp32 every iteration (measured: no traffic change).
    return jax.lax.optimization_barrier(out)


def _apply_group(gp, x, positions, cfg, mask_mode, states, cache_index):
    """One pass of block_pattern. states: dict pos{i}_{kind} → state or None."""
    new_states = {}
    for i, kind in enumerate(cfg.block_pattern):
        h = rmsnorm(gp[f"pos{i}_norm1"], x, cfg.norm_eps)
        key = f"pos{i}_{kind}"
        st = states.get(key) if states else None
        if kind == "attn":
            mix, new_st = attention_block(gp[key], h, positions, cfg,
                                          mask_mode, st, cache_index)
        elif kind == "mamba":
            mix, new_st = mamba_block(gp[key], h, cfg, st)
        elif kind == "mlstm":
            mix, new_st = mlstm_block(gp[key], h, cfg, st)
        else:
            mix, new_st = slstm_block(gp[key], h, cfg, st)
        x = x + mix
        new_states[key] = new_st
        if _has_mlp(cfg, i):
            h2 = rmsnorm(gp[f"pos{i}_norm2"], x, cfg.norm_eps)
            if i in cfg.moe_positions:
                x = x + moe_mlp(gp[f"pos{i}_moe"], h2, cfg)
            else:
                x = x + mlp(gp[f"pos{i}_mlp"], h2, cfg)
        x = shard(x, "batch", None, "act_embed")
    return x, new_states


def _embed_inputs(params, inputs, cfg: ModelConfig):
    """Returns (x (B,S,d), mask_mode)."""
    dt = jnp.dtype(cfg.compute_dtype)
    if cfg.frontend == "audio":
        x = jnp.einsum("bsd,de->bse", inputs["frames"].astype(dt),
                       params["in_proj"]["w"].astype(dt))
        return shard(x, "batch", None, "act_embed"), "full"
    tok_emb = embed(params["embed"], inputs["tokens"], cfg)
    if cfg.frontend == "vision":
        x = jnp.concatenate([inputs["patches"].astype(dt), tok_emb], axis=1)
        return shard(x, "batch", None, "act_embed"), "prefix"
    return tok_emb, "causal" if cfg.causal else "full"


def forward(params, inputs, cfg: ModelConfig, caches=None, cache_index=None,
            collect_states: bool = False):
    """Sequence-mode forward. Returns (hidden (B,S,d), states-per-group)."""
    x, mask_mode = _embed_inputs(params, inputs, cfg)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(xc, gp):
        xc, states = _apply_group(gp, xc, positions, cfg, mask_mode,
                                  None, None)
        return xc, (states if collect_states else 0)

    if cfg.remat == "block":
        body = jax.checkpoint(body)
    x, states = jax.lax.scan(body, x, _cast_big_params(params["groups"], cfg))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, states


def logits_from_hidden(params, x, cfg: ModelConfig):
    logits = jnp.einsum("bsd,dv->bsv", x, params["head"]["w"].astype(x.dtype))
    if cfg.logit_softcap > 0:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return shard(logits, "batch", None, "vocab")


def loss_fn(params, batch, cfg: ModelConfig):
    """Next-token (causal) or frame-classification (encoder) loss."""
    x, _ = forward(params, batch, cfg)
    logits = logits_from_hidden(params, x, cfg)
    labels = batch["labels"]
    if cfg.frontend == "vision":                # loss over text positions only
        logits = logits[:, cfg.n_prefix_embeds:, :]
    losses = softmax_xent(logits, labels, cfg.vocab_size)
    return jnp.mean(losses)


# ------------------------------------------------------------------ serve

def prefill(params, inputs, cfg: ModelConfig, max_seq: int):
    """Run the full prompt; returns (last-token logits, decode caches).

    For attention layers the per-segment K/V (already computed by the
    forward) are placed into max_seq-sized cache buffers.
    """
    x, mask_mode = _embed_inputs(params, inputs, cfg)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(xc, gp):
        xc, states = _apply_group(gp, xc, positions, cfg, mask_mode,
                                  None, None)
        return xc, states

    x, states = jax.lax.scan(body, x, _cast_big_params(params["groups"], cfg))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_from_hidden(params, x[:, -1:, :], cfg)

    # expand attention segment-caches to max_seq buffers
    caches = {}
    for key, st in states.items():
        if isinstance(st, KVCache):
            pad = max_seq - st.k.shape[2]
            cdt = jnp.dtype(cfg.cache_dtype)
            caches[key] = KVCache(
                shard(jnp.pad(st.k.astype(cdt),
                              ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
                      None, "batch", "kv_seq", "kv_heads", None),
                shard(jnp.pad(st.v.astype(cdt),
                              ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
                      None, "batch", "kv_seq", "kv_heads", None))
        else:
            caches[key] = st
    return logits, caches


def decode_step(params, token, caches, index, cfg: ModelConfig):
    """One decode step. token (B, 1) int32; index: scalar int32 position.

    caches: dict pos{i}_{kind} → state stacked over groups (leading G).
    Returns (logits (B, 1, vocab), new caches).

    The cache stack travels as the scan CARRY (not xs/ys): while-loop
    carries are buffer-aliased in place by XLA, so with donated inputs the
    multi-GB KV cache is updated without a second copy. The per-group
    slice/update runs on the UNSHARDED group dim with the loop counter —
    the SPMD-safe pattern (the seq-dim write inside uses a one-hot select,
    see attention.py).
    """
    x = embed(params["embed"], token, cfg) if cfg.frontend != "audio" else None
    B = token.shape[0]
    positions = jnp.broadcast_to(index, (B, 1)).astype(jnp.int32)

    def body(carry, gp):
        xc, stack, gidx = carry
        st = jax.tree.map(
            lambda c: jax.lax.dynamic_index_in_dim(c, gidx, 0, keepdims=False),
            stack)
        xc, new_st = _apply_group(gp, xc, positions, cfg, "causal", st, index)
        stack = jax.tree.map(
            lambda c, n: jax.lax.dynamic_update_index_in_dim(
                c, n.astype(c.dtype), gidx, 0),
            stack, new_st)
        return (xc, stack, gidx + 1), None

    (x, new_caches, _), _ = jax.lax.scan(
        body, (x, caches, jnp.asarray(0, jnp.int32)),
        _cast_big_params(params["groups"], cfg))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return logits_from_hidden(params, x, cfg), new_caches
