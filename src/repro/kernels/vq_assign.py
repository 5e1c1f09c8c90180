"""Pallas TPU kernel: fused nearest-centroid assignment.

Computes argmin_j ||x - c_j||^2 over centroid tiles with a running
(min, argmin) kept in VMEM scratch — only the final index/value leave the
core (HBM write O(n) instead of the O(n·c) distance matrix). The distance is
reassociated to the one-GEMM form ||c||^2 - 2<x,c> (+ ||x||^2 outside).

Grid: (points/BN, centroids/BC); the centroid dim is sequential
("arbitrary") so the scratch accumulates across tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BN = 512
DEFAULT_BC = 512


def _vq_assign_kernel(x_ref, c_ref, cn_ref, idx_ref, val_ref,
                      best_val, best_idx, *, bc: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_val[...] = jnp.full_like(best_val, jnp.inf)
        best_idx[...] = jnp.zeros_like(best_idx)

    x = x_ref[...]                                            # (BN, d)
    c = c_ref[...]                                            # (BC, d)
    cn = cn_ref[...]                                          # (1, BC) — +inf padded
    scores = cn - 2.0 * jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                   # (BN, BC)
    local_idx = jnp.argmin(scores, axis=-1)                   # (BN,)
    local_val = jnp.min(scores, axis=-1)
    gidx = (j * bc + local_idx).astype(jnp.int32)
    better = local_val < best_val[:, 0]
    best_val[...] = jnp.where(better, local_val, best_val[:, 0])[:, None]
    best_idx[...] = jnp.where(better, gidx, best_idx[:, 0])[:, None]

    @pl.when(j == pl.num_programs(1) - 1)
    def _write():
        idx_ref[...] = best_idx[...]
        val_ref[...] = best_val[...]


@functools.partial(jax.jit, static_argnames=("bn", "bc", "interpret"))
def vq_assign_pallas(X, C, bn: int = DEFAULT_BN, bc: int = DEFAULT_BC,
                     interpret: bool = True):
    """X (n, d), C (c, d) → (idx (n,) int32, sqdist (n,) f32)."""
    n, d = X.shape
    c = C.shape[0]
    npad = (-n) % bn
    cpad = (-c) % bc
    Xp = jnp.pad(X.astype(jnp.float32), ((0, npad), (0, 0)))
    Cp = jnp.pad(C.astype(jnp.float32), ((0, cpad), (0, 0)))
    cn = jnp.sum(C * C, axis=-1).astype(jnp.float32)
    cn = jnp.pad(cn, (0, cpad), constant_values=jnp.inf)[None, :]  # (1, cp)
    grid = (Xp.shape[0] // bn, Cp.shape[0] // bc)
    idx, val = pl.pallas_call(
        functools.partial(_vq_assign_kernel, bc=bc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bc, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Xp.shape[0], 1), jnp.int32),
            jax.ShapeDtypeStruct((Xp.shape[0], 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(Xp, Cp, cn)
    xn = jnp.sum(X * X, axis=-1)
    return idx[:n, 0], val[:n, 0] + xn
