"""Pallas TPU kernel: fused SOAR spilled assignment (Theorem 3.1 loss).

loss_ij = ||c_j||^2 - 2<x_i,c_j> + lam*(<rhat_i,x_i> - <rhat_i,c_j>)^2
          (+ ||x_i||^2, constant in j)

Two MXU passes per (point-tile × centroid-tile): X·Cᵀ and R̂·Cᵀ, then
elementwise penalty + primary-exclusion mask + running argmin in VMEM
scratch — the full (n × c) loss matrix never exists in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.kernels import note_route

DEFAULT_BN = 512
DEFAULT_BC = 512


def _soar_kernel(x_ref, rhat_ref, rx_ref, prim_ref, c_ref, cn_ref,
                 idx_ref, val_ref, best_val, best_idx, *, bc: int, lam: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_val[...] = jnp.full_like(best_val, jnp.inf)
        best_idx[...] = jnp.zeros_like(best_idx)

    x = x_ref[...]
    rhat = rhat_ref[...]
    rx = rx_ref[...]                                          # (BN, 1)
    prim = prim_ref[...]                                      # (BN, 1) int32
    c = c_ref[...]
    cn = cn_ref[...]                                          # (1, BC)
    xc = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    rc = jax.lax.dot_general(rhat, c, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    loss = cn - 2.0 * xc + lam * (rx - rc) ** 2               # (BN, BC)
    gids = j * bc + jax.lax.broadcasted_iota(jnp.int32, loss.shape, 1)
    loss = jnp.where(gids == prim, jnp.inf, loss)
    local_idx = jnp.argmin(loss, axis=-1)
    local_val = jnp.min(loss, axis=-1)
    gidx = (j * bc + local_idx).astype(jnp.int32)
    better = local_val < best_val[:, 0]
    best_val[...] = jnp.where(better, local_val, best_val[:, 0])[:, None]
    best_idx[...] = jnp.where(better, gidx, best_idx[:, 0])[:, None]

    @pl.when(j == pl.num_programs(1) - 1)
    def _write():
        idx_ref[...] = best_idx[...]
        val_ref[...] = best_val[...]


@functools.partial(jax.jit,
                   static_argnames=("lam", "bn", "bc", "interpret"))
def soar_assign_pallas(X, rhat, primary, C, lam: float = 1.0,
                       bn: int = DEFAULT_BN, bc: int = DEFAULT_BC,
                       interpret: bool = True):
    """Returns (idx (n,) int32, loss-at-idx (n,) incl. ||x||^2 term)."""
    n, d = X.shape
    c = C.shape[0]
    npad = (-n) % bn
    cpad = (-c) % bc
    Xp = jnp.pad(X.astype(jnp.float32), ((0, npad), (0, 0)))
    Rp = jnp.pad(rhat.astype(jnp.float32), ((0, npad), (0, 0)))
    rx = jnp.sum(rhat * X, axis=-1, keepdims=True).astype(jnp.float32)
    rx = jnp.pad(rx, ((0, npad), (0, 0)))
    prim = jnp.pad(primary.astype(jnp.int32)[:, None], ((0, npad), (0, 0)),
                   constant_values=-1)
    Cp = jnp.pad(C.astype(jnp.float32), ((0, cpad), (0, 0)))
    cn = jnp.sum(C * C, axis=-1).astype(jnp.float32)
    cn = jnp.pad(cn, (0, cpad), constant_values=jnp.inf)[None, :]
    grid = (Xp.shape[0] // bn, Cp.shape[0] // bc)
    idx, val = pl.pallas_call(
        functools.partial(_soar_kernel, bc=bc, lam=lam),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
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
    )(Xp, Rp, rx, prim, Cp, cn)
    xn = jnp.sum(X * X, axis=-1)
    return idx[:n, 0], val[:n, 0] + xn


# --------------------------------------------------------------------------
# Batched/fused primary + spill assignment (the sharded-build hot path)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_spills", "chunk"))
def _fused_assign_gemm(X, C, lam: float, n_spills: int, chunk: int):
    """Chunked fused primary + spill assignment (non-TPU backends).

    Per tile of X: ONE X·Cᵀ GEMM shared by the primary argmin and every
    spill step's distance term (the reassociated two-GEMM loss form of
    core/soar.py); each spill adds one R̂·Cᵀ GEMM and accumulates its
    orthogonality penalty, so the full multi-spill objective of
    `soar_assign_multi` is preserved. Total 1 + n_spills GEMM passes over
    the data vs 2 + 2·n_spills for the unfused train-then-spill sequence.

    The codebook is column-padded to the argmin group width with
    ||c||² = +inf sentinels (never selected) and argmins run through the
    grouped exact reduction of kernels/lloyd.py — identical indices to
    `jnp.argmin` (pinned against the core/soar.py compositions in
    tests/test_build.py), ~1.8x faster on XLA:CPU.
    """
    from repro.kernels.lloyd import ARGMIN_GROUP, _grouped_argmin
    from repro.utils import chunked_map

    c = C.shape[0]
    cpad = (-c) % ARGMIN_GROUP
    Cp = jnp.pad(C, ((0, cpad), (0, 0)))
    Ct = Cp.T
    cn = jnp.pad(jnp.sum(C * C, axis=-1), (0, cpad),
                 constant_values=jnp.inf)

    def f(xb):
        xc = xb @ Ct                                        # shared GEMM
        prim, _ = _grouped_argmin(cn[None, :] - 2.0 * xc)
        assigns = [prim]
        used = jax.nn.one_hot(prim, c + cpad, dtype=bool)
        pen = jnp.zeros_like(xc)
        for _ in range(n_spills):
            r = xb - Cp[assigns[-1]]
            rn = jnp.linalg.norm(r, axis=-1, keepdims=True)
            rhat = r / jnp.maximum(rn, 1e-12)
            rc = rhat @ Ct                                  # one GEMM / spill
            rx = jnp.sum(rhat * xb, axis=-1)
            pen = pen + (rx[:, None] - rc) ** 2
            loss = cn[None, :] - 2.0 * xc + lam * pen
            loss = jnp.where(used, jnp.inf, loss)
            nxt, _ = _grouped_argmin(loss)
            assigns.append(nxt)
            used = used | jax.nn.one_hot(nxt, c + cpad, dtype=bool)
        return jnp.stack(assigns, axis=1)

    return chunked_map(f, X.astype(jnp.float32), chunk)


def assign_fused(X, C, lam: float = 1.0, n_spills: int = 1,
                 chunk: int = 8192, use_pallas: bool = None,
                 interpret: bool = None):
    """Primary + spilled assignment(s) against a FROZEN codebook, fused.

    The sharded build driver (core/build.py) and the incremental-insert
    path (core/mutable.py) both route through here: assignment is the only
    per-point work at build time, so it runs as streamed tiles with nothing
    materialized at O(n × c).

    On TPU (or use_pallas=True) the single-spill case runs the Pallas
    kernel above (two MXU passes per tile, loss matrix never leaves VMEM)
    after a fused `vq_assign` primary pass; multi-spill and other backends
    use the chunked two-GEMM jnp path, which shares the X·Cᵀ GEMM between
    the primary argmin and every spill step.

    Returns (n, 1 + n_spills) int32 assignments, column 0 primary.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = kernels.interpret_mode()
    X = jnp.asarray(X, jnp.float32)
    C = jnp.asarray(C, jnp.float32)
    if n_spills == 0:
        from repro.utils import pairwise_neg_sqdist_argmin
        note_route("assign_fused", "xla")
        prim, _ = pairwise_neg_sqdist_argmin(X, C, chunk=chunk)
        return prim[:, None]
    if not use_pallas or n_spills > 1:
        note_route("assign_fused", "xla")
        return _fused_assign_gemm(X, C, lam=lam, n_spills=n_spills,
                                  chunk=chunk)
    note_route("assign_fused", "interpret" if interpret else "mosaic")
    from repro.kernels.vq_assign import vq_assign_pallas
    prim, _ = vq_assign_pallas(X, C, interpret=interpret)
    r = X - C[prim]
    rhat = r / jnp.maximum(jnp.linalg.norm(r, axis=-1, keepdims=True), 1e-12)
    sec, _ = soar_assign_pallas(X, rhat, prim, C, lam=lam,
                                interpret=interpret)
    return jnp.stack([prim, sec], axis=1)
