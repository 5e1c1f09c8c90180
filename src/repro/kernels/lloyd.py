"""Fused Lloyd sweep: assignment + per-centroid accumulation in one pass.

The classic two-pass Lloyd iteration (materialize an (n,) assignment
vector, then re-read X for a segment-sum) is what made the build path the
wall after PR 2 sped up search. The sweep here streams X once per
iteration: each row-tile computes its chunk of the distance matrix,
reduces it to (argmin, min) on the spot, and folds the tile's per-centroid
sums/counts/loss into the scan carry — nothing (n,)- or (n, c)-shaped
ever exists outside a tile (pinned by a jaxpr test in
tests/test_build_perf.py).

Two routes share the reassociated one-GEMM distance form
||c||^2 - 2<x,c> (+ ||x||^2 added to the loss only):

- `lloyd_sweep` (any backend): jit'd `lax.scan` over row-chunks;
  per-chunk `segment_sum` accumulate (XLA:CPU scatter is ~15x faster than
  a one-hot GEMM there — measured, see DESIGN.md §3.8);
- `lloyd_sweep_pallas` (TPU): row-tile grid with full C resident in VMEM;
  the accumulate is a one-hot MXU contraction into VMEM scratch, which on
  TPU *is* the fast path; sums/counts leave the core once.

Exact-argmin note: the reduction uses a grouped min (vectorized lane min
over G-wide groups, then an argmin over group minima, then first-match
within the winning group). Ties resolve to the lowest index — identical
to `jnp.argmin` — but the index-tracking reduction runs on 1/G of the
data, which is ~1.8x faster on XLA:CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.kernels import note_route

ARGMIN_GROUP = 8

# below this feature dim, x·Cᵀ runs as an unrolled multiply-add chain over
# the s axis instead of a dot_general: XLA:CPU dispatches k<=8 GEMMs as
# hundreds of tiny Eigen calls (the PQ-subspace regime, s=d/m=4), while the
# unrolled form fuses into one elementwise pass. The s-loop accumulates
# left-to-right, so results are deterministic and identical between the
# per-subspace and vmapped-batched callers (the train_pq bitwise pin).
SMALL_D = 8


def _xct(xb, Ct):
    """xb (..., d) @ Ct (d, c) with the small-d unrolled fast path."""
    d = Ct.shape[0]
    if d > SMALL_D:
        return xb @ Ct
    acc = xb[..., 0:1] * Ct[0]
    for s in range(1, d):
        acc = acc + xb[..., s:s + 1] * Ct[s]
    return acc


def _grouped_argmin(dm, G: int = ARGMIN_GROUP):
    """Exact first-tie argmin+min over the last axis of (..., c).

    c must be a multiple of G (pad with +inf columns). Returns
    (idx int32, minval) — bitwise identical to (jnp.argmin, jnp.min).
    """
    shape = dm.shape
    # barrier: both reduction paths below must read the SAME bits — without
    # it XLA duplicates the (fused) distance computation into each consumer
    # and FMA-contracts them differently, silently corrupting tie-breaks
    dg = jax.lax.optimization_barrier(dm.reshape(shape[:-1] + (-1, G)))
    gmin = jnp.min(dg, -1)                         # vectorized lane min
    g = jnp.argmin(gmin, -1)                       # over c/G group minima
    mv = jnp.take_along_axis(gmin, g[..., None], -1)[..., 0]
    rowg = jnp.take_along_axis(dg, g[..., None, None], -2)[..., 0, :]
    within = jnp.argmin(rowg, -1)                  # first min in the group
    return (g * G + within).astype(jnp.int32), mv


@functools.partial(jax.jit, static_argnames=("c", "chunk"))
def lloyd_sweep(X, C, c: int, chunk: int = 8192):
    """One fused Lloyd iteration over X against C.

    Returns (new_C, counts (c,) f32, mean distortion). Empty clusters keep
    their old centroid. Chunk boundaries change only the f32 accumulation
    grouping of sums/loss (assignments — hence counts — are exact for any
    chunk); at chunk >= n the result is bitwise-identical to the unfused
    `core.kmeans.lloyd_step` reference.
    """
    n, d = X.shape
    cpad = (-c) % ARGMIN_GROUP
    Ct = jnp.pad(C, ((0, cpad), (0, 0))).T         # (d, c+pad) contiguous
    cn = jnp.pad(jnp.sum(C * C, axis=-1), (0, cpad),
                 constant_values=jnp.inf)[None, :]
    npad = (-n) % chunk
    Xc = jnp.pad(X, ((0, npad), (0, 0))).reshape(-1, chunk, d)
    starts = (jnp.arange(Xc.shape[0]) * chunk).astype(jnp.int32)

    def body(carry, inp):
        sums, counts, loss = carry
        xb, i0 = inp
        dm = cn - 2.0 * _xct(xb, Ct)
        idx, mv = _grouped_argmin(dm)
        mind = mv + jnp.sum(xb * xb, axis=-1)
        valid = (i0 + jnp.arange(chunk, dtype=jnp.int32)) < n
        idx_m = jnp.where(valid, idx, c)           # pad rows → overflow bin
        sums = sums + jax.ops.segment_sum(xb, idx_m, num_segments=c + 1)[:c]
        counts = counts + jax.ops.segment_sum(
            valid.astype(X.dtype), idx_m, num_segments=c + 1)[:c]
        loss = loss + jnp.sum(jnp.where(valid, mind, 0.0))
        return (sums, counts, loss), None

    init = (jnp.zeros((c, d), X.dtype), jnp.zeros((c,), X.dtype),
            jnp.zeros((), X.dtype))
    (sums, counts, loss), _ = jax.lax.scan(body, init, (Xc, starts))
    new_C = jnp.where(counts[:, None] > 0,
                      sums / jnp.maximum(counts[:, None], 1.0), C)
    return new_C, counts, loss / n


def _lloyd_kernel(x_ref, valid_ref, c_ref, cn_ref,
                  sums_ref, counts_ref, loss_ref,
                  acc_sums, acc_counts, acc_loss, *, c: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_sums[...] = jnp.zeros_like(acc_sums)
        acc_counts[...] = jnp.zeros_like(acc_counts)
        acc_loss[...] = jnp.zeros_like(acc_loss)

    x = x_ref[...]                                  # (bn, d)
    valid = valid_ref[...]                          # (bn, 1) f32 0/1
    cm = c_ref[...]                                 # (c, d) full codebook
    cn = cn_ref[...]                                # (1, c)
    hi = jax.lax.Precision.HIGHEST
    dm = cn - 2.0 * jax.lax.dot_general(
        x, cm, (((1,), (1,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)
    # every reduction keeps its axis: Mosaic refuses a 1-D (bn,) row
    # reduced to a scalar ("Offset change"), so values stay (bn, 1)/(1, 1)
    idx = jnp.argmin(dm, axis=-1, keepdims=True)             # (bn, 1)
    mind = (jnp.min(dm, axis=-1, keepdims=True)
            + jnp.sum(x * x, axis=-1, keepdims=True))        # (bn, 1)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, dm.shape, 1)
              == idx).astype(jnp.float32) * valid
    # MXU contraction: on TPU the one-hot matmul IS the fast accumulate
    acc_sums[...] += jax.lax.dot_general(
        onehot, x, (((0,), (0,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)
    acc_counts[...] += jnp.sum(onehot, axis=0, keepdims=True)
    acc_loss[...] += jnp.sum(mind * valid, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _write():
        sums_ref[...] = acc_sums[...]
        counts_ref[...] = acc_counts[...]
        loss_ref[...] = acc_loss[...]


@functools.partial(jax.jit, static_argnames=("c", "bn", "interpret"))
def lloyd_sweep_pallas(X, C, c: int, bn: int = 1024, interpret: bool = True):
    """TPU route of the fused sweep (same contract as `lloyd_sweep`).

    Grid over row-tiles only (sequential, so VMEM scratch accumulates);
    the full (c, d) codebook stays VMEM-resident — sized for the build
    regime c <= 4096, d <= 256.
    """
    n, d = X.shape
    npad = (-n) % bn
    Xp = jnp.pad(X.astype(jnp.float32), ((0, npad), (0, 0)))
    valid = (jnp.arange(Xp.shape[0]) < n).astype(jnp.float32)[:, None]
    cn = jnp.sum(C * C, axis=-1).astype(jnp.float32)[None, :]
    grid = (Xp.shape[0] // bn,)
    sums, counts, loss = pl.pallas_call(
        functools.partial(_lloyd_kernel, c=c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((c, d), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((c, d), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c, d), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((c, d), jnp.float32),
            pltpu.VMEM((1, c), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(Xp, valid, C.astype(jnp.float32), cn)
    counts = counts[0]
    new_C = jnp.where(counts[:, None] > 0,
                      sums / jnp.maximum(counts[:, None], 1.0), C)
    return new_C, counts, loss[0, 0] / n


@functools.partial(jax.jit, static_argnames=("c", "chunk"))
def lloyd_sweep_batched(Xb, Cb, c: int, chunk: int = 16384):
    """`lloyd_sweep` over a leading batch of m independent problems
    (e.g. the m PQ subspaces trained jointly): one scan whose tiles carry
    all m slices, so the whole batch advances in a single device program
    per iteration.

    Hand-batched rather than vmap'd (vmap of the scan is ~2.5x slower on
    XLA:CPU), mirroring `lloyd_sweep` op-for-op in (m, ...) form: the
    small-d contraction is the same unrolled multiply-add chain, argmin
    the same grouped reduction, accumulation the same per-chunk vmapped
    segment-sum — per-slice results are bitwise-identical to calling
    `lloyd_sweep` per problem (pinned by tests/test_build_perf.py).
    """
    m, n, d = Xb.shape
    cpad = (-c) % ARGMIN_GROUP
    Cp = jnp.pad(Cb, ((0, 0), (0, cpad), (0, 0)))            # (m, c+pad, d)
    cn = jnp.pad(jnp.sum(Cb * Cb, axis=-1), ((0, 0), (0, cpad)),
                 constant_values=jnp.inf)[:, None, :]        # (m, 1, c+pad)
    npad = (-n) % chunk
    Xc = jnp.pad(Xb, ((0, 0), (0, npad), (0, 0))).reshape(
        m, -1, chunk, d).transpose(1, 0, 2, 3)               # (nch, m, chunk, d)
    starts = (jnp.arange(Xc.shape[0]) * chunk).astype(jnp.int32)

    def body(carry, inp):
        sums, counts, loss = carry
        xb, i0 = inp                                         # (m, chunk, d)
        if d <= SMALL_D:                                     # mirror _xct
            ip = xb[..., 0:1] * Cp[:, None, :, 0]
            for j in range(1, d):
                ip = ip + xb[..., j:j + 1] * Cp[:, None, :, j]
        else:
            ip = jnp.einsum("mbd,mcd->mbc", xb, Cp)
        dm = cn - 2.0 * ip
        idx, mv = _grouped_argmin(dm)                        # (m, chunk)
        mind = mv + jnp.sum(xb * xb, axis=-1)
        valid = (i0 + jnp.arange(chunk, dtype=jnp.int32)) < n
        idx_m = jnp.where(valid[None, :], idx, c)
        sums = sums + jax.vmap(
            lambda x, a: jax.ops.segment_sum(x, a, num_segments=c + 1)
        )(xb, idx_m)[:, :c]
        counts = counts + jax.vmap(
            lambda a: jax.ops.segment_sum(
                valid.astype(Xb.dtype), a, num_segments=c + 1))(idx_m)[:, :c]
        loss = loss + jnp.sum(jnp.where(valid[None, :], mind, 0.0), axis=-1)
        return (sums, counts, loss), None

    init = (jnp.zeros((m, c, d), Xb.dtype), jnp.zeros((m, c), Xb.dtype),
            jnp.zeros((m,), Xb.dtype))
    (sums, counts, loss), _ = jax.lax.scan(body, init, (Xc, starts))
    new_C = jnp.where(counts[..., None] > 0,
                      sums / jnp.maximum(counts[..., None], 1.0), Cb)
    return new_C, counts, loss / n


def lloyd_sweep_auto(X, C, c: int, chunk: int = 8192,
                     use_pallas: bool = None, interpret: bool = None):
    """Backend dispatch: Pallas on TPU (codebook fits VMEM), scan elsewhere.
    The route taken is logged
    (`repro.kernels.note_route`)."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = kernels.interpret_mode()
    if use_pallas and c * X.shape[1] <= 1 << 20:
        note_route("lloyd_sweep", "interpret" if interpret else "mosaic")
        return lloyd_sweep_pallas(X, C, c, interpret=interpret)
    note_route("lloyd_sweep", "xla")
    return lloyd_sweep(X, C, c, chunk=chunk)
