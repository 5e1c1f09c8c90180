"""Pallas TPU kernels: batched PQ LUT scoring as a one-hot MXU contraction.

TPU adaptation of ScaNN's AVX2 LUT16 (DESIGN.md §3): instead of in-register
shuffles, codes are expanded to one-hot IN VMEM and contracted against the
per-query LUTs on the MXU. The LUT block stays VMEM-resident across the whole
point dimension; HBM traffic is one streaming read of the (packed) codes.

Two variants:

- `pq_score_pallas`: shared code matrix — every query scores every point.
      score[q, i] = sum_m luts[q, m, codes[i, m]]
                  = luts[q].reshape(m*16) · onehot(codes[i]).reshape(m*16)

- `pq_score_window_pallas`: per-query candidate windows — query q scores only
  ITS OWN gathered candidates (the t·pmax window the IVF search probes), the
  shape the candidate-local `search_jit` pipeline produces (DESIGN.md §3.6).
      score[q, i] = sum_m luts[q, m, codes[q, i, m]]
  Each query has its own codes, so there is no shared operand for the MXU:
  the lookup is a 16-way select per subspace on the VPU over lane-dense
  (BQ, BN) code tiles, accumulated over the m subspaces.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

# Block sizes: BQ queries × BN points per grid cell. m*16 is the contraction
# dim (m=16 subspaces → 256, MXU-aligned). VMEM footprint per cell:
#   luts BQ×(m·16)·4B + codes BN×m·4B + onehot BN×(m·16)·4B + out BQ×BN·4B
#   ≈ 128·256·4 + 512·16·4 + 512·256·4 + 128·512·4 ≈ 0.9 MB  « 16 MB VMEM.
DEFAULT_BQ = 128
DEFAULT_BN = 512

# Window variant: codes m×BQ×BN·4B (400 KB at m=25) + luts + out « 16 MB.
DEFAULT_WIN_BQ = 8
DEFAULT_WIN_BN = 512


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """None → auto-detect: compile to Mosaic on TPU, interpret elsewhere."""
    return kernels.interpret_mode() if interpret is None else interpret


def _pq_score_kernel(lut_ref, codes_ref, out_ref, *, n_centers: int):
    codes = codes_ref[...]                                   # (BN, m) int32
    onehot = (codes[:, :, None]
              == jax.lax.broadcasted_iota(jnp.int32, (1, 1, n_centers), 2))
    onehot = onehot.astype(jnp.float32).reshape(codes.shape[0], -1)  # (BN, m*16)
    lut = lut_ref[...]                                       # (BQ, m*16)
    out_ref[...] = jax.lax.dot_general(
        lut, onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                  # (BQ, BN)


@functools.partial(jax.jit, static_argnames=("n_centers", "bq", "bn", "interpret"))
def pq_score_pallas(luts, codes, n_centers: int = 16,
                    bq: int = DEFAULT_BQ, bn: int = DEFAULT_BN,
                    interpret: Optional[bool] = None):
    """luts (nq, m, 16) f32, codes (n, m) int32 → (nq, n) f32 scores.

    interpret=None auto-detects the backend (Mosaic on TPU, interpret mode
    elsewhere) — pass an explicit bool only to force one mode.
    """
    interpret = _resolve_interpret(interpret)
    nq, m, k = luts.shape
    n = codes.shape[0]
    assert k == n_centers
    lutmat = luts.reshape(nq, m * k)
    # pad to block multiples (zero LUT rows / zero codes are harmless: stripped)
    qpad = (-nq) % bq
    npad = (-n) % bn
    lutmat = jnp.pad(lutmat, ((0, qpad), (0, 0)))
    codes_p = jnp.pad(codes.astype(jnp.int32), ((0, npad), (0, 0)))
    grid = (lutmat.shape[0] // bq, codes_p.shape[0] // bn)
    out = pl.pallas_call(
        functools.partial(_pq_score_kernel, n_centers=n_centers),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, m * k), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, m), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (lutmat.shape[0], codes_p.shape[0]), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(lutmat, codes_p)
    return out[:nq, :n]


def _pq_score_window_kernel(lut_ref, codes_ref, out_ref, *, n_centers: int):
    # codes arrive subspace-major, (m, BQ, BN): codes_ref[j] is one
    # lane-dense (BQ, BN) tile, and lut column j·16+k broadcasts along the
    # lanes of its query row — no 3-D one-hot, no batched dot_general
    # (Mosaic refuses a batch-dim dot_general)
    lut = lut_ref[...]                                       # (BQ, m*16)
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for j in range(codes_ref.shape[0]):
        cj = codes_ref[j]                                    # (BQ, BN) int32
        g = jnp.zeros_like(acc)
        for k in range(n_centers):
            col = j * n_centers + k
            g = jnp.where(cj == k, lut[:, col:col + 1], g)
        acc = acc + g
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("n_centers", "bq", "bn", "interpret"))
def pq_score_window_pallas(luts, codes, n_centers: int = 16,
                           bq: int = DEFAULT_WIN_BQ, bn: int = DEFAULT_WIN_BN,
                           interpret: Optional[bool] = None):
    """luts (nq, m, 16) f32, codes (nq, cand, m) int → (nq, cand) f32 scores.

    Per-query candidate-window scoring: row q of `codes` is query q's own
    gathered candidate window (already in partition-probe order). This is the
    hot-path shape of the candidate-local `search_jit` pipeline.
    """
    interpret = _resolve_interpret(interpret)
    nq, m, k = luts.shape
    assert k == n_centers
    assert codes.shape[0] == nq and codes.shape[2] == m, (luts.shape, codes.shape)
    cand = codes.shape[1]
    lutmat = luts.reshape(nq, m * k)
    qpad = (-nq) % bq
    npad = (-cand) % bn
    lutmat = jnp.pad(lutmat, ((0, qpad), (0, 0)))
    # int32 cast, padding and the subspace-major transpose fuse into one copy
    codes_p = jnp.pad(codes.astype(jnp.int32).transpose(2, 0, 1),
                      ((0, 0), (0, qpad), (0, npad)))        # (m, nq, cand)
    grid = (lutmat.shape[0] // bq, codes_p.shape[2] // bn)
    out = pl.pallas_call(
        functools.partial(_pq_score_window_kernel, n_centers=n_centers),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, m * k), lambda i, j: (i, 0)),
            pl.BlockSpec((m, bq, bn), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (lutmat.shape[0], codes_p.shape[2]), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(lutmat, codes_p)
    return out[:nq, :cand]
