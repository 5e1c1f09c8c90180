"""Fused two-level centroid routing (the TreeRouter probe stage).

Given the two-level tables of core/router.TreeRouter — super centroids
(S, d), a padded (S, cmax) children table, and the child centroid rows
grouped to match — produce, per query, the scores and partition ids of
every child of its top-``t_route`` super-clusters:

    (nq, d) -> scores (nq, t_route·cmax) f32, ids (nq, t_route·cmax) i32

(-inf / -1 at children-table padding). The final top-t cut happens in the
caller (core/router.TreeRouter.route) — the kernel's job is the fused
middle: super GEMM -> per-query super selection -> child gather+score,
with nothing (nq, S)- or (nq, t_route·cmax·d)-shaped leaving the tile.

Two routes, same contract (mirroring kernels/soar_assign.py):

- ``tree_route_ref`` (any backend): jit'd form — one (nq, S) GEMM +
  ``lax.top_k``, then a statically-unrolled per-round gather + einsum so
  the live child-centroid gather is bounded at (nq, cmax, d) per round
  instead of (nq, t_route·cmax, d);
- ``tree_route_pallas`` (TPU): query-tile grid with the super codebook
  and both child tables VMEM-resident; per round the selected super is
  materialized as a one-hot and the child block/id gathers run as
  one-hot MXU contractions (the same gather-as-matmul idiom as
  kernels/pq_score.py and the lloyd accumulate) — no dynamic gather
  lowering needed, and the (bq, S) score matrix never leaves VMEM.
  Sized for the routing regime S·d and cmax·d ≲ a few MB of VMEM
  (S ~ sqrt(c) ≤ 512, d ≤ 256); larger configs fall back to the ref.

The two routes select supers in the same order (iterative argmax ==
descending top-k with first-index tie-breaks); child scores may differ
by f32 reduction order only (allclose-pinned in tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.kernels import note_route

# 64-query tiles keep the gathered (bq, d·cmax) child block, the child
# table and the outputs inside the default 16 MiB scoped VMEM at d=100,
# S=50, cmax=128 (128-query tiles need 18.9 MiB)
DEFAULT_BQ = 64
LANES = 128


@functools.partial(jax.jit, static_argnames=("t_route",))
def tree_route_ref(Q, SC, CC, CH, t_route: int):
    """Reference route: (nq, S) GEMM + top-k supers, then one gathered
    (nq, cmax, d) einsum per round (statically unrolled, memory bounded
    per round regardless of t_route)."""
    ss = Q @ SC.T                                          # (nq, S)
    _, sup = jax.lax.top_k(ss, t_route)                    # (nq, tr)
    scores, ids = [], []
    for r in range(t_route):
        s_r = sup[:, r]
        cid = CH[s_r]                                      # (nq, cmax)
        cc = CC[s_r]                                       # (nq, cmax, d)
        sc = jnp.einsum("qcd,qd->qc", cc, Q)
        scores.append(jnp.where(cid >= 0, sc, -jnp.inf))
        ids.append(cid)
    return jnp.concatenate(scores, -1), jnp.concatenate(ids, -1)


def _tree_route_kernel(q_ref, sc_ref, ccf_ref, chf_ref,
                       scores_ref, ids_ref, *, t_route: int, cmax: int,
                       d: int):
    hi = jax.lax.Precision.HIGHEST     # the id gather must stay exact
    q = q_ref[...]                                         # (bq, d)
    ss = jax.lax.dot_general(q, sc_ref[...],
                             (((1,), (1,)), ((), ())), precision=hi,
                             preferred_element_type=jnp.float32)  # (bq, S)
    ccf = ccf_ref[...]                                     # (S, d·cmax)
    chf = chf_ref[...]                                     # (S, cmax) f32
    for r in range(t_route):
        idx = jnp.argmax(ss, axis=-1, keepdims=True)       # (bq, 1)
        onehot = (jax.lax.broadcasted_iota(jnp.int32, ss.shape, 1)
                  == idx).astype(jnp.float32)
        # one-hot MXU gather: selected super's child block / id row
        blk = jax.lax.dot_general(onehot, ccf,
                                  (((1,), (0,)), ((), ())), precision=hi,
                                  preferred_element_type=jnp.float32)
        cid = jax.lax.dot_general(onehot, chf,
                                  (((1,), (0,)), ((), ())), precision=hi,
                                  preferred_element_type=jnp.float32)
        # the child block is dimension-major (column k·cmax + j holds
        # child j's coordinate k) and cmax is lane-aligned, so each
        # coordinate is one aligned (bq, cmax) slice — no 3-D reshape,
        # which Mosaic refuses as a shape cast
        sc = blk[:, 0:cmax] * q[:, 0:1]
        for k in range(1, d):
            sc = sc + blk[:, k * cmax:(k + 1) * cmax] * q[:, k:k + 1]
        sc = jnp.where(cid > -0.5, sc, -jnp.inf)
        scores_ref[:, r * cmax:(r + 1) * cmax] = sc
        ids_ref[:, r * cmax:(r + 1) * cmax] = cid.astype(jnp.int32)
        ss = jnp.where(onehot > 0, -jnp.inf, ss)           # extract-and-mask


@functools.partial(jax.jit, static_argnames=("t_route", "bq", "interpret"))
def tree_route_pallas(Q, SC, CC, CH, t_route: int, bq: int = DEFAULT_BQ,
                      interpret: bool = True):
    """Pallas route (TPU target; interpret mode elsewhere/CI)."""
    nq, d = Q.shape
    S, cmax, _ = CC.shape
    npad = (-nq) % bq
    Qp = jnp.pad(Q.astype(jnp.float32), ((0, npad), (0, 0)))
    # children padded to a lane multiple (pad slots carry id -1 → -inf),
    # laid out dimension-major; the pad columns are cut after the call
    cp = cmax + (-cmax) % LANES
    CCp = jnp.pad(CC.astype(jnp.float32), ((0, 0), (0, cp - cmax), (0, 0)))
    ccf = CCp.transpose(0, 2, 1).reshape(S, d * cp)
    chf = jnp.pad(CH.astype(jnp.float32), ((0, 0), (0, cp - cmax)),
                  constant_values=-1.0)
    w = t_route * cp
    grid = (Qp.shape[0] // bq,)
    scores, ids = pl.pallas_call(
        functools.partial(_tree_route_kernel, t_route=t_route, cmax=cp,
                          d=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i: (i, 0)),
            pl.BlockSpec((S, d), lambda i: (0, 0)),
            pl.BlockSpec((S, d * cp), lambda i: (0, 0)),
            pl.BlockSpec((S, cp), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, w), lambda i: (i, 0)),
            pl.BlockSpec((bq, w), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp.shape[0], w), jnp.float32),
            jax.ShapeDtypeStruct((Qp.shape[0], w), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(Qp, SC.astype(jnp.float32), ccf, chf)

    def cut(a):
        return a[:nq].reshape(nq, t_route, cp)[:, :, :cmax].reshape(
            nq, t_route * cmax)
    return cut(scores), cut(ids)


def tree_route(Q, SC, CC, CH, t_route: int, use_pallas: bool = None,
               interpret: bool = None):
    """Backend dispatch, mirroring assign_fused: Pallas on TPU when the
    child tables fit VMEM, the jit'd reference elsewhere. The route taken
    is logged (`repro.kernels.note_route`)."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = kernels.interpret_mode()
    S, cmax, d = CC.shape
    cp = cmax + (-cmax) % LANES
    if use_pallas and cp * d <= 1 << 18 and S * d <= 1 << 20:
        note_route("tree_route", "interpret" if interpret else "mosaic")
        return tree_route_pallas(Q, SC, CC, CH, t_route,
                                 interpret=interpret)
    note_route("tree_route", "xla")
    return tree_route_ref(Q, SC, CC, CH, t_route)
