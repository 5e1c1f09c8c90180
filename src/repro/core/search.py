"""ANN search over a (possibly spilled) IVF index.

Two execution paths, both candidate-local (DESIGN.md §3.6): every per-query
intermediate is bounded by the probed candidate window (top_t·pmax entries),
never by the database size n — the property that keeps SOAR's spilled IVF
sublinear at serving time.

- `search_numpy`: host-orchestrated ragged search (like ScaNN's CPU engine):
  jit'd centroid scoring, one batch-level CSR gather, vectorized PQ LUT
  scoring, per-query segment dedup (a point may appear in 2+ searched
  partitions under spilling), exact rerank. Used by the recall/QPS benchmarks.

- `search_jit`: fixed-budget, fully-jit pipeline (padded partitions) — the
  TPU-target path the Pallas kernels and the distributed serving engine use.
  Batched centroid GEMM + top-t, gathered candidate ids, PQ LUT scoring
  by the Pallas window kernel reading the code table in place on TPU
  (jnp gather fallback elsewhere), sort-based dedup-by-max over the
  window, exact rerank.
  `search_jit_batched` streams large query batches through `bq`-sized tiles
  so live buffers stay bounded regardless of nq.

Both engines serve **filtered / subset queries** (DESIGN.md §3.9): an
index-side (n,) bitmap is gathered per candidate window — never expanded
per query — so the candidate-local invariant survives filtering, and a
selectivity-adaptive probe escalation (host-driven re-probe loop in the
numpy engine, one fixed doubled-top_t second pass in the jit engine)
rescues queries whose surviving window is thinner than the rerank budget.

The partition-probe stage of both engines is a pluggable `Router`
(core/router.py, DESIGN.md §3.10): the default `FlatRouter` reproduces
the historical inline `Q @ centroids.T` + top-t op-for-op (bitwise probe
sets, so the jaxpr/HLO pins and committed baselines are unchanged), and
`TreeRouter` replaces the O(c) GEMM with a two-level O(√c·t_route) probe.
Clamping and filtered escalation are router policy — the escalation
paths below ask the router for the next (router, top_t) step instead of
hardcoding the doubling.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ivf import IVFIndex
from repro.core.router import FlatRouter, check_query_dim
from repro import kernels
from repro.kernels import note_route
from repro.kernels.pq_score import code_table, code_table_codes
from repro.quant.pq import pq_lut, PQCodebook


# The exact stages score in full f32 on every backend: TPU's default matmul
# precision rounds f32 inputs to bf16, which reorders near-tied neighbours.
EXACT = jax.lax.Precision.HIGHEST


class SearchStats(NamedTuple):
    points_read: np.ndarray     # (nq,) assignments scanned (incl. duplicates)
    unique_candidates: np.ndarray


def _ragged_gather(starts: np.ndarray, top_parts: np.ndarray,
                   part_scores: np.ndarray):
    """Batch-level CSR gather: one flat index vector for every (query,
    partition) segment in the batch.

    Returns (cand_rows, qidx, seg_score, row_lens): flat CSR row of each
    candidate, its query, its source partition's ROUTER score (the coarse
    <q, centroid> term the PQ stage adds back), and per-query totals.
    Broadcasting the router's (nq, t) scores here is what lets the probe
    stage avoid materializing the full (nq, c) score matrix for routers
    that never compute it (TreeRouter)."""
    nq, t = top_parts.shape
    seg_starts = starts[top_parts].ravel()                       # (nq*t,)
    seg_lens = (starts[top_parts + 1] - starts[top_parts]).ravel()
    offs = np.concatenate([[0], np.cumsum(seg_lens)])
    total = int(offs[-1])
    ar = np.arange(total, dtype=np.int64)
    cand_rows = ar - np.repeat(offs[:-1], seg_lens) + np.repeat(seg_starts,
                                                                seg_lens)
    row_lens = seg_lens.reshape(nq, t).sum(axis=1)
    qidx = np.repeat(np.arange(nq, dtype=np.int64), row_lens)
    seg_score = np.repeat(np.asarray(part_scores, np.float32).ravel(),
                          seg_lens)
    return cand_rows, qidx, seg_score, row_lens


def _group_ranks(group: np.ndarray, n_groups: int) -> np.ndarray:
    """Rank of each element within its (sorted, contiguous) group."""
    starts = np.searchsorted(group, np.arange(n_groups))
    return np.arange(len(group)) - starts[group]


def search_numpy(index: IVFIndex, Q: np.ndarray, top_t: int,
                 final_k: int = 10, rerank_budget: int = 0,
                 filter_mask: Optional[np.ndarray] = None,
                 escalate: bool = True, router=None):
    """Returns (ids (nq, final_k), SearchStats). rerank_budget=0 → exact
    scoring of all candidates (no PQ stage).

    Fully vectorized over the batch: one ragged CSR gather, one LUT gather,
    and `np.lexsort`-based per-query segment dedup — no per-query Python loop.

    filter_mask: optional (n_points,) bool/uint8 subset bitmap; candidates
    with a 0 bit are dropped at the ragged-gather stage (Rii-style
    candidate-side subset masking). Short masks zero-pad (ids beyond the
    mask are excluded), matching MutableIVF.filter_bitmap. With `escalate`,
    queries whose surviving unique-candidate set is thinner than the stage
    budget (rerank_budget with a PQ stage, else final_k — the same signal
    as the jit engine, additionally capped at the filter's population so a
    subset smaller than the budget stops escalating once fully found)
    re-probe through the router's escalation ladder (doubled top_t; a
    TreeRouter also doubles t_route) — host-driven, repeated until
    satisfied or the router is exhausted, so very selective filters
    degrade toward filtered brute force instead of returning starved
    windows.

    router: probe-stage Router (core/router.py); default is the index's
    build-time router, else the flat probe (historical behavior, bitwise).
    """
    Q = np.asarray(Q, np.float32)
    if router is None:
        router = index.router or FlatRouter(index.centroids)
    check_query_dim(Q, index.centroids.shape[1])
    if Q.shape[0] == 0:                      # empty batch → empty results
        z = np.zeros(0, np.int64)
        return np.full((0, final_k), -1, np.int32), SearchStats(z, z)
    top_t = router.clamp(top_t)              # argpartition kth ∈ [0, c)
    fm = None
    if filter_mask is not None:
        mm = np.asarray(filter_mask).astype(bool).ravel()[:index.n_points]
        fm = np.zeros(index.n_points, bool)
        fm[:mm.shape[0]] = mm
    data = index.rerank_f32
    if data is None:
        from repro.quant.int8 import int8_dequantize
        data = np.asarray(int8_dequantize(index.rerank_int8))
    out, row_lens, uniq = _search_numpy_pass(index, Q, data, router, top_t,
                                             final_k, rerank_budget, fm)
    if fm is not None and escalate:
        use_pq = index.codes is not None and rerank_budget > 0
        thresh = min(rerank_budget if use_pq else final_k, int(fm.sum()))
        r, t = router, top_t
        thin = np.flatnonzero(uniq < thresh)
        while thin.size and r.can_escalate(t):
            r, t = r.escalated(t)
            o2, r2, u2 = _search_numpy_pass(index, Q[thin], data, r, t,
                                            final_k, rerank_budget, fm)
            out[thin], row_lens[thin], uniq[thin] = o2, r2, u2
            thin = thin[u2 < thresh]
    return out, SearchStats(row_lens, uniq)


def _search_numpy_pass(index: IVFIndex, Q: np.ndarray, data: np.ndarray,
                       router, top_t: int, final_k: int, rerank_budget: int,
                       fm: Optional[np.ndarray]):
    """One fixed-top_t pass of the host engine; returns (out, points_read,
    unique_candidates) so the escalation driver can splice per-query rows."""
    nq = Q.shape[0]
    # probe stage: router picks the partitions (score-descending) and
    # reports their coarse scores — the flat router reproduces the old
    # inline argpartition head bitwise
    psc, top_parts = router.route_numpy(Q, top_t)

    use_pq = index.codes is not None and rerank_budget > 0

    cand_rows, qidx, seg_score, row_lens = _ragged_gather(index.starts,
                                                          top_parts, psc)
    cand_ids = index.point_ids[cand_rows].astype(np.int64)
    if fm is not None:
        # subset masking at the gather stage: filtered candidates never
        # reach scoring, dedup, or the rerank budget
        keep = fm[cand_ids]
        cand_rows, qidx = cand_rows[keep], qidx[keep]
        seg_score, cand_ids = seg_score[keep], cand_ids[keep]
    # composite (query, id) key: one dedup pass for the whole batch
    key = qidx * np.int64(index.n_points) + cand_ids

    if use_pq:
        luts = np.asarray(
            jax.vmap(lambda q: pq_lut(index.pq, q))(jnp.asarray(Q)))
        codes = index.codes[cand_rows]                    # (total, m)
        m = codes.shape[1]
        approx = luts[qidx[:, None], np.arange(m)[None, :],
                      codes].sum(axis=1)
        approx = approx + seg_score                       # + <q, centroid>
        # dedup: keep best approx score per (query, id)
        order = np.lexsort((-approx, key))
        key_s = key[order]
        keep = np.ones(len(order), bool)
        keep[1:] = key_s[1:] != key_s[:-1]
        sel = order[keep]
        # per-query budget truncation by approx (descending)
        sel = sel[np.lexsort((-approx[sel], qidx[sel]))]
        sel = sel[_group_ranks(qidx[sel], nq) < rerank_budget]
    else:
        sel = np.unique(key, return_index=True)[1]        # first per (q, id)

    qs, ids_sel = qidx[sel], cand_ids[sel]
    uniq = np.bincount(qs, minlength=nq).astype(np.int64)
    exact = np.einsum("ij,ij->i", data[ids_sel], Q[qs])
    order = np.lexsort((-exact, qs))
    qs, ids_sel = qs[order], ids_sel[order]
    rank = _group_ranks(qs, nq)
    top = rank < final_k
    out = np.full((nq, final_k), -1, np.int32)
    out[qs[top], rank[top]] = ids_sel[top]
    return out, row_lens, uniq


# --------------------------------------------------------------------------
# Fixed-budget jit path (TPU target; used by distributed serving + kernels)
# --------------------------------------------------------------------------

LANES = 128      # minor (lane) width of a TPU vector register and tile


def rerank_span(d: int) -> int:
    """Lane-rows of the rerank table that one d-vector can touch: its
    start offset within a row is a multiple of gcd(d, 128), so the span
    reaches at most 128 - gcd(d, 128) + d lanes (2 rows for d=100, 1 for
    d=128)."""
    return -(-(d + LANES - math.gcd(d, LANES)) // LANES)


def rerank_table_shape(n: int, d: int) -> tuple:
    """Shape of the rerank table holding n d-vectors: (R, 128) lane-rows,
    R = ceil(n·d/128) plus the spare rows the last vector's span reads."""
    return (-(-n * d // LANES) + rerank_span(d) - 1, LANES)


def rerank_table(rows) -> np.ndarray:
    """(n, d) f32 vectors → the rerank table (see `PackedIVF`): vector i
    is flat elements [i·d, (i+1)·d) of 128-lane rows, zeros after."""
    rows = np.asarray(rows, np.float32)
    n, d = rows.shape
    flat = np.zeros(math.prod(rerank_table_shape(n, d)), np.float32)
    flat[:n * d] = rows.reshape(-1)
    return flat.reshape(-1, LANES)


def rerank_table_rows(table, d: int) -> np.ndarray:
    """Inverse of `rerank_table`: the (n', d) vectors a table holds, n'
    the most whose table has this shape (n' ≥ n; the extra rows are the
    zero padding, which no partition slot references), so that
    rows → table → rows → table is the identity on the table."""
    table = np.asarray(table)
    n = (table.shape[0] - rerank_span(d) + 1) * LANES // d
    return table.reshape(-1)[:n * d].reshape(n, d)


def rerank_rows(table, ids, d: int):
    """Gather vectors `ids` (any shape, values in [0, n)) from the rerank
    table → (rows (*ids.shape, W), lane (*ids.shape,) int32), where
    rows[..., lane:lane + d] is the vector, bit for bit.

    Vector i starts at flat element i·d, i.e. row ⌊i·d/128⌋, lane
    (i·d) mod 128; rows are the `rerank_span(d)` table rows its span
    covers, joined along lanes (W = 128·span). Row and lane are computed
    without forming i·d, so they hold for any n·d a table can have."""
    ids = ids.astype(jnp.int32)
    g = math.gcd(d, LANES)
    period, dg = LANES // g, d // g        # i = q·period + r
    q, r = ids // period, ids % period
    row = q * dg + (r * dg) // period
    rows = jnp.concatenate([table[row + j] for j in range(rerank_span(d))],
                           axis=-1)
    return rows, (r * dg) % period * g


def rerank_scores(table, ids, Q):
    """Exact f32 scores ⟨Q[q], x_ids[q, j]⟩ → (nq, w), for ids (nq, w)
    and Q (nq, d): the one read of the rerank table on every search path.

    Instead of moving each candidate's d values to lane 0 (a lane shift
    that differs per candidate, which has no cheap TPU form), the query
    moves. Qs[q, u] is Q[q] placed at lane u·g, one placement per lane a
    vector can start at (g = gcd(d, 128); one placement, the identity,
    when d is a multiple of 128); one batched matmul scores each
    candidate's rows against every placement, and the candidate keeps
    the placement at its own start lane. The other lanes meet zeros, so
    each score sums the same d products as a gather of (n, d) rows
    would, in another order: equal to float32 rounding."""
    d = Q.shape[-1]
    rows, lane = rerank_rows(table, ids, d)
    g = math.gcd(d, LANES)
    starts = g * jnp.arange(LANES // g, dtype=jnp.int32)          # (P,)
    place = (jnp.arange(rows.shape[-1])[None, None, :]
             == jnp.arange(d)[None, :, None] + starts[:, None, None])
    Qs = jnp.einsum("qd,udl->qul", Q, place.astype(Q.dtype),
                    precision=EXACT)                              # (nq, P, W)
    s = jnp.einsum("qwl,qul->qwu", rows, Qs, precision=EXACT)    # (nq, w, P)
    return jnp.sum(jnp.where(lane[..., None] == starts, s, 0.0), axis=-1)


class PackedIVF(NamedTuple):
    """Dense, padded IVF layout for the jit path.

    part_ids:    (c, pmax) int32 point ids, -1 padded
    part_codes:  (c, R, W·128) int32 code table, the only device copy of
                 the 4-bit PQ codes (`kernels/pq_score.py::code_table`,
                 the layout's one definition): partition p is one
                 tile-aligned block, slots lane-dense, 8 subspaces a
                 word. Why (DESIGN.md §3.6): the window kernel DMAs each
                 probed partition's block from HBM as it lies, where a
                 (c, pmax, m) uint8 array was laid out to pad least and
                 every search program copied it whole (130 MB at
                 glove-100) before gathering an int32 window from it.
    part_codes2: (c, pmax, ceil(m/2)) int16/int32 pre-offset PAIR-merged
                 codes (ScaNN-style LUT merging, DESIGN.md §3.6): entry j
                 is codes[2j]·16 + codes[2j+1] + j·256 (+ a single-subspace
                 tail when m is odd), directly indexable into the merged
                 per-query LUT — halves the gather count of CPU scoring
    sizes:       (c,) int32
    rerank:      f32 rerank table, read only through `rerank_rows`:
                 (R, 128) lane-rows, vector i at flat elements
                 [i·d, (i+1)·d), R = ceil(n·d/128) + spare rows (see
                 `rerank_table_shape`). Why (DESIGN.md §3.6): the TPU
                 runtime lays an (n, 100) f32 array out column-major
                 (d on sublanes, padded to 104; n on lanes), and a row
                 gather then made every search program copy the whole
                 table into row-major first (473 MB per call at
                 1.18M × 100). A (R, 128) table has no padding in either
                 order, is committed row-major, and is gathered in place.
    router:      optional probe-stage Router (core/router.py) attached at
                 pack time; None → flat probe over `centroids` (the
                 historical trace, bitwise)
    """
    centroids: jax.Array
    part_ids: jax.Array
    part_codes: Optional[jax.Array]
    part_codes2: Optional[jax.Array]
    sizes: jax.Array
    pq: Optional[PQCodebook]
    rerank: jax.Array           # rerank table, see above
    router: Optional[object] = None


def _paired_codes(codes: np.ndarray, n_centers: int = 16) -> np.ndarray:
    """(..., m) uint8 → (..., ceil(m/2)) pre-offset pair-merged codes."""
    m = codes.shape[-1]
    npairs, rem = divmod(m, 2)
    kk = n_centers * n_centers
    c32 = codes.astype(np.int32)
    out = c32[..., 0:2 * npairs:2] * n_centers + c32[..., 1:2 * npairs:2]
    out = out + np.arange(npairs, dtype=np.int32) * kk
    if rem:
        out = np.concatenate([out, c32[..., -1:] + npairs * kk], axis=-1)
    dt = np.int16 if npairs * kk + n_centers < 2 ** 15 else np.int32
    return out.astype(dt)


def _merged_luts(luts):
    """(nq, m, 16) per-subspace LUTs → (nq, npairs·256 [+16]) merged pair
    LUTs matching `_paired_codes` offsets. The merge is a tiny outer sum
    (nq·(m/2)·256 adds) that halves the per-candidate gather count."""
    nq, m, k = luts.shape
    npairs, rem = divmod(m, 2)
    l2 = luts[:, 0:2 * npairs:2, :, None] + luts[:, 1:2 * npairs:2, None, :]
    l2 = l2.reshape(nq, npairs * k * k)
    if rem:
        l2 = jnp.concatenate([l2, luts[:, -1, :]], axis=-1)
    return l2


def pack_ivf(index: IVFIndex, pmax: Optional[int] = None,
             pair_codes: Optional[bool] = None) -> PackedIVF:
    """Pack an IVFIndex into the dense jit layout.

    pair_codes: build the CPU pair-merged code table (part_codes2). Default
    (None) auto-detects — it is only read by the non-TPU scoring path, so
    TPU backends skip the host pass and the extra device allocation.
    Callers that only consume the raw arrays (e.g. the sharded builders)
    pass False explicitly.
    """
    if pair_codes is None:
        pair_codes = jax.default_backend() != "tpu"
    c = index.n_partitions
    sizes = index.partition_sizes()
    # honor an EXPLICIT pmax=0 (it is a cap, not "unset"); `pmax or max()`
    # conflated the two and an empty/fully-tombstoned index then produced a
    # zero-width pack whose downstream top_k crashed. Arrays are laid out at
    # width >= 1 so a degenerate pack is all -1 sentinels and search returns
    # all -1 ids through the _pad_topk contract.
    if pmax is None:
        pmax = int(sizes.max()) if sizes.size else 0
    pmax = int(pmax)
    width = max(pmax, 1)
    m = index.codes.shape[1] if index.codes is not None else 0
    ids = np.full((c, width), -1, np.int32)
    codes = np.zeros((c, width, m), np.uint8) if m else None
    # vectorized CSR → padded scatter (no per-partition Python loop)
    part = np.repeat(np.arange(c), sizes)                # (n_assign,)
    pos = np.arange(index.n_assignments) - np.repeat(index.starts[:-1], sizes)
    keep = pos < pmax
    ids[part[keep], pos[keep]] = index.point_ids[keep]
    if m:
        codes[part[keep], pos[keep]] = index.codes[keep]
    data = index.rerank_f32
    if data is None:
        from repro.quant.int8 import int8_dequantize
        data = np.asarray(int8_dequantize(index.rerank_int8))
    rt = index.router
    return PackedIVF(
        jnp.asarray(index.centroids), jnp.asarray(ids),
        jnp.asarray(code_table(codes)) if codes is not None else None,
        (jnp.asarray(_paired_codes(codes))
         if codes is not None and pair_codes else None),
        jnp.asarray(np.minimum(sizes, pmax).astype(np.int32)),
        index.pq, jnp.asarray(rerank_table(data)),
        rt.device() if rt is not None else None)


def window_pq_scores(luts, table, parts, pmax: int):
    """(nq, m, 16) LUTs × the code table (c, R, W·128) × probed partitions
    (nq, t) → (nq, t, pmax) scores of each probed partition's slots.

    On TPU the Pallas window kernel reads each probed partition's block
    from the table in place. Elsewhere the probed blocks are gathered and
    unpacked to a (nq, t·pmax, m) window scored by a flat per-query LUT
    gather — indexing the (nq, m·16) LUT with precomputed flat offsets
    keeps the gather operand tiny, where the naive
    `take_along_axis(luts[:, None], ...)` form (kernels/ref.py) broadcasts
    the LUT to (nq, cand, m, 16) — gigabytes at serving shapes.
    """
    nq, t = parts.shape
    if jax.default_backend() == "tpu":
        from repro.kernels.ops import pq_score_window
        note_route("pq_score_window",
                   "interpret" if kernels.interpret_mode() else "mosaic")
        return pq_score_window(luts, table, parts)[:, :, :pmax]
    note_route("pq_score_window", "xla")
    m = luts.shape[1]
    codes = code_table_codes(table[parts], m, pmax).reshape(nq, t * pmax, m)
    lutflat = luts.reshape(nq, m * luts.shape[-1])
    idx = codes + jnp.arange(m, dtype=jnp.int32) * luts.shape[-1]
    g = jnp.take_along_axis(lutflat, idx.reshape(nq, t * pmax * m), axis=-1)
    return g.reshape(nq, t, pmax, m).sum(axis=-1)


def dedup_topk_window(ids, scores, k: int, multiplicity: int = 2):
    """Candidate-local dedup-by-max + top-k, batched over leading axes.

    Two stages, both window-local (nothing ever scales with the database):

    1. cheap `top_k` of the raw window down to multiplicity·k entries — a
       point occupies at most `multiplicity` window slots (primary + spills),
       so the raw top multiplicity·k provably contains every copy that could
       reach the deduped top-k, and in particular each survivor's max;
    2. lexicographic sort of that small set by (id asc, score desc) so the
       first slot of every run of equal ids carries that id's best score;
       the rest (and -1 padding) mask to -inf before the final top-k.

    Stage 1 exists because XLA:CPU's variadic sort is ~10x slower than
    top_k at window width; the split leaves the expensive sort on O(k)
    elements. Pass multiplicity ≥ 1 + n_spills for multi-spill indexes
    (default 2 covers "naive"/"soar" single-spill).

    Returns (ids (..., k) int32, scores (..., k)); k is clamped to the
    window length.
    """
    raw = min(multiplicity * k, ids.shape[-1])
    if raw < ids.shape[-1]:
        scores, pos = jax.lax.top_k(scores, raw)
        ids = jnp.take_along_axis(ids, pos, axis=-1)
    ids_s, neg_s = jax.lax.sort((ids, -scores), num_keys=2)
    scores_s = -neg_s
    first = jnp.concatenate(
        [jnp.ones_like(ids_s[..., :1], dtype=bool),
         ids_s[..., 1:] != ids_s[..., :-1]], axis=-1)
    scores_s = jnp.where(first & (ids_s >= 0), scores_s, -jnp.inf)
    k = min(k, ids.shape[-1])
    v, pos = jax.lax.top_k(scores_s, k)
    return jnp.take_along_axis(ids_s, pos, axis=-1).astype(jnp.int32), v


def _pad_topk(ids, vals, k: int):
    """Pad (..., k') top-k outputs to width k with -1 ids / -inf scores —
    degenerate indexes (t·pmax < k, e.g. a fully-tombstoned mutable index)
    keep the caller-visible (nq, final_k) contract."""
    short = k - ids.shape[-1]
    if short <= 0:
        return ids, vals
    pads = [(0, 0)] * (ids.ndim - 1) + [(0, short)]
    return (jnp.pad(ids, pads, constant_values=-1),
            jnp.pad(vals, pads, constant_values=-jnp.inf))


def _search_pass(packed: PackedIVF, Q, router, top_t: int, final_k: int,
                 rerank_budget: int, multiplicity: int = 2, filter=None):
    """One fixed-top_t candidate-local pass.

    All per-query work is O(top_t·pmax): the probe stage is one router
    call (flat: one batched GEMM + top-t, bitwise the historical trace;
    tree: the fused two-level kernel), candidate gather/scoring/dedup
    operate on the (nq, t·pmax) window. A router may return fewer than
    top_t columns (tree with fewer reachable children); every downstream
    width derives from the probe output, and starved slots arrive as
    partition 0 at -inf coarse score per the router contract — the PQ
    path masks them via the -inf offset, the exact path at worst rescans
    partition 0's window (duplicates dedup away).

    `filter` is an index-side (n,) uint8 bitmap gathered PER WINDOW (the
    (n,) array is an input, never a per-query intermediate — the §3.6
    candidate-local invariant survives filtering, jaxpr-pinned in
    tests/test_filtered_search.py). Filtered candidates are rewritten to
    the -1 padding sentinel before dedup, so a spilled point that passes
    still dedups to one slot and a starved window pads with -1 ids rather
    than leaking filtered ids at -inf. Returns (ids, vals, n_surviving)
    where n_surviving (None unfiltered) counts UNIQUE surviving candidates
    capped at the stage budget — the escalation signal, matching the numpy
    engine's unique-candidate count.

    Each stage runs under a `jax.named_scope` (probe, gather, lut, score,
    dedup, rerank), which names its operations in a profiler trace
    (DESIGN.md §3.15); the scopes change HLO metadata only.
    """
    with jax.named_scope("probe"):
        psc, parts = router.route(Q, top_t)            # (nq, t) probe stage
    with jax.named_scope("gather"):
        ids = packed.part_ids[parts]                   # (nq, t, pmax)
        nq, t, pmax = ids.shape
        ids = ids.reshape(nq, t * pmax)
        valid = ids >= 0
        if filter is not None:
            fbits = filter[jnp.maximum(ids, 0)]        # (nq, t·pmax) gather
            valid = valid & (fbits > 0)
            ids = jnp.where(valid, ids, -1)            # filter-aware dedup
    surviving = None
    if packed.part_codes is None:
        # no PQ stage → exact-score the whole window (search_numpy's
        # rerank_budget=0 semantics); rerank_budget is ignored
        with jax.named_scope("score"):
            exact = rerank_scores(packed.rerank, jnp.maximum(ids, 0), Q)
            exact = jnp.where(valid, exact, -jnp.inf)
        with jax.named_scope("dedup"):
            di, dv = dedup_topk_window(ids, exact, final_k, multiplicity)
            di, dv = _pad_topk(di, dv, final_k)
        if filter is not None:
            # unique survivors, capped at final_k (finite ⟺ a real deduped
            # candidate filled the slot) — matches the numpy engine's
            # unique-count escalation signal
            surviving = jnp.sum(jnp.isfinite(dv), axis=-1)
        return di, dv, surviving
    with jax.named_scope("lut"):
        luts = jax.vmap(lambda q: pq_lut(packed.pq, q))(Q)     # (nq, m, 16)
    if jax.default_backend() != "tpu" and packed.part_codes2 is not None:
        # CPU: pair-merged LUT gather (half the lookups of per-subspace)
        note_route("pq_score_window", "xla")
        with jax.named_scope("gather"):
            idx = packed.part_codes2[parts].reshape(nq, -1).astype(jnp.int32)
        with jax.named_scope("score"):
            g = jnp.take_along_axis(_merged_luts(luts), idx, axis=-1)
            approx = g.reshape(nq, t, pmax, -1).sum(axis=-1)
    else:
        # TPU: the window kernel reads the code table in place; elsewhere
        # the raw-code fallback (pair_codes=False)
        with jax.named_scope("score"):
            approx = window_pq_scores(luts, packed.part_codes, parts, pmax)
    with jax.named_scope("score"):
        # + <q, centroid>, and mask, before the flattening: one pass over
        # the kernel's output on the way to the dedup
        approx = jnp.where(valid.reshape(nq, t, pmax),
                           approx + psc[:, :, None],
                           -jnp.inf).reshape(nq, t * pmax)
    with jax.named_scope("dedup"):
        bi, bv = dedup_topk_window(ids, approx, rerank_budget, multiplicity)
    if filter is not None:
        # unique survivors, capped at rerank_budget (a -inf slot means the
        # deduped candidate set ran short of the budget) — slot-counting
        # the raw window instead would over-count spilled duplicates and
        # skip escalation the numpy engine's unique count would take
        surviving = jnp.sum(jnp.isfinite(bv), axis=-1)
    with jax.named_scope("rerank"):
        exact = rerank_scores(packed.rerank, jnp.maximum(bi, 0), Q)
        exact = jnp.where(jnp.isfinite(bv), exact, -jnp.inf)
        fv, fpos = jax.lax.top_k(exact, min(final_k, exact.shape[-1]))
        fi, fv = _pad_topk(jnp.take_along_axis(bi, fpos, axis=-1), fv, final_k)
    return fi, fv, surviving


def _search_block(packed: PackedIVF, Q, top_t: int, final_k: int,
                  rerank_budget: int, multiplicity: int = 2, filter=None,
                  escalate: bool = False, router=None):
    """Search body shared by search_jit / search_jit_batched: one
    `_search_pass`, plus — on the filtered path only — a SECOND fixed pass
    one router-escalation step up (flat: doubled top_t; tree: doubled
    top_t AND t_route) whose rows are selected per-query where the first
    pass's surviving window was thinner than the rerank budget (the jit
    engine's shape-static analogue of the numpy engine's host-driven
    escalation loop). Unfiltered traces are byte-for-byte the single pass.
    """
    if router is None:
        router = packed.router if packed.router is not None \
            else FlatRouter(packed.centroids)
    check_query_dim(Q, packed.centroids.shape[1])
    top_t = router.clamp(top_t)            # lax.top_k width ∈ [0, c]
    ids1, vals1, surv1 = _search_pass(packed, Q, router, top_t, final_k,
                                      rerank_budget, multiplicity, filter)
    if filter is None or not escalate or not router.can_escalate(top_t):
        return ids1, vals1
    thresh = rerank_budget if packed.part_codes is not None else final_k
    r2, t2 = router.escalated(top_t)
    with jax.named_scope("escalate"):
        ids2, vals2, _ = _search_pass(packed, Q, r2, t2, final_k,
                                      rerank_budget, multiplicity, filter)
        # the escalated probe set is a superset for the flat router (top-2t ⊇
        # top-t of the same centroid scores) and reaches strictly more
        # children for the tree router, so taking pass-2 rows never loses
        # candidates
        need = (surv1 < thresh)[:, None]
        return jnp.where(need, ids2, ids1), jnp.where(need, vals2, vals1)


@functools.partial(jax.jit, static_argnames=("top_t", "final_k",
                                              "rerank_budget", "multiplicity",
                                              "escalate"))
def search_jit(packed: PackedIVF, Q, top_t: int, final_k: int,
               rerank_budget: int = 256, multiplicity: int = 2,
               filter=None, escalate: bool = True, router=None):
    """Fully-jit batched search. Returns (ids, scores) of shape (nq, final_k).

    Pipeline: router probe top-t (flat: batched centroid MIPS; tree: fused
    two-level kernel) → gather per-query candidate ids → PQ LUT
    scoring (+ coarse offset; on TPU the Pallas window kernel reads the
    probed partitions' codes from the code table in place) →
    sort-based dedup-by-max over the window → top rerank_budget → exact
    rerank → top final_k. No intermediate scales with n.

    filter: optional (n,) uint8 device bitmap over point ids (0 = drop);
    gathered per candidate window, never expanded per query. With
    `escalate` a second fixed router-escalated pass backstops thin
    surviving windows (selectivity escalation, DESIGN.md §3.9). Passing
    filter=None traces exactly the unfiltered PR 4 pipeline.

    router: probe-stage Router pytree (core/router.py); default is the
    router packed on the index, else the flat probe (historical trace).
    """
    return _search_block(packed, Q, top_t, final_k, rerank_budget,
                         multiplicity, filter, escalate, router)


def bq_bucket(nq: int, bq: int) -> int:
    """Power-of-two query-count bucket (≥ 8), capped at the serving tile
    size. Serving callers pad their batch to a bucket multiple BEFORE the
    jit boundary and slice the result — the traced Q shape (not just the
    static bq) keys the compile cache, so per-distinct-nq executables were
    a recompile storm for small online batches."""
    return min(bq, max(8, 1 << (max(nq, 1) - 1).bit_length()))


def pad_queries(Q: np.ndarray, bq_cap: int, multiple: int = 1):
    """Host-side bucket padding for serving entry points: (nq, d) float32
    → (padded Q, nq, bucket). Callers pass `bq=bucket` to
    search_jit_batched and slice results back to [:nq].

    `multiple` additionally pads the batch to a multiple of that many
    rows — the replica fan-out path (core/distributed.py
    make_replicated_search) shards the padded batch over R devices, so
    the row count must divide by R as well as land on a compile-cache
    bucket. Power-of-two R ≤ bucket costs no extra padding; otherwise the
    batch rounds up to lcm(bucket, R) rows. Pad rows are zero queries
    whose results are sliced off — per-query results are unaffected
    (every pipeline stage is query-local)."""
    Q = np.atleast_2d(np.asarray(Q, np.float32))
    nq = Q.shape[0]
    bq = bq_bucket(nq, bq_cap)
    step = bq * multiple // np.gcd(bq, multiple) if multiple > 1 else bq
    pad = (-nq) % step
    Qp = np.pad(Q, ((0, pad), (0, 0))) if pad else Q
    return Qp, nq, bq


@functools.partial(jax.jit,
                   static_argnames=("top_t", "final_k", "rerank_budget", "bq",
                                    "multiplicity", "escalate"))
def search_jit_batched(packed: PackedIVF, Q, top_t: int, final_k: int,
                       rerank_budget: int = 256, bq: int = 128,
                       multiplicity: int = 2, filter=None,
                       escalate: bool = True, router=None):
    """`search_jit` streamed over bq-query tiles via lax.map.

    Live buffers are O(bq·top_t·pmax) regardless of nq — the driver for
    large offline batches and the serving engine's bulk path, where a flat
    vmap over nq would blow VMEM/HBM. `filter`/`escalate`/`router` as in
    search_jit (bitmap and router tables are closed over, shared across
    tiles).
    """
    return _search_tiles(packed, Q, top_t, final_k, rerank_budget, bq,
                         multiplicity, filter, escalate, router)


def _search_tiles(packed: PackedIVF, Q, top_t: int, final_k: int,
                  rerank_budget: int, bq: int, multiplicity: int, filter,
                  escalate: bool, router):
    """`_search_block` over bq-query tiles (lax.map), the last tile
    zero-padded and sliced off: the body of search_jit_batched and of each
    shard of core/distributed.make_distributed_search."""
    nq, d = Q.shape
    if nq == 0:          # static at trace time: empty batch, no tiles
        return (jnp.zeros((0, final_k), jnp.int32),
                jnp.zeros((0, final_k), jnp.float32))
    pad = (-nq) % bq
    Qp = jnp.pad(Q, ((0, pad), (0, 0))) if pad else Q
    tiles = Qp.reshape(-1, bq, d)
    ids, vals = jax.lax.map(
        lambda qb: _search_block(packed, qb, top_t, final_k, rerank_budget,
                                 multiplicity, filter, escalate, router),
        tiles)
    k = ids.shape[-1]
    return ids.reshape(-1, k)[:nq], vals.reshape(-1, k)[:nq]
