"""Distributed SOAR serving: database sharded over the mesh, queries
replicated, local IVF search per shard, global top-k merge.

This is the cluster-scale layer of the reproduction (big-ann-benchmarks
scale: 1B+ vectors don't fit one host). Design (DESIGN.md §3.5):

- each shard owns n/D vectors and trains its OWN local VQ codebook +
  (optionally spilled) IVF over them — building is embarrassingly parallel
  and shard-local, exactly how ScaNN serving shards;
- a query batch is replicated to all shards (its bytes are tiny vs the DB);
- each shard runs the fixed-budget jit search (search_jit semantics) over
  its local partitions and emits its local top-k with GLOBAL ids;
- one `all_gather` of (D × nq × k) ids/scores + a replicated top-k merge.
  The collective moves O(nq·k·D) bytes — independent of database size, so
  SOAR's bandwidth frugality survives cluster scale.

Implemented with shard_map over the database axes; runs identically on the
8-device test mesh (tests/test_distributed.py) and the 512-chip production
mesh (dry-run cell `ann_serve`, launch/ann_dryrun.py).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.build import build_ivf_sharded, spill_plan
from repro.core.router import FlatRouter, TreeRouter
from repro.core.search import (EXACT, _pad_topk, _search_block,
                               dedup_topk_window, pack_ivf, window_pq_scores)
from repro.kernels.soar_assign import assign_fused


class ShardedIVF(NamedTuple):
    """Per-shard IVF arrays, stacked over a leading shard dim D."""
    centroids: jax.Array     # (D, c, d)
    part_ids: jax.Array      # (D, c, pmax) int32 GLOBAL point ids, -1 pad
    sizes: jax.Array         # (D, c) int32
    rerank: jax.Array        # (D, n_local, d) — highest-bitrate local shard
    local_base: jax.Array    # (D,) int32 global id offset of each shard


class ShardedIVFPQ(NamedTuple):
    """PQ-scored variant (§Perf H3 — the paper's actual pipeline): per
    ASSIGNMENT codes in partition order; candidates are scored from uint8
    codes (d/(2s)·2 bytes each at uint8 layout) instead of gathering the
    float32 vectors (4d bytes) — 16× less candidate traffic at m=d/4."""
    centroids: jax.Array     # (D, c, d)
    part_ids: jax.Array      # (D, c, pmax) int32 GLOBAL ids, -1 pad
    part_codes: jax.Array    # (D, c, pmax, m) uint8 PQ codes per assignment
    pq_centers: jax.Array    # (D, m, 16, s) per-shard PQ codebook
    sizes: jax.Array         # (D, c) int32
    rerank: jax.Array        # (D, n_local, d)
    local_base: jax.Array    # (D,) int32


class ShardedTreeRouter(NamedTuple):
    """Per-shard TreeRouter tables, stacked over the leading shard dim D
    (each shard trains its own router over its own local centroids, like
    its own codebook). Shards are padded to the common (S, cmax) envelope:
    pad supers are zero rows whose children are all -1, so selecting one
    contributes only -inf candidates (a wasted route slot, never a wrong
    result)."""
    super_centroids: jax.Array   # (D, S, d)
    children: jax.Array          # (D, S, cmax) int32 local partitions, -1 pad
    child_centroids: jax.Array   # (D, S, cmax, d)


def stack_tree_routers(routers) -> ShardedTreeRouter:
    """Stack per-shard TreeRouters (e.g. `idx.router` of each shard built
    with router="tree") into the sharded envelope for the
    `with_router=True` distributed search paths."""
    S = max(r.n_super for r in routers)
    cmax = max(r.cmax for r in routers)
    d = routers[0].d
    D = len(routers)
    SC = np.zeros((D, S, d), np.float32)
    CH = np.full((D, S, cmax), -1, np.int32)
    CC = np.zeros((D, S, cmax, d), np.float32)
    for i, r in enumerate(routers):
        SC[i, :r.n_super] = np.asarray(r.super_centroids)
        CH[i, :r.n_super, :r.cmax] = np.asarray(r.children)
        CC[i, :r.n_super, :r.cmax] = np.asarray(r.child_centroids)
    return ShardedTreeRouter(jnp.asarray(SC), jnp.asarray(CH),
                             jnp.asarray(CC))


def tree_router_pspecs(axes: Tuple[str, ...]) -> ShardedTreeRouter:
    a = axes if len(axes) > 1 else axes[0]
    return ShardedTreeRouter(P(a), P(a), P(a))


def _resolve_shard(idx):
    """Accept IVFIndex or MutableIVF (post-mutation) per shard."""
    from repro.core.mutable import MutableIVF
    return idx.to_ivf_index() if isinstance(idx, MutableIVF) else idx


def _stack_shards(indexes):
    """Shared stacker for the ShardedIVF(PQ) builders/refreshers: resolve
    mutable shards, pack, pad ids to the common pmax envelope (-1
    sentinel) and rerank to the max local id space (zero rows — padded
    ids never appear in any partition slot, so they are unreachable), and
    accumulate cumulative global-id base offsets. Returns
    (packed, resolved, ids, cents, sizes, reranks, bases)."""
    resolved = list(map(_resolve_shard, indexes))
    packed = [pack_ivf(idx, pair_codes=False) for idx in resolved]
    n_locals = [idx.n_points for idx in resolved]
    pmax = max(pk.part_ids.shape[1] for pk in packed)
    nmax = max(n_locals)
    cents, ids, sizes, reranks, bases = [], [], [], [], []
    base = 0
    for pk, nl in zip(packed, n_locals):
        pad = pmax - pk.part_ids.shape[1]
        ids.append(np.pad(np.asarray(pk.part_ids), ((0, 0), (0, pad)),
                          constant_values=-1))
        cents.append(np.asarray(pk.centroids))
        sizes.append(np.asarray(pk.sizes))
        reranks.append(np.pad(np.asarray(pk.rerank),
                              ((0, nmax - nl), (0, 0))))
        bases.append(base)
        base += nl
    return packed, resolved, ids, cents, sizes, reranks, bases


def sharded_from_indexes(indexes) -> ShardedIVF:
    """Stack per-shard indexes (IVFIndex or MutableIVF) into a ShardedIVF.

    The refresh path after online mutation: each shard's live snapshot is
    packed and padded to the common (pmax, n_local) envelope; local ids
    keep their shard-stable values and globalize via the cumulative base
    offsets.
    """
    _, _, ids, cents, sizes, reranks, bases = _stack_shards(indexes)
    return ShardedIVF(
        jnp.asarray(np.stack(cents)), jnp.asarray(np.stack(ids)),
        jnp.asarray(np.stack(sizes)), jnp.asarray(np.stack(reranks)),
        jnp.asarray(np.array(bases, np.int32)))


def build_sharded_ivf(key, X: np.ndarray, n_shards: int, n_partitions: int,
                      spill_mode: str = "soar", lam: float = 1.0,
                      train_iters: int = 8) -> ShardedIVF:
    """Host-side build: split X row-wise, build one spilled IVF per shard.

    Each per-shard build runs the streamed driver (core/build.py), so peak
    accelerator memory is O(shard tile) rather than O(n/D).
    """
    n = X.shape[0]
    assert n % n_shards == 0
    nl = n // n_shards
    indexes = [
        build_ivf_sharded(jax.random.fold_in(key, s),
                          X[s * nl:(s + 1) * nl], n_partitions,
                          spill_mode=spill_mode, lam=lam,
                          train_iters=train_iters)
        for s in range(n_shards)
    ]
    return sharded_from_indexes(indexes)


def make_sharded_assign(mesh, axes: Tuple[str, ...], *,
                        spill_mode: str = "soar", lam: float = 1.0,
                        n_spills: int = 1, chunk: int = 8192):
    """Build-side shard_map: fn(X rows sharded over `axes`, C replicated)
    → (n, 1 + n_spills) assignments, sharded like X.

    Assignment against a frozen replicated codebook is embarrassingly
    parallel — no collectives — which is exactly why the sharded build
    scales linearly with the mesh (DESIGN.md §3.7). Routes through the
    same `assign_fused` dispatcher as every other entry point (Pallas on
    TPU, chunked GEMM elsewhere; spill_mode semantics via spill_plan).
    Pairs with the serving local-search paths above, which consume the
    resulting per-shard CSR.
    """
    eff_lam, eff_spills = spill_plan(spill_mode, lam, n_spills)

    def local(Xs, C):
        return assign_fused(Xs, C, lam=eff_lam, n_spills=eff_spills,
                            chunk=chunk)

    a = axes if len(axes) > 1 else axes[0]
    return jax.shard_map(local, mesh=mesh, in_specs=(P(a), P()),
                         out_specs=P(a), check_vma=False)


def abstract_sharded_ivf(n_shards: int, n_local: int, n_partitions: int,
                         pmax: int, d: int) -> ShardedIVF:
    """ShapeDtypeStruct stand-in for the production-scale dry run."""
    f = jax.ShapeDtypeStruct
    return ShardedIVF(
        f((n_shards, n_partitions, d), jnp.float32),
        f((n_shards, n_partitions, pmax), jnp.int32),
        f((n_shards, n_partitions), jnp.int32),
        f((n_shards, n_local, d), jnp.float32),
        f((n_shards,), jnp.int32))


def abstract_sharded_ivf_pq(n_shards: int, n_local: int, n_partitions: int,
                            pmax: int, d: int, m: int) -> ShardedIVFPQ:
    f = jax.ShapeDtypeStruct
    return ShardedIVFPQ(
        f((n_shards, n_partitions, d), jnp.float32),
        f((n_shards, n_partitions, pmax), jnp.int32),
        f((n_shards, n_partitions, pmax, m), jnp.uint8),
        f((n_shards, m, 16, d // m), jnp.float32),
        f((n_shards, n_partitions), jnp.int32),
        f((n_shards, n_local, d), jnp.float32),
        f((n_shards,), jnp.int32))


def sharded_ivf_pspecs(axes: Tuple[str, ...]) -> ShardedIVF:
    a = axes if len(axes) > 1 else axes[0]
    return ShardedIVF(P(a), P(a), P(a), P(a), P(a))


def sharded_ivf_pq_pspecs(axes: Tuple[str, ...]) -> ShardedIVFPQ:
    a = axes if len(axes) > 1 else axes[0]
    return ShardedIVFPQ(P(a), P(a), P(a), P(a), P(a), P(a), P(a))


def stack_filters(masks, n_local_max: Optional[int] = None) -> jax.Array:
    """Per-shard LOCAL-id filter bitmaps → (D, nmax) uint8, zero-padded.

    Padded local ids never appear in any partition slot, and a 0 bit only
    re-masks them, so over-padding is harmless. Feed the result to the
    filtered distributed search paths (sharded like the index arrays).
    """
    masks = [np.asarray(m).astype(np.uint8).ravel() for m in masks]
    nmax = int(max(m.shape[0] for m in masks)
               if n_local_max is None else n_local_max)
    out = np.zeros((len(masks), nmax), np.uint8)
    for i, m in enumerate(masks):
        out[i, :m.shape[0]] = m
    return jnp.asarray(out)


def shard_filters(global_mask, n_locals) -> jax.Array:
    """Split a GLOBAL-id bitmap into the stacked per-shard local layout.

    Global ids are the cumulative-base globalization of shard-local ids
    (ShardedIVF.local_base), so shard s's slice is simply
    global_mask[base_s : base_s + n_local_s].
    """
    gm = np.asarray(global_mask).astype(np.uint8).ravel()
    total = int(sum(n_locals))
    assert gm.shape[0] == total, (
        f"global mask covers {gm.shape[0]} ids but shards hold {total} — "
        f"a short mask would silently zero-fill (exclude) trailing shards")
    out, off = [], 0
    for nl in n_locals:
        out.append(gm[off:off + nl])
        off += nl
    return stack_filters(out)


def _local_router(C, srt, t_route):
    """Per-shard probe router inside shard_map: the shard's stacked tree
    tables when given (squeezing the size-1 lead dim), else the flat probe
    over the local centroids — op-for-op the historical inline GEMM."""
    if srt is None:
        return FlatRouter(C)
    S = srt.super_centroids.shape[1]
    return TreeRouter(srt.super_centroids[0], srt.children[0],
                      srt.child_centroids[0],
                      t_route=(max(1, -(-S // 8)) if t_route is None
                               else t_route),
                      n_partitions=C.shape[0])


def _shard_map_variants(local_search, mesh, spec, axes, with_filter,
                        with_router, with_health=False):
    """shard_map wiring shared by both distributed search makers: the
    optional filter bitmap, router-table, and health-mask args extend
    in_specs in a fixed order (ivf, Q[, filt][, router][, health])."""
    a = axes if len(axes) > 1 else axes[0]
    specs = [spec, P()]
    if with_filter:
        specs.append(P(a))
    if with_router:
        specs.append(tree_router_pspecs(axes))
    if with_health:
        specs.append(P(a))

    def fn(ivf, Q, *rest):
        it = iter(rest)
        filt = next(it) if with_filter else None
        srt = next(it) if with_router else None
        health = next(it) if with_health else None
        return local_search(ivf, Q, filt, srt, health)

    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(specs),
                         out_specs=(P(), P()), check_vma=False)


def _mask_unhealthy(ids, vals, health):
    """Degraded fan-out (DESIGN.md §3.13): zero out a DOWN shard's local
    contribution before the global merge — its candidate rows become the
    (-1, -inf) padding sentinel, so the merged top-k comes entirely from
    the healthy shards (partial results, never a hang and never a stale
    answer attributed to a dead target). `health` is the (D,) uint8
    bitmap (HealthTracker.mask), sharded like the index, so each shard
    sees its own (1,) slice. With every bit set the select copies
    ids/vals through unchanged — healthy-path results stay
    bitwise-identical to the non-health trace (pinned in
    tests/test_resilience.py)."""
    if health is None:
        return ids, vals
    ok = health[0] > 0
    return (jnp.where(ok, ids, -1).astype(jnp.int32),
            jnp.where(ok, vals, -jnp.inf))


def make_replicated_search(mesh, axes: Tuple[str, ...], *, top_t: int,
                           final_k: int, rerank_budget: int = 256,
                           multiplicity: int = 2, with_filter: bool = False,
                           escalate: bool = True, params=None):
    """DATA-PARALLEL replica fan-out (DESIGN.md §3.12): the full packed
    index is REPLICATED on every device and the QUERY batch is sharded
    over `axes` — the dual of make_distributed_search, which shards the
    database and replicates queries. Returns a jit-able
    fn(PackedIVF, Q[, filter]) → (ids, scores), Q row count divisible by
    the mesh axis size (serve callers get this from
    pad_queries(multiple=R)).

    Each replica runs the SAME single-host candidate-local pipeline
    (`_search_block`, filtered escalation included) on its query slice
    with NO collectives — per-query results are bitwise identical to the
    single-device path, so a serve-time policy can flip between replica
    and shard-parallel execution without changing any answer. Replica
    fan-out is the right policy while the index fits one device and
    throughput is query-bound (the front-end's default when devices > 1);
    the shard-parallel path takes over when n outgrows device memory.

    `params`: optional serve/api.SearchParams overriding k/top_t/
    rerank_budget/escalate — the unified request API's route into the
    distributed layer (make_distributed_search takes it too).

    with_filter=True: the fn takes a trailing (n,) uint8 GLOBAL-id bitmap
    (replicated — every replica holds all ids), e.g. a tenant bitmap from
    the front-end's TenantFilterBank.

    Degraded mode (§3.13) is intentionally NOT a mask here, unlike the
    shard-parallel makers: replicas hold disjoint QUERY slices of one
    batch, so masking a dead replica would lose its queries' answers
    rather than narrow their coverage. The degraded path for replica
    fan-out lives at the front-end: a failed replica dispatch trips the
    per-target circuit breaker and the batch re-dispatches on the local
    single-device path (same data, full coverage), flagged
    `SearchResult.degraded`.
    """
    if params is not None:
        p = params.validate(default_top_t=top_t,
                            default_rerank=rerank_budget)
        top_t, final_k = p.top_t, p.k
        rerank_budget, escalate = p.rerank_budget, p.escalate

    a = axes if len(axes) > 1 else axes[0]

    def local(packed, Q, filt=None):
        return _search_block(packed, Q, top_t, final_k, rerank_budget,
                             multiplicity, filt, escalate)

    fn = (local if with_filter
          else (lambda packed, Q: local(packed, Q)))
    specs = [P(), P(a)] + ([P()] if with_filter else [])
    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(specs),
                         out_specs=(P(a), P(a)), check_vma=False)


def _apply_params(params, top_t, final_k):
    """Resolve a serve/api.SearchParams against a distributed maker's
    kwargs — the unified request API's seam into this layer."""
    if params is None:
        return top_t, final_k, True
    p = params.validate(default_top_t=top_t)
    return p.top_t, p.k, p.escalate


def make_distributed_search(mesh, axes: Tuple[str, ...], *, top_t: int,
                            final_k: int = 10, multiplicity: int = 2,
                            with_filter: bool = False,
                            with_router: bool = False,
                            t_route: Optional[int] = None,
                            with_health: bool = False,
                            params=None):
    """Returns jit-able fn(ShardedIVF, Q (nq, d)) → (ids, scores) global.

    Pass multiplicity ≥ 1 + n_spills when serving multi-spill shards
    (dedup_topk_window's correctness bound); default 2 covers the
    single-spill "naive"/"soar" builds.

    with_filter=True: the returned fn takes an extra argument — a (D, n_local)
    uint8 LOCAL-id bitmap (stack_filters / shard_filters), sharded like the
    index — and masks candidates per gathered window before dedup, exactly
    the §3.9 subset semantics of the single-host engines.

    with_router=True: the fn takes a trailing ShardedTreeRouter argument
    (stack_tree_routers over the shards' build-time routers) and probes
    through each shard's two-level router at the given `t_route` (default
    ceil(S/8)) instead of the flat local GEMM — the per-shard O(c)→O(√c)
    probe reduction, shard-local like everything else.

    with_health=True: the fn takes a FINAL (D,) uint8 health bitmap
    (HealthTracker.mask, sharded like the index) and serves top-k from
    the HEALTHY shards only — a down shard's candidates become (-1,
    -inf) padding before the global merge (partial results, DESIGN.md
    §3.13). An all-ones mask is bitwise-identical to the
    with_health=False results.

    params: optional serve/api.SearchParams whose k/top_t override the
    kwargs (the unified request API, DESIGN.md §3.12).
    """
    top_t, final_k, _ = _apply_params(params, top_t, final_k)

    def local_search(ivf: ShardedIVF, Q, filt=None, srt=None, health=None):
        # leading shard dim is size 1 inside shard_map — squeeze it
        C = ivf.centroids[0]
        part_ids = ivf.part_ids[0]
        rerank = ivf.rerank[0]
        base = ivf.local_base[0]

        # batched: one router probe, then candidate-local dedup — no
        # intermediate scales with the shard size (DESIGN.md §3.6)
        router = _local_router(C, srt, t_route)
        _, parts = router.route(Q, router.clamp(top_t))
        ids = part_ids[parts].reshape(Q.shape[0], -1)      # (nq, t·pmax) local
        valid = ids >= 0
        if filt is not None:
            valid = valid & (filt[0][jnp.maximum(ids, 0)] > 0)
            ids = jnp.where(valid, ids, -1)    # filtered ≡ padding for dedup
        scores = jnp.einsum("qwd,qd->qw", rerank[jnp.maximum(ids, 0)], Q,
                            precision=EXACT)
        scores = jnp.where(valid, scores, -jnp.inf)
        ids, vals = dedup_topk_window(ids, scores, final_k, multiplicity)
        # a tombstone-heavy mutable shard (sharded_from_indexes) can have a
        # window narrower than final_k — pad to keep the merge shapes fixed
        ids, vals = _pad_topk(ids, vals, final_k)
        # globalize local ids, preserving the -1 padding sentinel (an
        # under-filled window must not alias into the previous shard)
        ids = jnp.where(ids >= 0, ids + base, -1).astype(jnp.int32)
        ids, vals = _mask_unhealthy(ids, vals, health)
        # global merge: gather every shard's candidates, re-top-k
        ax = axes[0] if len(axes) == 1 else axes
        all_ids = jax.lax.all_gather(ids, ax, tiled=False)   # (D, nq, k)
        all_vals = jax.lax.all_gather(vals, ax, tiled=False)
        if len(axes) > 1:   # gathered over multiple axes → extra lead dims
            all_ids = all_ids.reshape((-1,) + ids.shape)
            all_vals = all_vals.reshape((-1,) + vals.shape)
        D = all_ids.shape[0]
        flat_v = jnp.moveaxis(all_vals, 0, 1).reshape(Q.shape[0], D * final_k)
        flat_i = jnp.moveaxis(all_ids, 0, 1).reshape(Q.shape[0], D * final_k)
        v, pos = jax.lax.top_k(flat_v, final_k)
        return jnp.take_along_axis(flat_i, pos, axis=1), v

    return _shard_map_variants(local_search, mesh, sharded_ivf_pspecs(axes),
                               axes, with_filter, with_router, with_health)


def make_distributed_search_pq(mesh, axes: Tuple[str, ...], *, top_t: int,
                               final_k: int = 10, rerank_k: int = 256,
                               q_chunk: int = 128, multiplicity: int = 2,
                               with_filter: bool = False,
                               with_router: bool = False,
                               t_route: Optional[int] = None,
                               with_health: bool = False,
                               params=None):
    """PQ-scored distributed search (§Perf H3 — the paper's own pipeline).

    Per shard per q_chunk tile: batched centroid top-t → PQ-score the
    gathered t·pmax candidate windows from their uint8 codes (Pallas one-hot
    MXU kernel on TPU, + the router's coarse score) → candidate-local
    dedup-by-max + top rerank_k over the window → exact rerank of only
    those from the float data → local top-k → global all_gather merge.
    Tiles stream through lax.map to bound the live candidate buffers
    (baseline peaked at 16 GiB gathering f32 candidates).

    with_filter as in make_distributed_search: fn gains a (D, n_local)
    uint8 local-id bitmap argument masking candidates pre-dedup.
    with_router/t_route as in make_distributed_search: a trailing
    ShardedTreeRouter argument replaces the flat local probe.
    with_health as in make_distributed_search: a final (D,) uint8 health
    bitmap masks down shards out of the merge (§3.13 partial results).
    params: optional serve/api.SearchParams overriding k/top_t (§3.12).
    """
    top_t, final_k, _ = _apply_params(params, top_t, final_k)

    def local_search(ivf: ShardedIVFPQ, Q, filt=None, srt=None,
                     health=None):
        C = ivf.centroids[0]
        part_ids = ivf.part_ids[0]
        part_codes = ivf.part_codes[0]
        pqc = ivf.pq_centers[0]                   # (m, 16, s)
        rerank = ivf.rerank[0]
        base = ivf.local_base[0]
        fbits = None if filt is None else filt[0]
        m = pqc.shape[0]
        s = pqc.shape[2]
        pmax = part_ids.shape[1]
        router = _local_router(C, srt, t_route)
        tt = router.clamp(top_t)

        def tile(Qb):                                      # (bq, d)
            psc, parts = router.route(Qb, tt)
            bq = Qb.shape[0]
            tw = parts.shape[-1]         # router may return fewer than tt
            ids = part_ids[parts].reshape(bq, -1)          # (bq, t·pmax)
            valid = ids >= 0
            if fbits is not None:
                valid = valid & (fbits[jnp.maximum(ids, 0)] > 0)
                ids = jnp.where(valid, ids, -1)
            codes = part_codes[parts].reshape(bq, tw * pmax, m)
            luts = jnp.einsum("qms,mks->qmk", Qb.reshape(bq, m, s), pqc)
            approx = window_pq_scores(luts, codes)
            approx = approx + jnp.repeat(psc, pmax, axis=-1)
            approx = jnp.where(valid, approx, -jnp.inf)
            bi, bv = dedup_topk_window(ids, approx, rerank_k, multiplicity)
            exact = jnp.einsum("qbd,qd->qb", rerank[jnp.maximum(bi, 0)], Qb,
                               precision=EXACT)
            exact = jnp.where(jnp.isfinite(bv), exact, -jnp.inf)
            v, pos = jax.lax.top_k(exact, min(final_k, exact.shape[-1]))
            gi, v = _pad_topk(jnp.take_along_axis(bi, pos, axis=-1), v,
                              final_k)
            # keep the -1 sentinel out of the global id space: an
            # under-filled tombstone-heavy shard must not alias elsewhere
            return jnp.where(gi >= 0, gi + base, -1).astype(jnp.int32), v

        nq = Q.shape[0]
        Qc = Q.reshape(nq // q_chunk, q_chunk, -1)
        ids, vals = jax.lax.map(tile, Qc)
        ids = ids.reshape(nq, final_k)
        vals = vals.reshape(nq, final_k)
        ids, vals = _mask_unhealthy(ids, vals, health)
        ax = axes[0] if len(axes) == 1 else axes
        all_ids = jax.lax.all_gather(ids, ax, tiled=False)
        all_vals = jax.lax.all_gather(vals, ax, tiled=False)
        if len(axes) > 1:
            all_ids = all_ids.reshape((-1,) + ids.shape)
            all_vals = all_vals.reshape((-1,) + vals.shape)
        D = all_ids.shape[0]
        flat_v = jnp.moveaxis(all_vals, 0, 1).reshape(nq, D * final_k)
        flat_i = jnp.moveaxis(all_ids, 0, 1).reshape(nq, D * final_k)
        v, pos = jax.lax.top_k(flat_v, final_k)
        return jnp.take_along_axis(flat_i, pos, axis=1), v

    return _shard_map_variants(local_search, mesh,
                               sharded_ivf_pq_pspecs(axes), axes,
                               with_filter, with_router, with_health)


def sharded_from_indexes_pq(indexes) -> ShardedIVFPQ:
    """Stack per-shard PQ indexes (IVFIndex or MutableIVF) — the refresh
    path that re-serves per-shard indexes after online mutation."""
    packed, resolved, ids, cents, sizes, reranks, bases = (
        _stack_shards(indexes))
    pmax = ids[0].shape[1]
    codes, pqcs = [], []
    for pk, idx in zip(packed, resolved):
        pad = pmax - pk.part_ids.shape[1]
        codes.append(np.pad(np.asarray(pk.part_codes),
                            ((0, 0), (0, pad), (0, 0))))
        pqcs.append(np.asarray(idx.pq.centers))
    return ShardedIVFPQ(
        jnp.asarray(np.stack(cents)), jnp.asarray(np.stack(ids)),
        jnp.asarray(np.stack(codes)), jnp.asarray(np.stack(pqcs)),
        jnp.asarray(np.stack(sizes)), jnp.asarray(np.stack(reranks)),
        jnp.asarray(np.array(bases, np.int32)))


# ------------------------------------------------------------- durability
def save_sharded(path: str, indexes, *, extra=None):
    """Per-shard snapshot envelope (DESIGN.md §3.11): one integrity-
    checked snapshot subdir per shard (IVFIndex or MutableIVF — full
    mutation state survives) plus an envelope manifest, committed with a
    single atomic directory swap. Keep the PER-SHARD indexes around for
    saving rather than the stacked device arrays: the envelope restores
    them, and `sharded_from_indexes(_pq)` restacks bitwise."""
    from repro.ckpt.index_store import save_shards
    save_shards(path, indexes, extra=extra)


def load_sharded(path: str):
    """→ (per-shard index objects, extra). Restack with
    `sharded_from_indexes` / `sharded_from_indexes_pq`; any torn or
    bit-flipped shard raises CorruptSnapshotError at load."""
    from repro.ckpt.index_store import load_shards
    return load_shards(path)


def build_sharded_ivf_pq(key, X: np.ndarray, n_shards: int, n_partitions: int,
                         pq_subspaces: int, spill_mode: str = "soar",
                         lam: float = 1.0, train_iters: int = 8
                         ) -> ShardedIVFPQ:
    """Host-side build of the PQ-scored sharded index (streamed per shard)."""
    n = X.shape[0]
    assert n % n_shards == 0
    nl = n // n_shards
    indexes = [
        build_ivf_sharded(jax.random.fold_in(key, sh),
                          X[sh * nl:(sh + 1) * nl], n_partitions,
                          spill_mode=spill_mode, lam=lam,
                          pq_subspaces=pq_subspaces, train_iters=train_iters)
        for sh in range(n_shards)
    ]
    return sharded_from_indexes_pq(indexes)
