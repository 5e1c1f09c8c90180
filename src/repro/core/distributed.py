"""Distributed SOAR serving: database sharded over the mesh, queries
replicated, local IVF search per shard, global top-k merge.

This is the cluster-scale layer of the reproduction (big-ann-benchmarks
scale: 1B+ vectors don't fit one host). Design (DESIGN.md §3.5):

- each shard owns n/D vectors and trains its OWN local VQ codebook +
  (optionally spilled) IVF over them — building is embarrassingly parallel
  and shard-local, exactly how ScaNN serving shards;
- a query batch is replicated to all shards (its bytes are tiny vs the DB);
- each shard runs the single-host search pass (`_search_block`, in the
  bq-query tiles of search_jit_batched) over its own PackedIVF and emits
  its local top-k with GLOBAL ids;
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
from repro.core.search import (PackedIVF, _search_block, _search_tiles,
                               pack_ivf, rerank_table_shape)
from repro.kernels.pq_score import code_table_shape
from repro.kernels.soar_assign import assign_fused
from repro.quant.pq import PQCodebook


class ShardedIVF(NamedTuple):
    """One `PackedIVF` per shard, stacked: every leaf of `packed` carries
    a leading shard dim D (centroids (D, c, d), part_ids (D, c, pmax)
    shard-LOCAL ids with -1 padding, the rerank table (D, R, 128), and,
    on a PQ-scored index, the code table part_codes (D, c, R', W·128)
    and pq.centers (D, m, 16, s)). An exact-score index has part_codes
    and pq None, as a single-host PackedIVF does; part_codes2 and router
    are always None (each shard's router travels as its own argument)."""
    packed: PackedIVF
    local_base: jax.Array    # (D,) int32 global id offset of each shard


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
    with router="tree") into the sharded envelope that
    make_distributed_search's `router` argument takes."""
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


def shard_spec(axes: Tuple[str, ...]) -> P:
    """The PartitionSpec of every sharded argument (index, filter, router
    tables, health mask): the leading shard dim split over `axes`. A spec
    is a pytree prefix, so this one spec lays out every leaf of a
    ShardedIVF or ShardedTreeRouter."""
    return P(axes if len(axes) > 1 else axes[0])


def _resolve_shard(idx):
    """Accept IVFIndex or MutableIVF (post-mutation) per shard."""
    from repro.core.mutable import MutableIVF
    return idx.to_ivf_index() if isinstance(idx, MutableIVF) else idx


def _padded(a, axis: int, width: int, fill=0) -> np.ndarray:
    a = np.asarray(a)
    pads = [(0, 0)] * a.ndim
    pads[axis] = (0, width - a.shape[axis])
    return np.pad(a, pads, constant_values=fill)


def sharded_from_indexes(indexes) -> ShardedIVF:
    """Stack per-shard indexes (IVFIndex or MutableIVF) into a ShardedIVF.

    Each shard's live snapshot is packed and padded to the common
    envelope: partitions to the longest one (-1 ids; zero code-table rows,
    whose slots the -1 ids mask), the rerank table to the longest one
    (zero rows — the ids they would hold never appear in any partition
    slot, so they are unreachable). The PQ codes and codebooks are
    stacked iff the shards have them. Local ids keep their shard-stable
    values and globalize through the cumulative base offsets, so this is
    also the refresh path after online mutation.
    """
    shards = list(map(_resolve_shard, indexes))
    packed = [pack_ivf(idx, pair_codes=False) for idx in shards]
    pmax = max(pk.part_ids.shape[1] for pk in packed)
    rmax = max(pk.rerank.shape[0] for pk in packed)
    has_pq = packed[0].part_codes is not None
    code_rows = max(pk.part_codes.shape[1] for pk in packed) if has_pq else 0

    def stack(arrays):
        return jnp.asarray(np.stack([np.asarray(a) for a in arrays]))

    bases = np.cumsum([0] + [idx.n_points for idx in shards[:-1]])
    return ShardedIVF(PackedIVF(
        centroids=stack(pk.centroids for pk in packed),
        part_ids=stack(_padded(pk.part_ids, 1, pmax, -1) for pk in packed),
        part_codes=(stack(_padded(pk.part_codes, 1, code_rows)
                          for pk in packed) if has_pq else None),
        part_codes2=None,
        sizes=stack(pk.sizes for pk in packed),
        pq=(PQCodebook(stack(idx.pq.centers for idx in shards))
            if has_pq else None),
        rerank=stack(_padded(pk.rerank, 0, rmax) for pk in packed)),
        jnp.asarray(bases.astype(np.int32)))


def build_sharded_ivf(key, X: np.ndarray, n_shards: int, n_partitions: int,
                      spill_mode: str = "soar", lam: float = 1.0,
                      train_iters: int = 8,
                      pq_subspaces: int = 0) -> ShardedIVF:
    """Host-side build: split X row-wise, build one spilled IVF per shard
    (PQ-scored with `pq_subspaces` > 0, else exact-scored).

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
                          pq_subspaces=pq_subspaces, train_iters=train_iters)
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
                         pmax: int, d: int, m: int = 0) -> ShardedIVF:
    """ShapeDtypeStruct stand-in for the production-scale dry run; m > 0
    makes it PQ-scored with m subspaces."""
    def f(shape, dtype):
        return jax.ShapeDtypeStruct((n_shards,) + shape, dtype)
    return ShardedIVF(PackedIVF(
        f((n_partitions, d), jnp.float32),
        f((n_partitions, pmax), jnp.int32),
        f(code_table_shape(n_partitions, pmax, m), jnp.int32) if m else None,
        None,
        f((n_partitions,), jnp.int32),
        PQCodebook(f((m, 16, d // m), jnp.float32)) if m else None,
        f(rerank_table_shape(n_local, d), jnp.float32)),
        f((), jnp.int32))


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


def _global_merge(ids, vals, axes: Tuple[str, ...], final_k: int):
    """Shard-parallel merge, under the `merge` named scope: gather every
    shard's (nq, k) candidates over `axes` and re-top-k them."""
    with jax.named_scope("merge"):
        ax = axes[0] if len(axes) == 1 else axes
        all_ids = jax.lax.all_gather(ids, ax, tiled=False)   # (D, nq, k)
        all_vals = jax.lax.all_gather(vals, ax, tiled=False)
        if len(axes) > 1:   # gathered over multiple axes → extra lead dims
            all_ids = all_ids.reshape((-1,) + ids.shape)
            all_vals = all_vals.reshape((-1,) + vals.shape)
        nq, D = ids.shape[0], all_ids.shape[0]
        flat_v = jnp.moveaxis(all_vals, 0, 1).reshape(nq, D * final_k)
        flat_i = jnp.moveaxis(all_ids, 0, 1).reshape(nq, D * final_k)
        v, pos = jax.lax.top_k(flat_v, final_k)
        return jnp.take_along_axis(flat_i, pos, axis=1), v


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
    shard-parallel maker: replicas hold disjoint QUERY slices of one
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


def replicate_on_mesh(tree, mesh):
    """`tree` (a PackedIVF, a filter bitmap) laid out as
    NamedSharding(mesh, P()) — the layout make_replicated_search's index
    and filter arguments expect, so its calls move no index bytes.

    Each leaf, a device array on one device of the mesh, keeps that
    buffer as its shard there (that device holds it once) and is copied
    once to each other device. Passing the single-device tree instead
    makes JAX broadcast all of it on every call (DESIGN.md §3.12)."""
    from jax.sharding import NamedSharding
    rep = NamedSharding(mesh, P())

    def one(x):
        shards = [x if d in x.devices() else jax.device_put(x, d)
                  for d in mesh.devices.flat]
        return jax.make_array_from_single_device_arrays(x.shape, rep, shards)

    return jax.tree_util.tree_map(one, tree)


def make_distributed_search(mesh, axes: Tuple[str, ...], *, top_t: int,
                            final_k: int = 10, rerank_budget: int = 256,
                            bq: int = 128, multiplicity: int = 2,
                            t_route: Optional[int] = None, params=None):
    """Shard-parallel search: returns a jit-able fn(index: ShardedIVF,
    Q (nq, d), filter=None, router=None, health=None) → (ids, scores),
    each (nq, final_k), ids global.

    Each shard runs the single-host pass (`_search_block`, the same named
    stages) over its own PackedIVF, in bq-query tiles as
    search_jit_batched does, globalizes its local top-k and masks it by
    `health`; one all_gather and top-k then merge the shards. The sharded
    path has no escalation pass: `escalate` is always off. `rerank_budget`
    is the PQ stage's budget (an exact-score index ignores it); pass
    multiplicity ≥ 1 + n_spills when serving multi-spill shards
    (dedup_topk_window's correctness bound).

    The optional arguments, each sharded like the index:

    - filter: (D, n_local) uint8 LOCAL-id bitmap (stack_filters /
      shard_filters), masking candidates per gathered window before
      dedup — the §3.9 subset semantics of the single-host engines;
    - router: a ShardedTreeRouter (stack_tree_routers over the shards'
      build-time routers): each shard probes through its own two-level
      router at `t_route` (default ceil(S/8)) instead of the flat GEMM
      over its centroids;
    - health: (D,) uint8 (HealthTracker.mask): a down shard's candidates
      become (-1, -inf) padding before the merge, so the top-k comes from
      the healthy shards (partial results, DESIGN.md §3.13). All ones is
      bitwise the health=None result.

    params: optional serve/api.SearchParams whose k/top_t/rerank_budget
    override the kwargs (the unified request API, DESIGN.md §3.12).
    """
    if params is not None:
        p = params.validate(default_top_t=top_t, default_rerank=rerank_budget)
        top_t, final_k, rerank_budget = p.top_t, p.k, p.rerank_budget
    spec = shard_spec(axes)

    def local(index: ShardedIVF, Q, filt, srt, health):
        # the leading shard dim is size 1 inside shard_map — squeeze it
        packed = jax.tree_util.tree_map(lambda x: x[0], index.packed)
        ids, vals = _search_tiles(
            packed, Q, top_t, final_k, rerank_budget, bq, multiplicity,
            None if filt is None else filt[0], False,
            _local_router(packed.centroids, srt, t_route))
        # globalize local ids, preserving the -1 padding sentinel (an
        # under-filled window must not alias into the previous shard)
        ids = jnp.where(ids >= 0, ids + index.local_base[0],
                        -1).astype(jnp.int32)
        ids, vals = _mask_unhealthy(ids, vals, health)
        return _global_merge(ids, vals, axes, final_k)

    search = jax.shard_map(local, mesh=mesh,
                           in_specs=(spec, P(), spec, spec, spec),
                           out_specs=(P(), P()), check_vma=False)

    def fn(index: ShardedIVF, Q, filter=None, router=None, health=None):
        return search(index, Q, filter, router, health)

    return fn


# ------------------------------------------------------------- durability
def save_sharded(path: str, indexes, *, extra=None):
    """Per-shard snapshot envelope (DESIGN.md §3.11): one integrity-
    checked snapshot subdir per shard (IVFIndex or MutableIVF — full
    mutation state survives) plus an envelope manifest, committed with a
    single atomic directory swap. Keep the PER-SHARD indexes around for
    saving rather than the stacked device arrays: the envelope restores
    them, and `sharded_from_indexes` restacks bitwise."""
    from repro.ckpt.index_store import save_shards
    save_shards(path, indexes, extra=extra)


def load_sharded(path: str):
    """→ (per-shard index objects, extra). Restack with
    `sharded_from_indexes`; any torn or
    bit-flipped shard raises CorruptSnapshotError at load."""
    from repro.ckpt.index_store import load_shards
    return load_shards(path)
