"""Mutable packed SOAR index: online insert/delete over a frozen codebook
(DESIGN.md §3.7).

A serving index cannot rebuild-the-world per mutation. Following the
SPANN/ScaNN playbook, the VQ codebook and PQ codebook are FROZEN at build
time, which makes mutations local:

- **insert**: the new vectors' primary + SOAR spill assignments are one
  fused-assign call against the fixed centroids (`kernels/soar_assign.py`)
  plus PQ encoding of their residuals — O(batch · c), nothing global moves;
- **delete**: a tombstone — the point's partition slots are blanked to -1
  (exactly the padding sentinel the search pipeline already masks to -inf),
  so deletion needs no data movement at all;
- **compaction**: tombstones waste probed-window slots, so when more than
  `compact_threshold` of occupied slots are dead, one vectorized pass
  shifts live slots left per partition and shrinks `sizes`.

Partition arrays are padded to a capacity that grows geometrically, so
appends are amortized O(batch). Point ids are STABLE across every mutation
(external handles never dangle); id space is append-only and dead rerank
rows are reclaimed only by `compact(reclaim=True)`.

Search serves from snapshots: `pack()` → PackedIVF for the candidate-local
jit pipeline, `to_ivf_index()` → CSR IVFIndex for the numpy engine. The
packed snapshot is maintained INCREMENTALLY (delta pack): the device
arrays are cached at the padded capacity width, mutations record which
partitions (and which appended rerank rows) they touched, and the next
`pack()` scatters only those rows into the cached arrays — skipping the
host-side O(index) re-pack and the full host→device re-upload (the
device-side buffer copies remain; see `_apply_pack_delta`). Because the
width is capacity-stable, the serving jit pipeline also stops
recompiling when pmax drifts across mutations.
Slot growth or compaction fall back to a full repack. The CSR snapshot
stays invalidate-on-mutation (the numpy engine re-reads it wholesale).
The equivalence contract — an index mutated into a state equals a
from-scratch build of that state against the same frozen stages — is
pinned by tests/test_mutable.py; delta-pack vs full-pack identity by
tests/test_build_perf.py.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.wal import REC_ADD, REC_COMPACT, REC_HARDEN, REC_REMOVE
from repro.core.build import build_ivf_sharded, spill_plan
from repro.core.ivf import IVFIndex
from repro.core.search import LANES, PackedIVF, _paired_codes, rerank_table
from repro.kernels.pq_score import code_table
from repro.kernels.soar_assign import assign_fused
from repro.quant.pq import PQCodebook, pq_encode


def _grow_rows(arr: np.ndarray, n_new: int, fill) -> np.ndarray:
    """Geometric row growth to at least n_new rows."""
    if arr.shape[0] >= n_new:
        return arr
    cap = max(n_new, 2 * arr.shape[0], 64)
    out = np.full((cap,) + arr.shape[1:], fill, arr.dtype)
    out[:arr.shape[0]] = arr
    return out


class EpochLRU:
    """Epoch-keyed LRU of derived values (device filter bitmaps).

    Generalizes the PR 5 standing-filter cache OUT of MutableIVF: an
    entry is (epoch, value) under a caller key; `get` returns the cached
    value only while the epoch matches, else rebuilds via the callback
    and refreshes the entry. Capacity-1 instances back the index's own
    standing tombstone bitmap; the serving front-end's TenantFilterBank
    (serve/frontend.py, DESIGN.md §3.12) holds a capacity-N instance
    keyed by tenant, so steady-state tenant serving pays zero per-search
    host composition or upload, and a mutation (epoch bump) invalidates
    every tenant's bitmap at once without touching device memory until a
    tenant is next served."""

    def __init__(self, capacity: int = 1):
        from collections import OrderedDict
        self.capacity = max(1, int(capacity))
        self._d = OrderedDict()
        self.fills = 0              # cache-miss rebuilds (tests/telemetry)

    def get(self, key, epoch, build):
        hit = self._d.get(key)
        if hit is not None and hit[0] == epoch:
            self._d.move_to_end(key)
            return hit[1]
        val = build()
        self.fills += 1
        self._d[key] = (epoch, val)
        self._d.move_to_end(key)
        while len(self._d) > self.capacity:
            self._d.popitem(last=False)
        return val

    def drop(self, key):
        self._d.pop(key, None)

    def __contains__(self, key):
        return key in self._d

    def __len__(self):
        return len(self._d)


@dataclass
class MutableIVF:
    """Mutable padded-partition SOAR index over frozen VQ/PQ codebooks."""
    centroids: np.ndarray               # (c, d) f32, FROZEN
    pq: Optional[PQCodebook]            # FROZEN (None → no PQ stage)
    spill_mode: str
    lam: float
    n_spills: int                       # spills per point (0 for "none")
    part_ids: np.ndarray                # (c, cap) int32; -1 = empty/tombstone
    part_codes: Optional[np.ndarray]    # (c, cap, m) uint8
    sizes: np.ndarray                   # (c,) int32 slots in use (incl. dead)
    rerank: np.ndarray                  # (cap_n, d) f32 by point id
    assignments: np.ndarray             # (cap_n, a) int32; -1 rows dead/unused
    alive: np.ndarray                   # (cap_n,) bool
    n_total: int                        # high-water point id (append-only)
    n_dead_slots: int = 0
    n_soft_deleted: int = 0             # alive=False but slots NOT blanked
    compact_threshold: float = 0.25
    # probe-stage Router (core/router.py), FROZEN like the codebooks:
    # online `add` routes through the build-time tables untouched; snapshots
    # serve a derived view with emptied partitions pruned (_serving_router)
    router: Optional[object] = None
    _packed: Optional[PackedIVF] = field(default=None, repr=False)
    _packed_pair: Optional[bool] = field(default=None, repr=False)
    _csr: Optional[IVFIndex] = field(default=None, repr=False)
    # delta-pack state: partitions / appended-id range touched since the
    # cached _packed was last synced; None marks "needs full repack"
    _dirty_parts: Optional[np.ndarray] = field(default=None, repr=False)
    _dirty_ids: int = field(default=0, repr=False)      # rerank rows synced
    # standing-filter cache: device uint8 alive bitmap, keyed by an epoch
    # bumped whenever `alive` mutates (add/remove) — a capacity-1 EpochLRU
    # (the front-end's per-tenant bank is the capacity-N generalization)
    _alive_epoch: int = field(default=0, repr=False)
    _filter_cache: EpochLRU = field(default_factory=EpochLRU, repr=False)
    # serving-router cache, keyed by the live-partition mask (see
    # _serving_router)
    _router_dev: Optional[object] = field(default=None, repr=False)
    _router_key: Optional[bytes] = field(default=None, repr=False)
    # durability (DESIGN.md §3.11): sequence number of the last mutation
    # covered by this state — a snapshot stores it, and WAL replay skips
    # records at or below it. _wal, when attached, gets one CRC-framed
    # record per mutation BEFORE the mutation applies (write-ahead).
    wal_seq: int = 0
    _wal: Optional[object] = field(default=None, repr=False)
    _replaying: bool = field(default=False, repr=False)

    # ------------------------------------------------------------ builders
    @classmethod
    def from_index(cls, idx: IVFIndex, compact_threshold: float = 0.25,
                   capacity_slack: float = 1.25) -> "MutableIVF":
        """Wrap a built IVFIndex (any builder) into the mutable layout."""
        c = idx.n_partitions
        sizes = idx.partition_sizes().astype(np.int32)
        cap = max(8, int(np.ceil(sizes.max() * capacity_slack))
                  if sizes.size else 8)
        part_ids = np.full((c, cap), -1, np.int32)
        m = idx.codes.shape[1] if idx.codes is not None else 0
        part_codes = np.zeros((c, cap, m), np.uint8) if m else None
        part = np.repeat(np.arange(c), sizes)
        pos = (np.arange(idx.n_assignments)
               - np.repeat(idx.starts[:-1], sizes)).astype(np.int64)
        part_ids[part, pos] = idx.point_ids
        if m:
            part_codes[part, pos] = idx.codes
        data = idx.rerank_f32
        if data is None:
            from repro.quant.int8 import int8_dequantize
            data = np.asarray(int8_dequantize(idx.rerank_int8))
        a = idx.assignments.shape[1]
        _, n_spills = spill_plan(idx.spill_mode, idx.lam, a - 1)
        return cls(
            centroids=np.asarray(idx.centroids, np.float32), pq=idx.pq,
            spill_mode=idx.spill_mode, lam=idx.lam, n_spills=n_spills,
            part_ids=part_ids, part_codes=part_codes, sizes=sizes,
            rerank=np.ascontiguousarray(data, dtype=np.float32),
            assignments=np.asarray(idx.assignments, np.int32).copy(),
            alive=np.ones(idx.n_points, bool), n_total=idx.n_points,
            compact_threshold=compact_threshold, router=idx.router)

    @classmethod
    def build(cls, key, X, n_partitions: int, **kw) -> "MutableIVF":
        """Sharded build (core/build.py) → mutable wrap."""
        compact_threshold = kw.pop("compact_threshold", 0.25)
        idx = build_ivf_sharded(key, X, n_partitions, **kw)
        return cls.from_index(idx, compact_threshold=compact_threshold)

    # ------------------------------------------------------------ accessors
    @property
    def n_alive(self) -> int:
        return int(self.alive[:self.n_total].sum())

    @property
    def n_slots(self) -> int:
        return int(self.sizes.sum())

    @property
    def dead_fraction(self) -> float:
        s = self.n_slots
        return self.n_dead_slots / s if s else 0.0

    def _invalidate(self):
        """Full snapshot invalidation (capacity growth / compaction)."""
        self._packed = None
        self._csr = None
        self._dirty_parts = None

    def invalidate_snapshots(self):
        """Public full invalidation: the next `pack()`/`to_ivf_index()`
        rebuilds from scratch instead of delta-updating. Exists for
        benchmarking the delta path against a forced full re-pack and for
        callers that externally mutate the numpy mirrors."""
        self._invalidate()

    def _mark_dirty(self, parts: np.ndarray):
        """Record a local mutation: only `parts` rows changed. The CSR
        snapshot is rebuilt wholesale (numpy engine), the packed snapshot
        delta-updates those rows on the next pack()."""
        self._csr = None
        if self._packed is None or self._dirty_parts is None:
            self._packed = None
            self._dirty_parts = None
            return
        self._dirty_parts[parts] = True

    # ---------------------------------------------------------- durability
    def attach_wal(self, wal, replay: bool = True) -> int:
        """Attach a MutationWAL (ckpt/wal.py): every subsequent mutation
        appends one record before applying. With `replay` (default), any
        committed records in the log with seq > this state's `wal_seq`
        are applied first — the open-after-crash path (snapshot + WAL →
        the exact live state). Returns how many records were replayed."""
        import os

        from repro.ckpt.wal import read_records
        n = 0
        if replay and os.path.exists(wal.path):
            for seq, rtype, meta, arrays in read_records(wal.path):
                if self.replay_record(seq, rtype, meta, arrays):
                    n += 1
        self._wal = wal
        return n

    def replay_record(self, seq: int, rtype: int, meta: dict,
                      arrays: dict) -> bool:
        """Apply one WAL record if it postdates this state (seq >
        wal_seq). Mutations replay through the SAME code paths that
        logged them — determinism of those paths (frozen-codebook fused
        assignment, stable sorts) is what makes recovery bitwise."""
        from repro.ckpt.index_store import CorruptSnapshotError
        if seq <= self.wal_seq:
            return False               # already folded into the snapshot
        self._replaying = True
        try:
            if rtype == REC_ADD:
                self.add(arrays["x"])
            elif rtype == REC_REMOVE:
                self.remove(arrays["ids"], hard=bool(meta["hard"]))
            elif rtype == REC_HARDEN:
                self.harden_soft_deletes()
            elif rtype == REC_COMPACT:
                self._compact_impl()
            else:
                raise CorruptSnapshotError(
                    f"unknown WAL record type {rtype} (seq {seq})")
        finally:
            self._replaying = False
        self.wal_seq = seq
        return True

    def _log(self, rtype: int, meta: Optional[dict] = None,
             arrays: Optional[dict] = None):
        """Write-ahead: append the record (durably, per the WAL's fsync
        policy) BEFORE the mutation applies. A crash after the append
        recovers to the post-mutation state via replay; a crash during it
        leaves a torn record that recovery drops — either way a committed
        state, never a hybrid."""
        if self._wal is not None and not self._replaying:
            self.wal_seq = self._wal.append(rtype, meta, arrays)

    # ------------------------------------------------------------ mutation
    def add(self, X_new) -> np.ndarray:
        """Insert a batch of vectors; returns their (stable) point ids.

        Assignments are computed incrementally against the frozen codebook
        via the fused batched path; PQ codes encode the residual w.r.t.
        each assignment's centroid, exactly as at build time.
        """
        X_new = np.atleast_2d(np.asarray(X_new, np.float32))
        b = X_new.shape[0]
        if b == 0:
            return np.empty((0,), np.int32)
        self._log(REC_ADD, arrays={"x": X_new})
        eff_lam, eff_spills = spill_plan(self.spill_mode, self.lam,
                                         self.n_spills)
        # right-size the streamed tile: a 64-row online insert must not pay
        # for an 8192-row padded tile (compile cache is per chunk size, and
        # online batch sizes are few and repeated)
        chunk = min(8192, max(256, 1 << (b - 1).bit_length()))
        A = np.asarray(assign_fused(jnp.asarray(X_new),
                                    jnp.asarray(self.centroids),
                                    lam=eff_lam, n_spills=eff_spills,
                                    chunk=chunk))
        a = A.shape[1]
        ids = np.arange(self.n_total, self.n_total + b, dtype=np.int32)
        cap_parts0 = self.part_ids.shape[1]
        cap_rerank0 = self.rerank.shape[0]

        # per-point state (geometric growth keeps appends amortized O(b))
        need = self.n_total + b
        self.rerank = _grow_rows(self.rerank, need, 0.0)
        self.assignments = _grow_rows(self.assignments, need, -1)
        self.alive = _grow_rows(self.alive, need, False)
        self.rerank[self.n_total:need] = X_new
        self.assignments[self.n_total:need] = A
        self.alive[self.n_total:need] = True
        self._alive_epoch += 1

        # partition inserts: group the (b·a) flat entries by partition and
        # append each group at its partition's current fill offset
        # (same O(N) stable counting sort as the CSR builder)
        from repro.core.ivf import _stable_counting_sort
        flat_part = A.reshape(-1)
        flat_pid = np.repeat(ids, a)
        order = _stable_counting_sort(flat_part, self.centroids.shape[0])
        sp = flat_part[order]
        counts = np.bincount(sp, minlength=self.centroids.shape[0])
        new_sizes = self.sizes + counts.astype(np.int32)
        cap = self.part_ids.shape[1]
        if new_sizes.max() > cap:
            new_cap = max(int(new_sizes.max()), 2 * cap)
            grown = np.full((self.part_ids.shape[0], new_cap), -1, np.int32)
            grown[:, :cap] = self.part_ids
            self.part_ids = grown
            if self.part_codes is not None:
                m = self.part_codes.shape[2]
                gc = np.zeros((self.part_codes.shape[0], new_cap, m),
                              np.uint8)
                gc[:, :cap] = self.part_codes
                self.part_codes = gc
        rank = np.arange(sp.shape[0]) - np.searchsorted(sp, sp)
        pos = self.sizes[sp] + rank
        self.part_ids[sp, pos] = flat_pid[order]
        if self.pq is not None and self.part_codes is not None:
            res = np.repeat(X_new, a, axis=0) - self.centroids[flat_part]
            ec = min(16384, max(256, 1 << (res.shape[0] - 1).bit_length()))
            codes = np.asarray(pq_encode(self.pq, jnp.asarray(res),
                                         chunk=ec))
            self.part_codes[sp, pos] = codes[order]
        self.sizes = new_sizes
        self.n_total = need
        if (self.part_ids.shape[1] != cap_parts0
                or self.rerank.shape[0] != cap_rerank0):
            self._invalidate()       # capacity grew → cached shapes stale
        else:
            self._mark_dirty(np.unique(sp))
        return ids

    def remove(self, ids: Sequence[int], hard: bool = True) -> int:
        """Tombstone a batch of point ids; returns how many were removed.

        hard=True (default): slots blank to -1 (the search pipelines'
        existing padding sentinel) — no data moves. Compaction runs
        automatically once the dead-slot fraction crosses
        `compact_threshold`.

        hard=False: the point is only marked dead in the `alive` bitmap —
        nothing else is touched, NO snapshot invalidation, no device
        traffic. Soft tombstones are served through the standing filter
        bitmap (`filter_bitmap()`; DESIGN.md §3.9 unifies them with user
        subset filters), which every filter-aware search path ANDs in.
        They are hardened lazily: any later hard `remove`/`compact` leaves
        them in place, and `harden_soft_deletes()` converts them in one
        batch when their slot waste starts to matter.
        """
        ids = np.unique(np.asarray(ids, np.int64))
        ids = ids[(ids >= 0) & (ids < self.n_total)]
        ids = ids[self.alive[ids]]
        if ids.size == 0:
            return 0
        self._log(REC_REMOVE, {"hard": bool(hard)}, {"ids": ids})
        self._alive_epoch += 1
        if not hard:
            self.alive[ids] = False
            self.n_soft_deleted += int(ids.size)
            return int(ids.size)
        self.alive[ids] = False
        self._blank_slots(ids)
        return int(ids.size)

    def _blank_slots(self, ids: np.ndarray):
        """Hard-tombstone bookkeeping shared by remove(hard=True) and
        harden_soft_deletes: blank the ids' partition slots to -1, retire
        their assignment rows, mark dirty, maybe compact."""
        rows = np.unique(self.assignments[ids].reshape(-1))
        rows = rows[rows >= 0]
        sub = self.part_ids[rows]
        dead = np.isin(sub, ids)
        self.part_ids[rows] = np.where(dead, -1, sub)
        self.n_dead_slots += int(dead.sum())
        self.assignments[ids] = -1
        self._mark_dirty(rows)
        if self.dead_fraction > self.compact_threshold:
            # implied by the remove/harden record already logged — logging
            # it again would double-compact on replay
            self._compact_impl()

    def compact(self):
        """Shift live slots left within each partition, dropping tombstones.

        One vectorized stable argsort per row; slot order (hence search
        tie-breaking) of survivors is preserved. Point ids do not change.
        """
        self._log(REC_COMPACT)
        self._compact_impl()

    def _compact_impl(self):
        hole = self.part_ids < 0
        order = np.argsort(hole, axis=1, kind="stable")   # live slots first
        self.part_ids = np.take_along_axis(self.part_ids, order, axis=1)
        if self.part_codes is not None:
            self.part_codes = np.take_along_axis(
                self.part_codes, order[:, :, None], axis=1)
        self.sizes = (self.part_ids >= 0).sum(axis=1).astype(np.int32)
        self.n_dead_slots = 0
        self._invalidate()

    def harden_soft_deletes(self) -> int:
        """Convert soft tombstones (alive=False, slots intact) into hard
        ones (slots blanked to -1) in one batch — reclaims their probed-
        window slots once filter masking alone wastes too many. Returns
        how many were hardened; may trigger compaction."""
        self._log(REC_HARDEN)
        dead = np.flatnonzero(~self.alive[:self.n_total]
                              & (self.assignments[:self.n_total, 0] >= 0))
        self.n_soft_deleted = 0
        if dead.size == 0:
            return 0
        self._blank_slots(dead)
        return int(dead.size)

    # ------------------------------------------------------------ filtering
    @property
    def standing_filter_thin(self) -> bool:
        """True when the standing soft-tombstone filter is selective enough
        (majority of ids dead) that probe escalation can plausibly help;
        serving paths skip the fixed second escalation pass otherwise."""
        return 2 * self.n_soft_deleted > self.n_total

    def serving_filter(self, mask: Optional[np.ndarray] = None,
                       ids: Optional[Sequence[int]] = None,
                       escalate: bool = True):
        """(device filter | None, escalate) plan for the jit serving
        paths — the single source of truth for the standing-vs-user rule,
        routed through by AnnEngine.search and KNNMemory.retrieve:

        - no user subset → the CACHED standing bitmap (only if soft
          tombstones exist), with escalation additionally gated on
          `standing_filter_thin` (a fat tombstone filter can never trigger
          escalation usefully, so don't pay its fixed second probe pass);
        - user subset → a freshly composed + uploaded `filter_bitmap`,
          escalation left to the caller's choice."""
        if mask is None and ids is None:
            if not self.n_soft_deleted:
                return None, escalate
            return (self.standing_filter(),
                    escalate and self.standing_filter_thin)
        return jnp.asarray(self.filter_bitmap(mask=mask, ids=ids)), escalate

    def standing_filter(self) -> jax.Array:
        """Cached DEVICE uint8 alive bitmap at capacity width — the
        no-user-subset standing filter (soft tombstones). Rebuilt and
        re-uploaded only when `alive` has mutated since the last call
        (EpochLRU keyed on the alive epoch + capacity width), so
        steady-state serving with a standing filter pays zero per-search
        host work or transfer."""
        return self._filter_cache.get(
            None, (self._alive_epoch, self.alive.shape[0]),
            lambda: jnp.asarray(self.alive.astype(np.uint8)))

    def filter_bitmap(self, mask: Optional[np.ndarray] = None,
                      ids: Optional[Sequence[int]] = None) -> np.ndarray:
        """Standing serving filter (DESIGN.md §3.9): the alive bitmap —
        which already carries every soft tombstone — AND'd with an optional
        user subset given as a bitmap over point ids and/or an explicit id
        allowlist. Returned as uint8 at the rerank CAPACITY width, so the
        jit engines' per-window filter gather keeps a mutation-stable shape
        (no recompiles as n_total drifts); capacity rows beyond n_total are
        0 and unreachable anyway."""
        out = self.alive.astype(np.uint8).copy()
        if ids is not None:
            sel = np.zeros_like(out)
            ii = np.asarray(ids, np.int64).ravel()
            ii = ii[(ii >= 0) & (ii < out.shape[0])]
            sel[ii] = 1
            out &= sel
        if mask is not None:
            m = np.zeros(out.shape[0], np.uint8)
            mm = np.asarray(mask).astype(bool).ravel()[:out.shape[0]]
            m[:mm.shape[0]] = mm
            out &= m
        return out

    # ------------------------------------------------------------ snapshots
    def _serving_router(self):
        """Router view served by snapshots — the frozen-router analogue of
        the frozen codebook: the build-time tables never retrain, but a
        TreeRouter is REFRESHED against the current live-partition mask
        (children of partitions whose every slot is tombstoned prune to
        -1, so probe slots are not wasted reaching empty partitions after
        heavy deletion/compaction churn). Cached by the mask, so steady-
        state packs pay a c-bit compare, and an `add` that repopulates an
        emptied partition un-prunes it on the next snapshot."""
        if self.router is None:
            return None
        live = (self.part_ids >= 0).any(axis=1)
        key = live.tobytes()
        if self._router_dev is None or self._router_key != key:
            rt = self.router
            if hasattr(rt, "pruned"):
                rt = rt.pruned(live)
            self._router_dev = rt.device()
            self._router_key = key
        return self._router_dev

    def _apply_pack_delta(self, p: PackedIVF) -> PackedIVF:
        """Scatter only the dirty partition rows / appended rerank rows
        into the cached device snapshot.

        What this saves vs a full re-pack: the host-side O(index) re-pack
        work (paired-code recompute, live-size scan) and the host→device
        upload of every array — only the touched rows cross the host
        boundary. The eager `.at[].set` updates still COPY the device
        buffers (device-side memcpy is O(index) bytes; true O(touched)
        would need buffer donation), but device memcpy is far cheaper
        than the host path: ~1.8x per add+pack+search step at n=100k.
        At toy scale the fixed dispatch overhead dominates and a full
        re-pack wins — see the bench's smoke cadence rows."""
        dirty = np.flatnonzero(self._dirty_parts)
        part_ids, part_codes = p.part_ids, p.part_codes
        part_codes2, sizes = p.part_codes2, p.sizes
        if dirty.size:
            di = jnp.asarray(dirty)
            rows = self.part_ids[dirty]
            part_ids = part_ids.at[di].set(jnp.asarray(rows))
            sizes = sizes.at[di].set(
                jnp.asarray((rows >= 0).sum(axis=1).astype(np.int32)))
            if part_codes is not None:
                crows = self.part_codes[dirty]
                part_codes = part_codes.at[di].set(
                    jnp.asarray(code_table(crows)))
                if part_codes2 is not None:
                    part_codes2 = part_codes2.at[di].set(
                        jnp.asarray(_paired_codes(crows)))
        rerank = p.rerank
        if self._dirty_ids < self.n_total:
            rerank = jax.lax.dynamic_update_slice_in_dim(
                rerank, *self._table_rows(self._dirty_ids, self.n_total), 0)
        self._dirty_parts[:] = False
        self._dirty_ids = self.n_total
        return PackedIVF(p.centroids, part_ids, part_codes, part_codes2,
                         sizes, self.pq, rerank, self._serving_router())

    def _table_rows(self, lo: int, hi: int):
        """(table rows holding vectors [lo, hi), index of the first) —
        the delta pack's upload into the lane-row table (PackedIVF).
        Vector lo may start mid-row: the row is rewritten whole, with the
        values of the vectors before lo that it already holds."""
        d = self.rerank.shape[1]
        r0, r1 = lo * d // LANES, -(-hi * d // LANES)
        flat = self.rerank.reshape(-1)[r0 * LANES:r1 * LANES]
        flat = np.pad(flat, (0, (r1 - r0) * LANES - flat.shape[0]))
        return jnp.asarray(flat.reshape(-1, LANES)), r0

    def pack(self, pair_codes: Optional[bool] = None) -> PackedIVF:
        """Padded snapshot for the candidate-local jit pipeline (cached;
        the pair_codes choice is part of the cache identity).

        The snapshot is built at the CAPACITY width of the padded
        partition arrays (not the tight pmax): shapes then stay stable
        across mutations, so (1) the serving jit pipeline never recompiles
        mid-stream and (2) subsequent pack() calls after add/remove only
        scatter the touched rows (delta pack) instead of re-packing and
        re-uploading the whole index. Extra padded slots carry the -1
        sentinel the search window already masks — results are identical
        to a tight pack."""
        if pair_codes is None:
            pair_codes = jax.default_backend() != "tpu"
        if (self._packed is not None and self._packed_pair == pair_codes
                and self._dirty_parts is not None):
            if self._dirty_parts.any() or self._dirty_ids < self.n_total:
                self._packed = self._apply_pack_delta(self._packed)
            return self._packed
        ids = self.part_ids
        codes = self.part_codes
        live_sizes = (ids >= 0).sum(axis=1).astype(np.int32)
        self._packed = PackedIVF(
            jnp.asarray(self.centroids), jnp.asarray(ids),
            jnp.asarray(code_table(codes)) if codes is not None else None,
            (jnp.asarray(_paired_codes(codes))
             if codes is not None and pair_codes else None),
            jnp.asarray(live_sizes), self.pq,
            jnp.asarray(rerank_table(self.rerank)), self._serving_router())
        self._packed_pair = pair_codes
        self._dirty_parts = np.zeros(ids.shape[0], bool)
        self._dirty_ids = self.n_total
        return self._packed

    def to_ivf_index(self) -> IVFIndex:
        """CSR snapshot of the live assignments (numpy engine; cached).

        Point ids keep their stable values; dead rerank rows remain in the
        array (they are never referenced by any partition slot).
        """
        if self._csr is not None:
            return self._csr
        c, cap = self.part_ids.shape
        mask = self.part_ids >= 0
        counts = mask.sum(axis=1)
        starts = np.zeros(c + 1, np.int64)
        np.cumsum(counts, out=starts[1:])
        point_ids = self.part_ids[mask].astype(np.int32)
        codes = self.part_codes[mask] if self.part_codes is not None else None
        self._csr = IVFIndex(
            centroids=self.centroids, starts=starts, point_ids=point_ids,
            codes=codes, pq=self.pq, rerank_int8=None,
            rerank_f32=self.rerank[:self.n_total],
            assignments=self.assignments[:self.n_total],
            n_points=self.n_total, spill_mode=self.spill_mode, lam=self.lam,
            router=self._serving_router())
        return self._csr

    def rebuild_reference(self, key=None) -> IVFIndex:
        """From-scratch build of the CURRENT live state against the same
        frozen codebook/PQ (the mutation-equivalence comparator)."""
        live = np.flatnonzero(self.alive[:self.n_total])
        return build_ivf_sharded(
            key, self.rerank[live], self.centroids.shape[0],
            spill_mode=self.spill_mode, lam=self.lam,
            n_spills=max(self.n_spills, 1), codebook=self.centroids,
            pq=self.pq, router=self.router)
