"""Online ANN serving over a mutable SOAR index: `AnnEngine`
add/remove/search against a live index (DESIGN.md §3.7), and the search
plan that both its own launch and the front-end's replica fan-out run
(§3.12).
"""
from __future__ import annotations

import os
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import faults
from repro.serve.api import (DEFAULT_BQ, DEFAULT_RERANK_BUDGET,
                             DEFAULT_TOP_T, SearchParams, SearchResult,
                             _positive_int, validate_queries)


class SearchPlan(NamedTuple):
    """What one search launch runs (AnnEngine.plan): the packed index,
    the padded queries, the filter, and the static arguments every launch
    of the search pass takes (`static`: top_t, final_k, rerank_budget,
    multiplicity, escalate)."""
    packed: object              # the index's PackedIVF snapshot
    Q: np.ndarray               # (padded rows, d) float32
    nq: int                     # rows before padding
    bq: int                     # the compile-cache bucket (pad_queries)
    filter: Optional[jax.Array]
    static: dict

    @property
    def escalated(self) -> bool:
        return bool(self.static["escalate"] and self.filter is not None)


class PendingSearch:
    """A search launched on the device: the program is queued, and its
    padded ids and scores are already being copied back
    (`copy_to_host_async`). `wait()` blocks until the device has computed
    them and fetches nothing. The first read of `ids` or `scores` collects
    both, in one `jax.device_get` under the `span` host span, and slices
    off the padding; a caller may replace them, as on a SearchResult.
    `result()` is the SearchResult. The serve point named like the span
    (`engine:download`, `replica:gather`) fires at collection, where an
    error of the device surfaces (DESIGN.md §3.12–3.13)."""

    def __init__(self, arrays, nq: int, *, t0: Optional[float] = None,
                 span: str = "engine.download", **meta):
        self.arrays = arrays            # (ids, scores), padded
        self.nq, self.t0, self.span, self.meta = nq, t0, span, meta
        self._host = None
        for a in arrays:
            if isinstance(a, jax.Array):
                a.copy_to_host_async()

    def wait(self) -> None:
        jax.block_until_ready(self.arrays)

    def _collect(self) -> list:
        if self._host is None:
            with TraceAnnotation(self.span):
                faults.serve_point(self.span.replace(".", ":"))
                ids, scores = jax.device_get(self.arrays)
            self._host = [ids[:self.nq], scores[:self.nq]]
        return self._host

    @property
    def ids(self) -> np.ndarray:
        return self._collect()[0]

    @ids.setter
    def ids(self, v) -> None:
        self._collect()[0] = v

    @property
    def scores(self) -> np.ndarray:
        return self._collect()[1]

    @scores.setter
    def scores(self, v) -> None:
        self._collect()[1] = v

    def result(self) -> SearchResult:
        ids, scores = self._collect()
        us = 0.0 if self.t0 is None else (time.perf_counter() - self.t0) * 1e6
        return SearchResult(ids, scores, engine_us=us, **self.meta)


class AnnEngine:
    """Online ANN serving engine over a mutable SOAR index.

    Wraps core/mutable.MutableIVF with the candidate-local jit search
    pipeline (DESIGN.md §3.6): `search` serves from the index's cached
    packed snapshot, which mutation (`add`/`remove`) invalidates — the
    snapshot cost is amortized across the mutation batch, and the
    tombstone/compaction policy (§3.7) bounds how stale the padded layout
    can get. Point ids returned by `add` are stable handles for `remove`
    and for joining search results back to caller-side payloads.
    """

    def __init__(self, index, *, top_t: int = DEFAULT_TOP_T,
                 rerank_budget: int = DEFAULT_RERANK_BUDGET,
                 bq: int = DEFAULT_BQ):
        self.index = index
        self.top_t = _positive_int("top_t", top_t)
        self.rerank_budget = _positive_int("rerank_budget", rerank_budget)
        self.bq = _positive_int("bq", bq)

    @classmethod
    def build(cls, key, X, n_partitions: int, *, spill_mode: str = "soar",
              lam: float = 1.0, pq_subspaces: int = 0,
              top_t: int = DEFAULT_TOP_T,
              rerank_budget: int = DEFAULT_RERANK_BUDGET,
              bq: int = DEFAULT_BQ, router=None,
              router_kw=None, **build_kw):
        """Sharded build (core/build.py) → serving engine.

        router: probe-stage router spec plumbed to the build ("tree"
        trains a TreeRouter over the centroids and every search then
        probes through it; None keeps the flat probe — DESIGN.md §3.10).
        """
        from repro.core.mutable import MutableIVF
        idx = MutableIVF.build(key, X, n_partitions, spill_mode=spill_mode,
                               lam=lam, pq_subspaces=pq_subspaces,
                               router=router, router_kw=router_kw, **build_kw)
        return cls(idx, top_t=top_t, rerank_budget=rerank_budget, bq=bq)

    @property
    def n_alive(self) -> int:
        return self.index.n_alive

    def add(self, X) -> np.ndarray:
        faults.serve_point("engine:add")
        return self.index.add(X)

    def remove(self, ids, hard: bool = True) -> int:
        """Delete points. hard=False leaves slots in place and serves the
        tombstones through the standing filter bitmap (zero data movement,
        no snapshot invalidation) — see MutableIVF.remove."""
        faults.serve_point("engine:remove")
        return self.index.remove(ids, hard=hard)

    def search(self, Q, k: int = 10, top_t: Optional[int] = None,
               filter_ids=None, filter_mask=None, escalate: bool = True,
               sanitize: bool = False):
        """(nq, d) queries → (ids (nq, k) int32, scores (nq, k)).

        Thin shim over the unified request API (DESIGN.md §3.12): builds
        a SearchParams and routes through `search_request` — results are
        bitwise identical to constructing the params directly (pinned by
        tests/test_serve_api.py). See SearchParams for the full contract;
        the engine remains the hardened serving edge (dtype/finiteness
        validation, explicit top_t=0 raises, nq=0 returns empties).

        filter_ids / filter_mask restrict the search to a subset of live
        points; both compose with the index's standing soft-tombstone
        filter. The filtered path runs the selectivity-escalating jit
        pipeline (§3.9) — pass escalate=False when the filter is known to
        be fat. Unfiltered serving with no soft tombstones stays on the
        exact PR 4 trace.
        """
        r = self.search_request(Q, SearchParams(
            k=k, top_t=top_t, filter_ids=filter_ids,
            filter_mask=filter_mask, escalate=escalate, sanitize=sanitize))
        return r.ids, r.scores

    def search_request(self, Q, params: Optional[SearchParams] = None, *,
                       _filter_dev=None, wait: bool = True):
        """Structured serving entry point: (nq, d) queries + SearchParams
        → SearchResult (DESIGN.md §3.12).

        Validation (query hygiene + k/top_t/rerank_budget bounds) runs
        through `SearchParams.validate()` — the single hardened path
        shared with KNNMemory. `_filter_dev` is the front-end's seam: a
        pre-composed DEVICE filter bitmap (tenant ∧ alive, cached by the
        TenantFilterBank) that skips the per-call host composition and
        upload `serving_filter` would pay for a user subset.

        `wait=False` returns as soon as the search program is launched,
        with a PendingSearch whose `result()` collects it: the front-end
        launches the next batch before it collects this one.

        The call runs under four host spans, `engine.prepare` (validation,
        filter, pack, padding), `engine.upload`, `engine.launch` (the call
        and the start of the copy back) and `engine.download` (at
        collection), which a profiler trace records on the device trace's
        clock (DESIGN.md §3.15).
        """
        from repro.core.search import search_jit_batched
        with TraceAnnotation("engine.prepare"):
            p = (params or SearchParams()).validate(
                default_top_t=self.top_t, default_rerank=self.rerank_budget)
            Q = validate_queries(Q, self.index.centroids.shape[1],
                                 sanitize=p.sanitize)
            meta = dict(epoch=getattr(self.index, "_alive_epoch", -1),
                        tenant=p.tenant, deadline_ms=p.deadline_ms)
            if Q.shape[0] == 0:
                empty = PendingSearch((np.empty((0, p.k), np.int32),
                                       np.empty((0, p.k), np.float32)),
                                      0, **meta)
                return empty.result() if wait else empty
            faults.serve_point("engine:search")
            t0 = time.perf_counter()
            plan = self.plan(Q, p, _filter_dev)
        with TraceAnnotation("engine.upload"):
            Qd = jnp.asarray(plan.Q)
        with TraceAnnotation("engine.launch"):
            pending = PendingSearch(
                search_jit_batched(plan.packed, Qd, bq=plan.bq,
                                   filter=plan.filter, **plan.static),
                plan.nq, t0=t0, batch_size=plan.nq,
                escalated=plan.escalated, **meta)
        return pending.result() if wait else pending

    def plan(self, Q: np.ndarray, p: SearchParams, filt_dev=None,
             rows: int = 1) -> SearchPlan:
        """The search plan for validated queries `Q` and resolved params
        `p` — the one source of what a launch runs, for this engine's own
        launch and the front-end's replica fan-out alike (DESIGN.md
        §3.12): the filter and escalation (`filt_dev`, a device bitmap
        such as a tenant's, else the index's `serving_filter`), the probe
        width clamped to the partitions, the rerank floor k, the dedup
        multiplicity of the index's spills, and the queries padded to a
        compile-cache bucket and to a multiple of `rows`. It validates
        nothing and opens no span."""
        from repro.core.router import clamp_top_t
        from repro.core.search import pad_queries
        if filt_dev is not None:
            filt, escalate = filt_dev, p.escalate
        else:
            filt, escalate = self.index.serving_filter(
                mask=p.filter_mask, ids=p.filter_ids, escalate=p.escalate)
        packed = self.index.pack()
        Qp, nq, bq = pad_queries(Q, self.bq, multiple=rows)
        static = dict(
            top_t=clamp_top_t(p.top_t, self.index.centroids.shape[0]),
            final_k=p.k, rerank_budget=max(p.rerank_budget, p.k),
            multiplicity=1 + max(self.index.n_spills, 1),
            escalate=bool(escalate))
        return SearchPlan(packed, Qp, nq, bq, filt, static)

    # ---------------------------------------------------------- durability
    def save(self, path: str, *, extra: Optional[dict] = None,
             extra_arrays: Optional[dict] = None):
        """Atomic, versioned snapshot of the full serving state — index
        (codebooks, router, partitions, tombstones, wal_seq) + engine
        config — under `path` (DESIGN.md §3.11). If a WAL is attached,
        the log is rotated afterwards: every record is covered by the
        snapshot's wal_seq, and sequence numbers continue monotonically,
        so a crash between snapshot commit and rotation is benign.

        `extra` (JSON-able) and `extra_arrays` (name → ndarray) ride the
        snapshot for layers above the engine — the serving front-end
        stores its batching config and per-tenant filter bitmaps here
        (§3.12) so a reopened index serves the same tenants."""
        from repro.ckpt.index_store import save_snapshot
        os.makedirs(path, exist_ok=True)
        meta = {"engine": {"top_t": self.top_t,
                           "rerank_budget": self.rerank_budget,
                           "bq": self.bq}}
        meta.update(extra or {})
        save_snapshot(os.path.join(path, "index"), self.index,
                      extra=meta, extra_arrays=extra_arrays)
        wal = getattr(self.index, "_wal", None)
        if wal is not None:
            wal.rotate(self.index.wal_seq)

    @classmethod
    def open(cls, path: str, *, wal: bool = False, fsync: str = "always"):
        """Reopen a saved engine: load the latest valid snapshot (the
        atomic-swap `.old` fallback included) and replay any committed
        WAL records past its wal_seq — recovery lands bitwise on the last
        committed state, never a torn hybrid. `wal=True` (or a log
        already on disk) leaves a WAL attached so every subsequent
        mutation is logged transparently; `fsync` is its durability
        policy ("always" | "never")."""
        from repro.ckpt.index_store import load_snapshot
        from repro.ckpt.wal import MutationWAL
        idx, extra = load_snapshot(os.path.join(path, "index"),
                                   expect_kind="MutableIVF")
        cfg = dict(extra.get("engine", {}))
        eng = cls(idx, top_t=int(cfg.get("top_t", DEFAULT_TOP_T)),
                  rerank_budget=int(cfg.get("rerank_budget",
                                            DEFAULT_RERANK_BUDGET)),
                  bq=int(cfg.get("bq", DEFAULT_BQ)))
        wal_path = os.path.join(path, "wal.log")
        if wal or os.path.exists(wal_path):
            idx.attach_wal(MutationWAL(wal_path, fsync=fsync,
                                       start_seq=idx.wal_seq))
        return eng
