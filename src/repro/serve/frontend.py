"""Production serving front-end (DESIGN.md §3.12): deadline-aware dynamic
batching, standing multi-tenant filters, and replica fan-out in front of
AnnEngine.

The engines (serve/engine.py, serve/knn_memory.py) are synchronous,
single-caller edges: every `search` pays its own padded jit dispatch, and
concurrent callers must serialize around the mutable index themselves. This
module adds the missing production layer:

- **ServingFrontend** — an async request loop. Callers `submit` a
  (queries, SearchParams) request and get a Future (or `await asearch`);
  a single dispatcher thread owns the engine and coalesces compatible
  pending requests into ONE padded `search_jit_batched` call. Because the
  engine pads every batch to a power-of-two bucket anyway (pad_queries),
  eight concurrent single-query callers cost ~one bucket-8 call instead of
  eight — and coalescing reuses exactly the buckets solo calls would
  compile, so it NEVER adds a compile (pinned by
  tests/test_frontend.py::test_no_recompilation).

- **Two batches in flight** — the dispatcher launches a ripe group while
  fewer than `DEPTH` (two) are in flight and collects each once a waiter
  thread has seen its results computed, so the host's preparation and
  copy back overlap the device's work on the other batch. Futures are
  still resolved on the dispatcher thread, in launch order.

- **Deadline-aware flushing** — a batch dispatches when it reaches
  `max_batch` queries OR when the oldest compatible request has spent half
  its `deadline_ms` budget waiting (clamped by `max_delay_ms`, so
  steady-state trickle traffic still coalesces without stalling a
  half-deadline on every 50 ms-budget request). `max_delay_ms=None` gives
  the pure half-deadline policy.

- **Determinism** — coalescing is result-invariant: every stage of the jit
  pipeline is query-local, so a request served inside a coalesced batch is
  BITWISE identical to the same request served solo at the same index
  epoch (pinned by tests/test_frontend.py::test_coalesced_equals_solo).
  Requests carrying an ad-hoc inline filter (raw bitmap/allowlist) have
  per-request device state and dispatch solo; requests sharing a
  registered `tenant` coalesce, since their filter is the same standing
  bitmap.

- **TenantFilterBank** — standing per-tenant subset filters. A tenant's
  id-set is registered once; at dispatch the front-end serves from an
  epoch-keyed LRU of DEVICE bitmaps (tenant ∧ alive), so per-request cost
  is a dict hit, not an O(n) host compose + upload. Mutations bump the
  index epoch and invalidate every cached bitmap at once (the
  generalization of the capacity-1 standing-filter cache inside
  MutableIVF — same EpochLRU).

- **Mutations as barriers** — `add`/`remove` enqueue through the same
  queue and dispatch only from the queue head, after every
  earlier-submitted search; no search submitted after a mutation is
  served before it. Epoch-tagged SearchResults make the ordering
  observable.

- **Replica fan-out** — with >1 device and `policy="replica"` (or
  "auto"), coalesced batches are sharded row-wise over a device mesh via
  make_replicated_search (index replicated, queries split): the
  data-parallel dual of the shard-parallel distributed layer, bitwise
  identical to local execution because replicas run the same query-local
  pipeline with no collectives. The packed index and each filter bitmap
  are placed on the replicas once per snapshot (device 0 keeps the
  engine's own buffers), and queries go straight to their replica.

Durability rides the engine snapshot: `save` stores the front-end config
and every tenant bitmap as `extra`/`extra_arrays` alongside the index, and
`open` restores a front-end serving the same tenants.

Resilience layer (ISSUE 9, DESIGN.md §3.13) — the front-end learns to say
"no" and "partially" instead of hanging:

- **Admission control / load shedding** — `max_queue` bounds the pending
  cost (queries queued; a mutation counts `mutation_cost`, default one
  full batch). At the bound, `overload="reject"` refuses the new request
  with `OverloadedError`; `overload="shed-oldest"` evicts the queued
  search with the LEAST deadline slack (the one most likely to miss
  anyway) to admit the newcomer. Mutations are never shed (their Futures
  represent writes) and never evict searches — a mutation flood hits
  admission itself, so barriers can't starve the search share of the
  queue.

- **Deadline enforcement** — a request carrying an explicit `deadline_ms`
  whose budget expires while still queued is dropped AT DISPATCH with
  `DeadlineExceededError` (carrying its `queued_us`) instead of spending
  engine time on an answer nobody is waiting for. `deadline_ms=None`
  requests are best-effort: paced by `default_deadline_ms` for batching,
  never shed.

- **Failure containment** — an engine `Exception` fails ONLY the
  offending dispatch group's Futures; the dispatcher keeps serving.
  Failures classified retryable (`serve/api.is_retryable`) get a bounded
  retry with exponential backoff (`max_retries`/`retry_backoff_ms`);
  mutations are never retried (a partially-applied add must not
  double-apply). A `BaseException` (an injected crash, interpreter
  shutdown) is fatal: the in-flight group gets the original error, every
  queued Future fails with `FrontendClosedError` (cause attached), and
  subsequent `submit` raises it — callers NEVER hang on a dead
  dispatcher. `close(drain=False)` fails pending Futures deterministically
  instead of draining.

- **Degraded replica fan-out** — replica dispatch runs behind a
  per-target circuit breaker (serve/health.py): a failed replica batch
  trips the breaker and falls back to the local single-device path (same
  data, full coverage) with `SearchResult.degraded=True`; while the
  breaker is open, traffic stays local (still flagged degraded) until the
  half-open probe heals it. The shard-parallel degraded path (partial
  top-k from healthy shards) is the `health=` argument of
  core/distributed.make_distributed_search.
"""
from __future__ import annotations

import contextlib
import os
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import faults
from repro.core.mutable import EpochLRU
from repro.serve.api import (DEFAULT_DEADLINE_MS, DeadlineExceededError,
                             FrontendClosedError, OverloadedError,
                             SearchParams, SearchResult, _positive_int,
                             is_retryable)
from repro.serve.engine import AnnEngine, PendingSearch
from repro.serve.health import HealthTracker


class UnknownTenantError(KeyError):
    """A request named a tenant never registered with the front-end."""


class TenantFilterBank:
    """Standing per-tenant filters over a mutable index (DESIGN.md §3.12).

    A tenant is a named id-subset (e.g. one customer's vectors in a shared
    index). `register` stores the subset as a host bool mask over point
    ids; `get` returns the DEVICE uint8 bitmap (tenant ∧ alive) the jit
    filter path consumes, served from an EpochLRU keyed on
    (index alive-epoch, capacity width, tenant version):

    - index mutation (add/remove) bumps `_alive_epoch` → every tenant's
      cached bitmap is stale and rebuilds on next use (tombstoned ids
      drop out of the tenant's serving set immediately);
    - `register`/`extend` bump the tenant's own version → only that
      tenant rebuilds;
    - unchanged tenants hit the cache: steady-state per-request filter
      cost is a dict lookup, zero host compose, zero upload.

    `capacity` bounds device memory: at most that many tenant bitmaps
    stay resident, LRU-evicted (an evicted tenant re-uploads on next
    use — correctness is unaffected). The underlying EpochLRU is the same
    cache class MutableIVF uses at capacity 1 for its standing
    tombstone filter.
    """

    def __init__(self, index, capacity: int = 32):
        self.index = index
        self._cache = EpochLRU(capacity=_positive_int("capacity", capacity))
        self._masks: dict = {}      # tenant -> host bool mask over ids
        self._versions: dict = {}   # tenant -> int, bumped on (re)register
        self._lock = threading.Lock()

    # ------------------------------------------------------------ registry
    def register(self, tenant: str, ids: Optional[Sequence[int]] = None,
                 mask: Optional[np.ndarray] = None) -> None:
        """(Re)define a tenant's id-set from an allowlist or a bool mask.
        Replaces any previous definition and invalidates its cached
        bitmap."""
        if (ids is None) == (mask is None):
            raise ValueError("register needs exactly one of ids= or mask=")
        if mask is not None:
            m = np.asarray(mask).astype(bool).ravel().copy()
        else:
            ii = np.asarray(ids, np.int64).ravel()
            if ii.size and ii.min() < 0:
                raise ValueError("tenant ids must be non-negative")
            m = np.zeros(int(ii.max()) + 1 if ii.size else 0, bool)
            m[ii] = True
        with self._lock:
            self._masks[tenant] = m
            self._versions[tenant] = self._versions.get(tenant, 0) + 1
            self._cache.drop(tenant)

    def extend(self, tenant: str, ids: Sequence[int]) -> None:
        """Grow a tenant's id-set (e.g. after `add` returned fresh ids for
        that tenant's vectors)."""
        ii = np.asarray(ids, np.int64).ravel()
        with self._lock:
            if tenant not in self._masks:
                raise UnknownTenantError(tenant)
            m = self._masks[tenant]
            need = int(ii.max()) + 1 if ii.size else 0
            if need > m.shape[0]:
                m = np.concatenate([m, np.zeros(need - m.shape[0], bool)])
            m[ii] = True
            self._masks[tenant] = m
            self._versions[tenant] += 1
            self._cache.drop(tenant)

    @property
    def tenants(self):
        with self._lock:
            return sorted(self._masks)

    @property
    def fills(self) -> int:
        """Device bitmap (re)builds so far — the observable for cache
        efficiency tests (steady state: one fill per tenant per index
        epoch)."""
        return self._cache.fills

    def __contains__(self, tenant) -> bool:
        with self._lock:
            return tenant in self._masks

    def __len__(self) -> int:
        with self._lock:
            return len(self._masks)

    # ------------------------------------------------------------- serving
    def get(self, tenant: str) -> jax.Array:
        """DEVICE uint8 bitmap (tenant ∧ alive) at capacity width, cached
        per (alive-epoch, capacity, tenant-version)."""
        with self._lock:
            if tenant not in self._masks:
                raise UnknownTenantError(tenant)
            idx, m = self.index, self._masks[tenant]
            epoch = (getattr(idx, "_alive_epoch", -1), idx.alive.shape[0],
                     self._versions[tenant])
            return self._cache.get(
                tenant, epoch,
                lambda: jnp.asarray(idx.filter_bitmap(mask=m)))

    # ---------------------------------------------------------- durability
    def state(self):
        """(meta, arrays) for riding an engine snapshot."""
        with self._lock:
            meta = {"tenants": sorted(self._masks)}
            arrays = {f"tenant.{t}": self._masks[t].astype(np.uint8)
                      for t in self._masks}
            return meta, arrays


@dataclass
class _Request:
    """One queued front-end operation. kind: "search" | "add" | "remove"."""
    kind: str
    future: Future
    Q: Optional[np.ndarray] = None
    params: Optional[SearchParams] = None     # validated at submit
    key: Optional[tuple] = None               # coalescing key (None = solo)
    t_admit: float = 0.0                      # perf_counter at submit
    flush_at: float = field(default=float("inf"))
    payload: Optional[tuple] = None           # mutation args
    deadline_at: Optional[float] = None       # absolute expiry (explicit
    #                                           deadline_ms only; None =
    #                                           best-effort, never shed)
    cost: int = 1                             # admission units (queries)
    retries: int = 0                          # dispatch retries so far

    @property
    def nq(self) -> int:
        return int(self.Q.shape[0]) if self.Q is not None else 0

    @property
    def slack(self) -> float:
        """Deadline slack for shed-oldest ordering (None = infinite —
        best-effort requests are shed last)."""
        return (float("inf") if self.deadline_at is None
                else self.deadline_at - time.perf_counter())


DEPTH = 2   # coalesced search groups in flight at once (DESIGN.md §3.12)


@dataclass
class _Flight:
    """A launched search group whose results are on their way back."""
    group: list
    pending: PendingSearch
    Q: np.ndarray                 # the coalesced queries and filter, kept
    filt_dev: Optional[jax.Array]  # for a replica group's local fallback
    t0: float                     # perf_counter at launch
    degraded: bool
    replica: bool                 # launched on the replicas
    ahead: bool                   # launched with another group in flight
    ready: bool = False           # computed on the device (the waiter)


class ServingFrontend:
    """Async serving loop in front of AnnEngine (DESIGN.md §3.12).

    One dispatcher thread owns the engine: searches AND mutations flow
    through its queue, so callers never take a lock around the mutable
    index. Compatible searches (same SearchParams.batch_key) coalesce
    into one padded jit call; mutations are strict barriers.

    Flush policy: a pending group dispatches when

    - its total queries reach `max_batch` (default: the engine's jit tile
      `bq` — one full tile), or
    - the oldest request in it has waited `min(max_delay_ms,
      deadline_ms / 2)` — half the request's latency budget, clamped so a
      generous deadline doesn't stall the queue (`max_delay_ms=None`
      removes the clamp → pure half-deadline policy), or
    - the front-end is closing / `flush()` was called.

    `policy` selects execution: "local" always runs the single-device
    engine path; "replica" shards each coalesced batch row-wise over all
    visible devices via make_replicated_search (index replicated — the
    query-bound regime's scaling axis); "auto" picks replica iff more
    than one device is visible. Both paths are bitwise identical per
    query, so the policy is purely a throughput decision.
    """

    def __init__(self, engine: AnnEngine, *,
                 max_batch: Optional[int] = None,
                 max_delay_ms: Optional[float] = 2.0,
                 default_deadline_ms: float = DEFAULT_DEADLINE_MS,
                 policy: str = "auto",
                 tenant_capacity: int = 32,
                 max_queue: Optional[int] = None,
                 overload: str = "reject",
                 mutation_cost: Optional[int] = None,
                 max_retries: int = 2,
                 retry_backoff_ms: float = 1.0,
                 breaker_threshold: int = 3,
                 breaker_reset_s: float = 5.0):
        if policy not in ("local", "replica", "auto"):
            raise ValueError(f"policy must be local|replica|auto, "
                             f"got {policy!r}")
        if overload not in ("reject", "shed-oldest"):
            raise ValueError(f"overload must be reject|shed-oldest, "
                             f"got {overload!r}")
        self.engine = engine
        self.max_batch = _positive_int(
            "max_batch", max_batch if max_batch is not None else engine.bq)
        if max_delay_ms is not None and not max_delay_ms > 0:
            raise ValueError("max_delay_ms must be positive or None")
        self.max_delay_ms = max_delay_ms
        self.default_deadline_ms = float(default_deadline_ms)
        self.policy = policy
        self.max_queue = (None if max_queue is None
                          else _positive_int("max_queue", max_queue))
        self.overload = overload
        self.mutation_cost = _positive_int(
            "mutation_cost",
            mutation_cost if mutation_cost is not None else self.max_batch)
        if max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        self.max_retries = int(max_retries)
        if retry_backoff_ms < 0:
            raise ValueError("retry_backoff_ms must be >= 0")
        self.retry_backoff_ms = float(retry_backoff_ms)
        self.health = HealthTracker(fail_threshold=breaker_threshold,
                                    reset_after_s=breaker_reset_s)
        self.tenants = TenantFilterBank(engine.index,
                                        capacity=tenant_capacity)
        self.stats = {"dispatches": 0, "coalesced": 0, "requests": 0,
                      "mutations": 0, "replica_dispatches": 0,
                      "replica_placements": 0, "replica_placed_bytes": 0,
                      "rejected": 0, "shed": 0, "expired": 0,
                      "retries": 0, "failures": 0, "degraded": 0,
                      "launched_ahead": 0}
        self._q: deque = deque()
        self._inflight: deque = deque()   # launched groups, oldest first
        self._busy = False              # the dispatcher holds a group
        self._launched: queue.SimpleQueue = queue.SimpleQueue()
        self._cost = 0                  # admission units currently queued
        self._fatal: Optional[BaseException] = None
        self._cond = threading.Condition()
        self._closed = False
        self._draining = False
        self._rep_cache: dict = {}      # static-shape key -> jitted replica fn
        self._mesh = None
        self._rows = None               # queries split row-wise on _mesh
        # replicated placements by source identity: the current pack, and
        # the standing tombstone filter plus every cached tenant bitmap
        self._rep_index = EpochLRU(capacity=1)
        self._rep_filters = EpochLRU(capacity=tenant_capacity + 1)
        self._thread = threading.Thread(target=self._loop,
                                        name="serve-frontend", daemon=True)
        self._waiter = threading.Thread(target=self._wait_ready,
                                        name="serve-frontend-waiter",
                                        daemon=True)
        self._thread.start()
        self._waiter.start()

    # ------------------------------------------------------------- clients
    def submit(self, Q, params: Optional[SearchParams] = None) -> Future:
        """Enqueue a search; returns a Future[SearchResult]. Validation
        (param bounds + query hygiene) runs HERE, in the caller's thread —
        a malformed request fails fast and never reaches the batcher."""
        from repro.serve.api import validate_queries
        p = (params or SearchParams()).validate(
            default_top_t=self.engine.top_t,
            default_rerank=self.engine.rerank_budget)
        Q = validate_queries(Q, self.engine.index.centroids.shape[1],
                             sanitize=p.sanitize)
        if p.tenant is not None and p.tenant not in self.tenants:
            raise UnknownTenantError(p.tenant)
        fut: Future = Future()
        now = time.perf_counter()
        deadline = (p.deadline_ms if p.deadline_ms is not None
                    else self.default_deadline_ms)
        wait_ms = deadline / 2.0
        if self.max_delay_ms is not None:
            wait_ms = min(wait_ms, self.max_delay_ms)
        req = _Request("search", fut, Q=Q, params=p, key=p.batch_key(),
                       t_admit=now, flush_at=now + wait_ms * 1e-3,
                       deadline_at=(now + p.deadline_ms * 1e-3
                                    if p.deadline_ms is not None else None),
                       cost=max(int(Q.shape[0]), 1))
        self._enqueue(req)
        return fut

    def search(self, Q, params: Optional[SearchParams] = None,
               **kw) -> SearchResult:
        """Blocking search through the front-end loop. Legacy kwargs
        (k=, top_t=, tenant=, deadline_ms=, ...) accepted as a
        SearchParams shim."""
        if kw:
            if params is not None:
                raise TypeError("pass params= or kwargs, not both")
            params = SearchParams(**kw)
        return self.submit(Q, params).result()

    async def asearch(self, Q, params: Optional[SearchParams] = None
                      ) -> SearchResult:
        """Awaitable search for asyncio servers."""
        import asyncio
        return await asyncio.wrap_future(self.submit(Q, params))

    def add(self, X, tenant: Optional[str] = None) -> np.ndarray:
        """Mutation barrier: append points through the queue (after every
        earlier search, before every later one). With `tenant`, the fresh
        ids also extend that tenant's standing filter atomically with the
        insert (no window where the points are live but unfindable by
        their tenant)."""
        fut: Future = Future()
        self._enqueue(_Request("add", fut, payload=(X, tenant),
                               t_admit=time.perf_counter(),
                               cost=self.mutation_cost))
        return fut.result()

    def remove(self, ids, hard: bool = True) -> int:
        """Mutation barrier: tombstone points through the queue."""
        fut: Future = Future()
        self._enqueue(_Request("remove", fut, payload=(ids, hard),
                               t_admit=time.perf_counter(),
                               cost=self.mutation_cost))
        return fut.result()

    def register_tenant(self, tenant: str,
                        ids: Optional[Sequence[int]] = None,
                        mask: Optional[np.ndarray] = None) -> None:
        self.tenants.register(tenant, ids=ids, mask=mask)

    def flush(self) -> None:
        """Block until every currently queued request, and every launched
        batch, has been served (pending deadline timers are overridden —
        the queue drains now)."""
        with self._cond:
            self._draining = True
            self._cond.notify_all()
            self._cond.wait_for(
                lambda: self._idle_locked() or self._closed
                or self._fatal is not None)
            self._draining = False

    def close(self, drain: bool = True) -> None:
        """Stop the dispatcher. Idempotent, and deterministic about every
        pending Future: `drain=True` (default) serves the queue first;
        `drain=False` fails queued Futures with FrontendClosedError
        immediately; batches already launched are still served. If the
        dispatcher already died, pending Futures were failed at death —
        close() just reaps the threads."""
        with self._cond:
            if not self._closed:
                if drain and self._fatal is None:
                    self._draining = True
                    self._cond.notify_all()
                    self._cond.wait_for(
                        lambda: self._idle_locked()
                        or self._fatal is not None)
                    self._draining = False
                self._fail_pending_locked(FrontendClosedError(
                    "front-end is closed (closed before dispatch)"))
                self._closed = True
                self._cond.notify_all()
        self._thread.join(timeout=10.0)
        self._waiter.join(timeout=10.0)

    def _fail_pending_locked(self, exc: BaseException) -> None:
        """Lock held: fail every queued Future with `exc` and empty the
        queue — nobody blocks on a Future the dispatcher will never
        serve."""
        for r in self._q:
            if not r.future.done():
                r.future.set_exception(exc)
        self._q.clear()
        self._cost = 0

    def _idle_locked(self) -> bool:
        """Lock held: nothing queued, in flight or in the dispatcher's
        hands."""
        return not self._q and not self._inflight and not self._busy

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # ---------------------------------------------------------- dispatcher
    def _enqueue(self, req: _Request) -> None:
        with self._cond:
            if self._closed or self._fatal is not None:
                err = FrontendClosedError("front-end is closed")
                err.__cause__ = self._fatal
                raise err
            if (self.max_queue is not None
                    and self._cost + req.cost > self.max_queue):
                self._admit_locked(req)   # sheds or raises OverloadedError
            self._q.append(req)
            self._cost += req.cost
            self._cond.notify_all()

    def _admit_locked(self, req: _Request) -> None:
        """Lock held, queue over budget: make room for `req` or refuse it.

        Mutations never shed (a write's Future is a promise) and never
        evict queued searches — an over-budget mutation is rejected under
        BOTH policies, so a mutation flood backpressures its producer
        instead of starving the search share of the queue. Under
        "shed-oldest", queued searches are evicted least-deadline-slack
        first (the requests most likely to miss anyway); best-effort
        requests (no explicit deadline → infinite slack) go last."""
        if self.overload == "reject" or req.kind != "search":
            self.stats["rejected"] += 1
            raise OverloadedError(
                f"queue full ({self._cost}/{self.max_queue} units pending)")
        victims = sorted((r for r in self._q if r.kind == "search"),
                         key=lambda r: (r.slack, r.t_admit))
        now = time.perf_counter()
        shed = set()
        for v in victims:
            if self._cost + req.cost <= self.max_queue:
                break
            shed.add(id(v))
            self._cost -= v.cost
            self.stats["shed"] += 1
            if not v.future.done():
                v.future.set_exception(OverloadedError(
                    "shed under overload (least deadline slack)",
                    queued_us=(now - v.t_admit) * 1e6))
        if shed:
            self._q = deque(r for r in self._q if id(r) not in shed)
        if self._cost + req.cost > self.max_queue:
            self.stats["rejected"] += 1
            raise OverloadedError(
                f"queue full ({self._cost}/{self.max_queue} units pending, "
                f"nothing sheddable)")

    def _loop(self) -> None:
        """The dispatcher: launch a ripe group while fewer than DEPTH are
        in flight, collect the oldest once the waiter has seen its results
        computed, and otherwise sleep on the one condition that both the
        queue and the waiter signal (DESIGN.md §3.12)."""
        try:
            while True:
                with self._cond:
                    flight, group = self._next_locked()
                    if flight is None and group is None:
                        return
                    self._busy = True
                if flight is not None:
                    group = flight.group
                try:
                    if flight is not None:
                        self._complete(flight)
                    else:
                        self._dispatch(group)
                except Exception as e:       # contained: group-local
                    self._contain(group, e)
                except BaseException as e:   # fatal: crash the dispatcher
                    for r in group:
                        if not r.future.done():
                            r.future.set_exception(e)
                    raise
                with self._cond:
                    self._busy = False
                    if self._idle_locked():
                        self._cond.notify_all()   # wake flush()/close()
        except BaseException as e:  # noqa: BLE001 — recorded as _fatal
            self._dispatcher_died(e)
        finally:
            self._launched.put(None)             # stops the waiter

    def _next_locked(self):
        """Lock held: wait for the dispatcher's next step — (flight, None)
        to collect the oldest launched group, whose results are computed;
        (None, group) to dispatch a group from the queue; (None, None) to
        exit. Collecting first resolves finished requests without waiting
        for another launch."""
        while True:
            if self._inflight and self._inflight[0].ready:
                return self._inflight.popleft(), None
            timeout = None
            if len(self._inflight) < DEPTH:
                group, timeout = self._next_group_locked()
                if group is not None:
                    return None, group
            if self._closed and not self._q and not self._inflight:
                return None, None
            self._cond.wait(timeout=timeout)

    def _wait_ready(self) -> None:
        """The waiter thread: blocks on each launched group's results in
        launch order and marks it ready under the condition. It resolves
        nothing and opens no span; an error of the device surfaces again
        where the dispatcher collects the group."""
        while (flight := self._launched.get()) is not None:
            with contextlib.suppress(Exception):
                flight.pending.wait()
            with self._cond:
                flight.ready = True
                self._cond.notify_all()

    def _dispatcher_died(self, exc: BaseException) -> None:
        """The dispatcher thread is exiting on a fatal error. Fail every
        queued and in-flight Future (nobody should block forever on a dead
        loop) and poison `submit` — pinned by the stranded-Future
        regression tests."""
        with self._cond:
            self._fatal = exc
            err = FrontendClosedError(
                f"dispatcher thread died: {exc!r}")
            err.__cause__ = exc
            self._fail_pending_locked(err)
            for flight in self._inflight:
                for r in flight.group:
                    if not r.future.done():
                        r.future.set_exception(err)
            self._inflight.clear()
            self._cond.notify_all()

    def _contain(self, group, exc: Exception) -> None:
        """An engine Exception at launch or at collection: fail THIS group
        only; the dispatcher keeps serving. Retryable search failures get a
        bounded exponential backoff and re-queue at the head (still before
        any queued mutation — searches at one epoch commute, so head
        re-entry preserves the barrier order). Mutations never retry: the
        engine may have partially applied the write, and replaying it could
        double-apply."""
        r0 = group[0]
        if (r0.kind == "search" and is_retryable(exc)
                and r0.retries < self.max_retries):
            time.sleep(self.retry_backoff_ms * (2 ** r0.retries) * 1e-3)
            with self._cond:
                if self._closed or self._fatal is not None:
                    err = FrontendClosedError(
                        "front-end closed during retry")
                    err.__cause__ = exc
                    for r in group:
                        if not r.future.done():
                            r.future.set_exception(err)
                    return
                for r in group:
                    r.retries += 1
                self.stats["retries"] += 1
                self._q.extendleft(reversed(group))
                self._cost += sum(r.cost for r in group)
                self._cond.notify_all()
            return
        self.stats["failures"] += 1
        for r in group:
            if not r.future.done():
                r.future.set_exception(exc)

    def _next_group_locked(self):
        """With the lock held: pick the next dispatch group, or
        (None, timeout) to sleep. Mutations dispatch only from the queue
        head (strict barrier), and only once every launched search has been
        collected and resolved; searches group by coalescing key across the
        pre-mutation prefix (searches at one epoch commute, so grouping
        past a different-keyed search is safe — past a mutation is not).

        Deadline enforcement happens HERE, at collection time: a queued
        search whose explicit deadline already passed is dropped with
        DeadlineExceededError instead of consuming engine time. Requests
        already handed to the engine are never clawed back."""
        self._expire_locked()
        q = self._q
        if not q:
            return None, None
        head = q[0]
        if head.kind != "search":
            if self._inflight:
                return None, None   # the waiter wakes us as they finish
            q.popleft()
            self._cost -= head.cost
            return [head], None
        pre = []                    # searches before the first mutation
        for r in q:
            if r.kind != "search":
                break
            pre.append(r)
        groups: dict = {}
        for r in pre:
            groups.setdefault(r.key if r.key is not None else id(r),
                              []).append(r)
        now = time.perf_counter()
        target = None
        for g in groups.values():   # a full batch dispatches immediately
            if sum(r.nq for r in g) >= self.max_batch:
                target = g
                break
        if target is None:
            ripe = ([min(pre, key=lambda r: r.flush_at)] if self._draining
                    else [r for r in pre if now >= r.flush_at])
            if not ripe:
                return None, max(min(r.flush_at for r in pre) - now, 1e-4)
            first = min(ripe, key=lambda r: r.flush_at)
            target = groups[first.key if first.key is not None
                            else id(first)]
        chosen, total = [], 0
        for r in target:            # cap the coalesced batch at max_batch:
            if chosen and total + r.nq > self.max_batch:
                break               # never overflow into a LARGER padding
            chosen.append(r)        # bucket than solo serving would use
            total += r.nq
            if total >= self.max_batch:
                break
        taken = set(map(id, chosen))
        self._q = deque(r for r in q if id(r) not in taken)
        self._cost -= sum(r.cost for r in chosen)
        return chosen, None

    def _expire_locked(self) -> None:
        """Lock held: shed queued searches whose explicit deadline has
        already passed (their caller has given up; an answer now is pure
        waste). Best-effort requests (deadline_at=None) never expire."""
        now = time.perf_counter()
        dead = [r for r in self._q
                if r.kind == "search" and r.deadline_at is not None
                and now >= r.deadline_at]
        if not dead:
            return
        gone = set(map(id, dead))
        self._q = deque(r for r in self._q if id(r) not in gone)
        self._cost -= sum(r.cost for r in dead)
        self.stats["expired"] += len(dead)
        for r in dead:
            qd = (now - r.t_admit) * 1e6
            if not r.future.done():
                r.future.set_exception(DeadlineExceededError(
                    f"deadline_ms={r.params.deadline_ms} expired after "
                    f"{qd / 1e3:.1f}ms queued", queued_us=qd))

    def _dispatch(self, group) -> None:
        req = group[0]
        if req.kind == "add":
            X, tenant = req.payload
            ids = self.engine.add(X)
            if tenant is not None:
                if tenant in self.tenants:
                    self.tenants.extend(tenant, ids)
                else:
                    self.tenants.register(tenant, ids=ids)
            self.stats["mutations"] += 1
            req.future.set_result(ids)
            return
        if req.kind == "remove":
            ids, hard = req.payload
            n = self.engine.remove(ids, hard=hard)
            self.stats["mutations"] += 1
            req.future.set_result(n)
            return
        self._launch(group)

    def _launch(self, group) -> None:
        """Launch one coalesced search group and put it in flight, under
        the `frontend.dispatch` host span, which encloses
        `frontend.coalesce` and the engine's (or the replicas') launch-side
        spans (DESIGN.md §3.15)."""
        with TraceAnnotation("frontend.dispatch", requests=len(group),
                             queries=sum(r.nq for r in group)):
            p = group[0].params          # key-equal across the group
            with TraceAnnotation("frontend.coalesce"):
                Qcat = (np.concatenate([r.Q for r in group])
                        if len(group) > 1 else group[0].Q)
                filt_dev = (self.tenants.get(p.tenant)
                            if p.tenant is not None else None)
            t0 = time.perf_counter()
            want_replica = self._use_replica(p)
            use_replica = want_replica and self.health.allow("replica")
            # breaker open: full-coverage local serve, but the fan-out
            # capacity is reduced
            degraded = want_replica and not use_replica
            pending = None
            if use_replica:
                try:
                    pending = self._replica_launch(Qcat, p, filt_dev)
                except Exception:   # replica target failed: trip + fall back
                    self.health.failure("replica")
                    degraded = True
            replica = pending is not None
            if not replica:     # local path (policy, breaker, or fallback)
                pending = self.engine.search_request(
                    Qcat, p, _filter_dev=filt_dev, wait=False)
        flight = _Flight(group, pending, Qcat, filt_dev, t0, degraded,
                         replica, ahead=bool(self._inflight))
        with self._cond:
            self._inflight.append(flight)
        self._launched.put(flight)

    def _complete(self, flight: "_Flight") -> None:
        """Collect a launched group and resolve its Futures, under the
        `frontend.complete` host span, which encloses the engine's
        `engine.download` (or `replica.gather`) and `frontend.resolve`. A
        replica failure that surfaces here trips the breaker and serves the
        group on the local path."""
        group = flight.group
        with TraceAnnotation("frontend.complete", requests=len(group)):
            p = group[0].params
            degraded = flight.degraded
            try:
                r = flight.pending.result()
            except Exception:
                if not flight.replica:
                    raise
                self.health.failure("replica")
                degraded = True
                r = self.engine.search_request(flight.Q, p,
                                               _filter_dev=flight.filt_dev)
            else:
                if flight.replica:
                    self.health.success("replica")
                    self.stats["replica_dispatches"] += 1
            ids, vals, escalated = r.ids, r.scores, r.escalated
            if degraded:
                self.stats["degraded"] += len(group)
            engine_us = (time.perf_counter() - flight.t0) * 1e6
            t_done = time.perf_counter()
            epoch = getattr(self.engine.index, "_alive_epoch", -1)
            self.stats["dispatches"] += 1
            self.stats["requests"] += len(group)
            self.stats["coalesced"] += len(group) - 1
            self.stats["launched_ahead"] += flight.ahead
            with TraceAnnotation("frontend.resolve"):
                total = int(ids.shape[0])
                off = 0
                for r in group:
                    sl = slice(off, off + r.nq)
                    off += r.nq
                    r.future.set_result(SearchResult(
                        ids[sl], vals[sl] if vals is not None else None,
                        engine_us=engine_us,
                        queued_us=(t_done - r.t_admit) * 1e6 - engine_us,
                        batch_size=total, escalated=escalated, epoch=epoch,
                        tenant=p.tenant, deadline_ms=r.params.deadline_ms,
                        degraded=degraded, retries=r.retries))

    # ------------------------------------------------------ replica fan-out
    def _use_replica(self, p: SearchParams) -> bool:
        if self.policy == "local":
            return False
        n_dev = len(jax.devices())
        if self.policy == "replica" and n_dev < 2:
            return False
        if self.policy == "auto" and n_dev < 2:
            return False
        # inline host filters stay on the engine path (it owns their
        # compose-and-upload); tenant filters are already device-resident
        return not p.has_inline_filter

    def _replica_launch(self, Q: np.ndarray, p: SearchParams,
                        filt_dev) -> PendingSearch:
        """Launch a coalesced batch sharded row-wise over all devices; its
        `replica.gather` is at collection. The engine's plan decides what
        runs (filter, escalation, static arguments, padding to a multiple
        of the replicas), as for a local launch, so results stay bitwise
        identical to local execution."""
        faults.serve_point("replica:dispatch")
        devs = jax.devices()
        R = len(devs)
        if self._mesh is None:
            from jax.sharding import Mesh, NamedSharding
            from jax.sharding import PartitionSpec as P
            self._mesh = Mesh(np.array(devs), ("r",))
            self._rows = NamedSharding(self._mesh, P("r"))
        plan = self.engine.plan(Q, p, filt_dev, rows=R)
        key = (*plan.static.items(), plan.filter is not None, R)
        fn = self._rep_cache.get(key)
        if fn is None:
            from repro.core.distributed import make_replicated_search
            fn = jax.jit(make_replicated_search(
                self._mesh, ("r",), with_filter=plan.filter is not None,
                **plan.static))
            self._rep_cache[key] = fn
        args = [self._placed(self._rep_index, plan.packed)]
        if plan.filter is not None:
            args.append(self._placed(self._rep_filters, plan.filter))
        with TraceAnnotation("replica.scatter"):
            Qd = jax.device_put(plan.Q, self._rows)
        with TraceAnnotation("replica.launch"):
            return PendingSearch(fn(args[0], Qd, *args[1:]), plan.nq,
                                 span="replica.gather",
                                 escalated=plan.escalated)

    def _placed(self, cache: EpochLRU, src):
        """`src` (the packed index, a filter bitmap) replicated on the
        replica mesh, placed once per source object: pack() and the filter
        caches return the same object until a mutation, so a new object is
        exactly what needs placing again. The entry holds `src`, so its
        id is not reused while cached (DESIGN.md §3.12)."""
        def place():
            from repro.core.distributed import replicate_on_mesh
            nbytes = (sum(x.nbytes for x in jax.tree_util.tree_leaves(src))
                      * (self._mesh.devices.size - 1))
            with TraceAnnotation("replica.place", bytes=nbytes):
                placed = jax.block_until_ready(
                    replicate_on_mesh(src, self._mesh))
            self.stats["replica_placements"] += 1
            self.stats["replica_placed_bytes"] += nbytes
            return src, placed
        return cache.get(id(src), None, place)[1]

    # ---------------------------------------------------------- durability
    def save(self, path: str) -> None:
        """Snapshot engine + front-end: the index snapshot carries the
        batching config in its manifest and every tenant mask as an
        `extra.` array (same atomicity/CRC guarantees)."""
        self.flush()
        tmeta, tarrays = self.tenants.state()
        cfg = {"max_batch": self.max_batch,
               "max_delay_ms": self.max_delay_ms,
               "default_deadline_ms": self.default_deadline_ms,
               "policy": self.policy,
               "tenant_capacity": self.tenants._cache.capacity,
               "max_queue": self.max_queue,
               "overload": self.overload,
               "mutation_cost": self.mutation_cost,
               "max_retries": self.max_retries,
               "retry_backoff_ms": self.retry_backoff_ms}
        self.engine.save(path, extra={"frontend": cfg, **tmeta},
                         extra_arrays=tarrays)

    @classmethod
    def open(cls, path: str, *, wal: bool = False, fsync: str = "always",
             **overrides) -> "ServingFrontend":
        """Reopen a saved front-end: engine snapshot (+ WAL replay) plus
        the saved batching config and tenant registry. `overrides` replace
        saved config fields (e.g. policy="local")."""
        from repro.ckpt.index_store import load_extra_arrays, read_manifest
        eng = AnnEngine.open(path, wal=wal, fsync=fsync)
        ipath = os.path.join(path, "index")
        extra = read_manifest(ipath)["meta"].get("extra", {})
        cfg = dict(extra.get("frontend", {}))
        cfg.update(overrides)
        fe = cls(eng, **cfg)
        arrays = load_extra_arrays(ipath)
        for t in extra.get("tenants", []):
            m = arrays.get(f"tenant.{t}")
            if m is not None:
                fe.tenants.register(t, mask=m.astype(bool))
        return fe
