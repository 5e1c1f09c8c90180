"""Serving front-end (ISSUE 8 tentpole, DESIGN.md §3.12).

Pins, per the acceptance criteria:

1. Determinism: a request served inside a coalesced batch is BITWISE
   identical to the same request served solo at the same index epoch —
   under genuinely concurrent clients.
2. No recompiles: coalescing reuses the engine's power-of-two padding
   buckets, so batching across arbitrary arrival patterns adds ZERO jit
   cache entries beyond the buckets solo serving already compiled.
3. Deadline flushing: a partial batch dispatches once the oldest request
   has spent half its deadline budget queued (and `max_delay_ms` clamps
   that wait under generous deadlines).
4. Tenant filters: standing per-tenant bitmaps are cached per index
   epoch (one device upload per tenant per epoch), LRU-evicted at
   capacity, and invalidated by mutation.
5. Mutations are barriers: searches submitted before an enqueued
   mutation serve the pre-mutation epoch, searches after it the
   post-mutation epoch — observable via SearchResult.epoch.
6. Durability: save/open round-trips the batching config and the tenant
   registry alongside the engine snapshot.
7. Replica fan-out (subprocess, 8 virtual devices): policy="replica"
   shards coalesced batches across devices with bitwise-local results.
8. Two batches in flight: the dispatcher launches a ripe group while the
   last is still on the device, never holds more than two, answers
   bitwise as solo, and lets a mutation or `flush()`/`close()` through
   only once every launched batch has been resolved.
"""
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.core.search import search_jit_batched
from repro.data.vectors import make_manifold
from repro.serve.api import SearchParams
from repro.serve.engine import AnnEngine
from repro.serve.frontend import (ServingFrontend, TenantFilterBank,
                                  UnknownTenantError)

N, D, NQ = 3_000, 24, 32


@pytest.fixture(scope="module")
def ds():
    return make_manifold(jax.random.PRNGKey(0), n=N, d=D, nq=NQ,
                         intrinsic_dim=8)


@pytest.fixture()
def engine(ds):
    return AnnEngine.build(jax.random.PRNGKey(1), ds.X, 16,
                           spill_mode="soar", train_iters=5)


# ------------------------------------------------------------- determinism
def test_coalesced_equals_solo(ds, engine):
    """Concurrent single-query clients coalesce into shared dispatches;
    every client's rows are bitwise the solo engine answer."""
    solo = {i: engine.search(ds.Q[i:i + 1], k=6) for i in range(NQ)}
    with ServingFrontend(engine, policy="local",
                         default_deadline_ms=200.0) as fe:
        results = {}

        def client(i):
            results[i] = fe.search(ds.Q[i:i + 1], SearchParams(k=6))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(NQ)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = dict(fe.stats)
    assert stats["requests"] == NQ
    assert stats["dispatches"] < NQ          # coalescing actually happened
    assert stats["coalesced"] == NQ - stats["dispatches"]
    for i in range(NQ):
        assert np.array_equal(results[i].ids, solo[i][0]), i
        assert np.array_equal(results[i].scores, solo[i][1]), i
        assert results[i].batch_size >= 1
        assert results[i].queued_us >= 0.0


def test_inline_filter_dispatches_solo(ds, engine):
    mask = np.zeros(N, np.uint8)
    mask[: N // 4] = 1
    ref_ids, ref_sc = engine.search(ds.Q[:3], k=5, filter_mask=mask)
    with ServingFrontend(engine, policy="local") as fe:
        r = fe.search(ds.Q[:3], SearchParams(k=5, filter_mask=mask))
        assert fe.stats["dispatches"] == 1 and r.batch_size == 3
    assert np.array_equal(r.ids, ref_ids)
    assert np.array_equal(r.scores, ref_sc)


# ------------------------------------------------------------ no recompiles
def test_no_recompilation_from_coalescing(ds, engine):
    """Coalesced dispatch reuses the solo path's padding buckets: after
    warming the buckets solo traffic uses, arbitrary concurrent batch
    sizes through the front-end add no jit cache entries."""
    from repro.analysis import CacheWatch
    for nq in (1, 9, 17):            # warm buckets 8, 16, 32
        engine.search(ds.Q[:nq], k=6)
    with CacheWatch(search_jit_batched):         # shared sentinel (§3.14)
        with ServingFrontend(engine, policy="local", max_batch=32,
                             default_deadline_ms=100.0) as fe:
            futs = []
            for i in range(24):      # mixed sizes, concurrent arrival
                nq = 1 + (i % 3)
                futs.append(fe.submit(ds.Q[i % NQ:i % NQ + nq],
                                      SearchParams(k=6)))
            for f in futs:
                f.result()


# --------------------------------------------------------- deadline flushes
def test_deadline_flushes_partial_batch(ds, engine):
    """max_delay_ms=None → pure half-deadline policy: a partial batch
    (3 ≪ max_batch) must dispatch once half the 80 ms budget is spent,
    not wait for the batch to fill."""
    with ServingFrontend(engine, policy="local", max_batch=64,
                         max_delay_ms=None) as fe:
        t0 = time.perf_counter()
        futs = [fe.submit(ds.Q[i:i + 1],
                          SearchParams(k=5, deadline_ms=80.0))
                for i in range(3)]
        res = [f.result(timeout=5.0) for f in futs]
        elapsed_ms = (time.perf_counter() - t0) * 1e3
    assert all(r.batch_size == 3 for r in res)   # one coalesced dispatch
    assert fe.stats["dispatches"] == 1
    # flushed by the deadline timer: waited at least ~half the budget
    # (not dispatched instantly as a full batch) but well under the
    # full deadline plus engine time
    assert elapsed_ms < 5_000


def test_max_delay_clamps_generous_deadlines(ds, engine):
    """A 10 s deadline must NOT stall the queue 5 s — max_delay_ms caps
    the batching wait."""
    with ServingFrontend(engine, policy="local", max_batch=64,
                         max_delay_ms=5.0) as fe:
        t0 = time.perf_counter()
        fe.search(ds.Q[:1], SearchParams(k=5, deadline_ms=10_000.0))
        elapsed = time.perf_counter() - t0
    assert elapsed < 3.0


# ---------------------------------------------------------- tenant filters
def test_tenant_filter_serving(ds, engine):
    ids_t0 = np.flatnonzero(np.arange(N) % 3 == 0)
    with ServingFrontend(engine, policy="local") as fe:
        fe.register_tenant("t0", ids=ids_t0)
        r = fe.search(ds.Q, SearchParams(k=6, tenant="t0"))
        # tenant serving == engine-level subset filtering, bitwise
        ref_ids, ref_sc = engine.search(ds.Q, k=6, filter_ids=ids_t0)
        assert np.array_equal(r.ids, ref_ids)
        assert np.array_equal(r.scores, ref_sc)
        ok = r.ids[r.ids >= 0]
        assert (ok % 3 == 0).all()
        with pytest.raises(UnknownTenantError):
            fe.search(ds.Q[:1], SearchParams(k=3, tenant="nope"))


def test_tenant_lru_eviction_and_epoch_invalidation(ds, engine):
    bank = TenantFilterBank(engine.index, capacity=2)
    for t in ("a", "b", "c"):
        bank.register(t, ids=np.arange(100))
    bank.get("a"); bank.get("b")
    assert bank.fills == 2
    bank.get("a"); bank.get("b")               # steady state: cache hits
    assert bank.fills == 2
    bank.get("c")                              # fills + evicts "a" (LRU)
    assert bank.fills == 3 and "a" not in bank._cache
    bank.get("a")                              # re-upload after eviction
    assert bank.fills == 4
    engine.remove([0, 1], hard=False)          # mutation bumps the epoch
    bank.get("a")                              # stale → rebuild
    assert bank.fills == 5
    assert int(np.asarray(bank.get("a"))[0]) == 0   # tombstone composed in
    assert bank.fills == 5                     # second get in-epoch: hit
    bank.extend("a", [200, 201])               # registry bump → rebuild
    assert int(np.asarray(bank.get("a"))[200]) == 1
    assert bank.fills == 6


def test_tenant_coalescing_same_tenant_only(ds, engine):
    """Same-tenant requests share a dispatch; different tenants never
    share one (their filter bitmaps differ)."""
    with ServingFrontend(engine, policy="local",
                         default_deadline_ms=200.0) as fe:
        fe.register_tenant("a", ids=np.arange(0, N, 2))
        fe.register_tenant("b", ids=np.arange(1, N, 2))
        futs = ([fe.submit(ds.Q[i:i + 1], SearchParams(k=4, tenant="a"))
                 for i in range(4)]
                + [fe.submit(ds.Q[i:i + 1], SearchParams(k=4, tenant="b"))
                   for i in range(4)])
        res = [f.result(timeout=10.0) for f in futs]
    assert all(r.tenant == "a" for r in res[:4])
    assert all(r.tenant == "b" for r in res[4:])
    for r in res[:4]:
        ok = r.ids[r.ids >= 0]
        assert (ok % 2 == 0).all()
    for r in res[4:]:
        ok = r.ids[r.ids >= 0]
        assert (ok % 2 == 1).all()
    assert fe.stats["dispatches"] >= 2


# ------------------------------------------------------- mutation barriers
def test_mutation_is_a_barrier(ds, engine):
    """Searches queued before a mutation serve the old epoch; searches
    queued after it serve the new one — even when everything is enqueued
    back-to-back before the dispatcher wakes."""
    from concurrent.futures import Future
    from repro.serve.frontend import _Request
    # generous deadlines: queued-behind-a-barrier requests must not be
    # shed by deadline enforcement while the mutation (re)compiles
    with ServingFrontend(engine, policy="local", max_batch=64,
                         max_delay_ms=5.0) as fe:
        e0 = engine.index._alive_epoch
        pre = [fe.submit(ds.Q[i:i + 1],
                         SearchParams(k=5, deadline_ms=10_000.0))
               for i in range(3)]
        mfut: Future = Future()
        fe._enqueue(_Request("remove", mfut,
                             payload=(np.arange(N), False)))
        post = [fe.submit(ds.Q[i:i + 1],
                          SearchParams(k=5, deadline_ms=10_000.0))
                for i in range(3)]
        pre_r = [f.result(timeout=10.0) for f in pre]
        assert mfut.result(timeout=10.0) == N
        post_r = [f.result(timeout=10.0) for f in post]
    for r in pre_r:                  # served before the tombstoning
        assert r.epoch == e0
        assert (r.ids >= 0).any()
    for r in post_r:                 # served after: everything is dead
        assert r.epoch > e0
        assert (r.ids == -1).all()


def test_add_with_tenant_is_atomic(ds, engine):
    """add(tenant=...) extends the tenant's standing filter in the same
    barrier as the insert: the fresh points are immediately findable
    under their tenant, and only the allowed ids are ever served."""
    rng = np.random.default_rng(7)
    with ServingFrontend(engine, policy="local") as fe:
        fe.register_tenant("t", ids=[0])
        new = rng.normal(size=(5, D)).astype(np.float32)
        ids = fe.add(new, tenant="t")
        allowed = {0, *map(int, ids)}
        r = fe.search(new, SearchParams(k=3, tenant="t"))
        served = set(map(int, r.ids[r.ids >= 0]))
        assert served and served <= allowed
        # a brand-new tenant can be created by its first add, too
        ids2 = fe.add(new, tenant="fresh")
        r2 = fe.search(new, SearchParams(k=3, tenant="fresh"))
        srv2 = set(map(int, r2.ids[r2.ids >= 0]))
        assert srv2 and srv2 <= set(map(int, ids2))


# -------------------------------------------------------------- durability
def test_save_open_round_trip(tmp_path, ds, engine):
    with ServingFrontend(engine, policy="local", max_batch=48,
                         max_delay_ms=3.0,
                         default_deadline_ms=77.0) as fe:
        fe.register_tenant("acme", ids=np.arange(0, N, 5))
        ref = fe.search(ds.Q, SearchParams(k=6, tenant="acme"))
        fe.save(str(tmp_path / "snap"))
    fe2 = ServingFrontend.open(str(tmp_path / "snap"))
    try:
        assert fe2.max_batch == 48 and fe2.max_delay_ms == 3.0
        assert fe2.default_deadline_ms == 77.0
        assert fe2.tenants.tenants == ["acme"]
        r = fe2.search(ds.Q, SearchParams(k=6, tenant="acme"))
        assert np.array_equal(r.ids, ref.ids)
        assert np.array_equal(r.scores, ref.scores)
    finally:
        fe2.close()


def test_close_rejects_new_work(ds, engine):
    fe = ServingFrontend(engine, policy="local")
    fe.search(ds.Q[:1], SearchParams(k=3))
    fe.close()
    fe.close()                                    # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        fe.submit(ds.Q[:1], SearchParams(k=3))


# --------------------------------------------------- two batches in flight
def _until(cond, seconds=30.0):
    t0 = time.perf_counter()
    while not cond():
        assert time.perf_counter() - t0 < seconds, "timed out"
        time.sleep(0.001)


def test_launch_ahead_keeps_at_most_two_in_flight(ds, engine, held):
    """Three groups ripe together (three coalescing keys): the second
    launches while the first is still on the device, the third waits for
    a slot and launches once the first is collected, still ahead of the
    second. Every answer is bitwise the solo one."""
    solo = [engine.search(ds.Q[2 * i:2 * i + 2], k=4 + i) for i in range(3)]
    with ServingFrontend(engine, policy="local", max_delay_ms=1.0) as fe:
        futs = [fe.submit(ds.Q[2 * i:2 * i + 2], SearchParams(k=4 + i))
                for i in range(3)]
        _until(lambda: len(fe._inflight) == 2)
        time.sleep(0.05)
        assert len(fe._inflight) == 2 and len(fe._q) == 1   # no third slot
        held.release()                       # the first batch finishes
        _until(lambda: futs[0].done() and not fe._q
               and len(fe._inflight) == 2)
        assert not futs[1].done()
        held.release(2)
        res = [f.result(timeout=30) for f in futs]
        stats = dict(fe.stats)
    assert stats["dispatches"] == 3 and stats["launched_ahead"] == 2
    for r, (ids, sc) in zip(res, solo):
        assert np.array_equal(r.ids, ids) and np.array_equal(r.scores, sc)


@pytest.mark.parametrize("n_pre", [1, 2])
def test_mutation_waits_for_the_batches_in_flight(ds, engine, held, n_pre):
    """A mutation queued behind launched batches (one, with a slot free,
    or two) runs only after they are resolved at the old epoch; a search
    queued after it serves the new epoch."""
    from concurrent.futures import Future
    from repro.serve.frontend import _Request
    with ServingFrontend(engine, policy="local", max_delay_ms=1.0) as fe:
        e0 = engine.index._alive_epoch
        pre = [fe.submit(ds.Q[i:i + 1],
                         SearchParams(k=4 + i, deadline_ms=10_000.0))
               for i in range(n_pre)]
        _until(lambda: len(fe._inflight) == n_pre)
        mfut: Future = Future()
        fe._enqueue(_Request("remove", mfut,
                             payload=(np.arange(N), False)))
        post = fe.submit(ds.Q[:1], SearchParams(k=4, deadline_ms=10_000.0))
        time.sleep(0.05)
        assert not mfut.done() and not post.done() and len(fe._q) == 2
        held.release(n_pre + 1)
        pre_r = [f.result(timeout=30) for f in pre]
        assert mfut.result(timeout=30) == N
        post_r = post.result(timeout=30)
    for r in pre_r:
        assert r.epoch == e0 and (r.ids >= 0).any()
    assert post_r.epoch > e0 and (post_r.ids == -1).all()


@pytest.mark.parametrize("how", ["flush", "close"])
def test_flush_and_close_wait_for_the_batches_in_flight(ds, engine, held,
                                                        how):
    fe = ServingFrontend(engine, policy="local", max_delay_ms=1.0)
    try:
        fut = fe.submit(ds.Q[:1], SearchParams(k=4))
        _until(lambda: len(fe._inflight) == 1)
        t = threading.Thread(target=getattr(fe, how))
        t.start()
        time.sleep(0.05)
        assert t.is_alive() and not fut.done()
        held.release()
        t.join(timeout=30)
        assert not t.is_alive()
        assert fut.done() and fut.result().ids.shape == (1, 4)
    finally:
        fe.close()


def test_pipeline_under_thread_stress(ds, engine):
    """More clients than cores, three coalescing keys and a short switch
    interval: every request resolves exactly once, bitwise as solo, the
    counters add up, and nothing is left in flight after close()."""
    keys = (4, 5, 6)
    solo = {(i, k): engine.search(ds.Q[i:i + 1], k=k)
            for i in range(NQ) for k in keys}
    for k in keys:                     # every bucket the clients can hit
        for nq in (2, 4):
            engine.search(ds.Q[:nq], k=k)
    n_clients, per_client = os.cpu_count() + 2, 8
    got, errors = [], []
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        fe = ServingFrontend(engine, policy="local", max_delay_ms=1.0,
                             max_batch=4)

        def client(c):
            try:
                for j in range(per_client):
                    i, k = (c * per_client + j) % NQ, keys[(c + j) % 3]
                    got.append((i, k, fe.search(ds.Q[i:i + 1],
                                                SearchParams(k=k))))
            except Exception as e:     # surfaced below
                errors.append(repr(e))

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        fe.close()
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    assert len(got) == n_clients * per_client
    st = fe.stats
    assert st["requests"] == len(got)
    assert st["requests"] - st["coalesced"] == st["dispatches"]
    assert st["launched_ahead"] <= st["dispatches"]
    assert not fe._inflight and not fe._q and fe._cost == 0
    for i, k, r in got:
        assert np.array_equal(r.ids, solo[i, k][0])
        assert np.array_equal(r.scores, solo[i, k][1])


# ----------------------------------------------------------- replica policy
SCRIPT_REPLICA = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.data.vectors import make_manifold
from repro.serve.api import SearchParams
from repro.serve.engine import AnnEngine
from repro.serve.frontend import ServingFrontend

assert len(jax.devices()) == 8
ds = make_manifold(jax.random.PRNGKey(0), n=3_000, d=24, nq=32,
                   intrinsic_dim=8)
eng = AnnEngine.build(jax.random.PRNGKey(1), ds.X, 16, train_iters=5)
solo_ids, solo_sc = eng.search(ds.Q, k=6)

fe = ServingFrontend(eng, policy="replica", default_deadline_ms=200.0)
r = fe.search(ds.Q, SearchParams(k=6))
assert fe.stats["replica_dispatches"] == 1
assert np.array_equal(r.ids, solo_ids), "replica ids != local"
assert np.array_equal(r.scores, solo_sc), "replica scores != local"

# tenant filter under replica fan-out, still bitwise local
fe.register_tenant("t", ids=np.arange(0, 3_000, 2))
rt = fe.search(ds.Q, SearchParams(k=6, tenant="t"))
ref_ids, ref_sc = eng.search(ds.Q, k=6, filter_ids=np.arange(0, 3_000, 2))
assert np.array_equal(rt.ids, ref_ids)
assert np.array_equal(rt.scores, ref_sc)

# "auto" on 8 devices picks replica
fe.policy = "auto"
fe.search(ds.Q, SearchParams(k=6))
assert fe.stats["replica_dispatches"] == 3
fe.close()
print("OK")
"""


def test_replica_policy_multidevice():
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT_REPLICA], capture_output=True,
        text=True, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                        "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "OK" in r.stdout


# One plan (DESIGN.md §3.12): the replica launch runs the engine's plan, so
# for the same request its jit-cache key holds exactly the static arguments
# of the engine's own launch — unfiltered, behind the standing soft-delete
# filter, and for a tenant. A one-device mesh is enough to launch it.
@pytest.mark.parametrize("case", ["plain", "soft_deleted", "tenant"])
def test_replica_launch_takes_the_engine_plan(ds, engine, monkeypatch, case):
    from repro.core import search
    seen = []
    real = search.search_jit_batched

    def spy(*a, **kw):
        seen.append({k: v for k, v in kw.items()
                     if k not in ("bq", "filter")})
        return real(*a, **kw)

    monkeypatch.setattr(search, "search_jit_batched", spy)
    if case == "soft_deleted":
        engine.remove(np.arange(0, N, 3), hard=False)
    with ServingFrontend(engine, policy="local") as fe:
        fe.register_tenant("t", ids=np.arange(0, N, 2))
        p = SearchParams(k=6, tenant="t" if case == "tenant" else None,
                         escalate=True).validate(
            default_top_t=engine.top_t, default_rerank=engine.rerank_budget)
        filt = fe.tenants.get("t") if case == "tenant" else None
        local = engine.search_request(ds.Q, p, _filter_dev=filt)
        rep = fe._replica_launch(ds.Q, p, filt).result()
        (key,) = fe._rep_cache
    assert dict(key[:-2]) == seen[-1]
    assert key[-2:] == (case != "plain", len(jax.devices()))
    assert rep.escalated == local.escalated
    assert np.array_equal(rep.ids, local.ids)
    assert np.array_equal(rep.scores, local.scores)


# The index is placed on the replicas once per packed snapshot (DESIGN.md
# §3.12). After the warm-up dispatch every further one runs with implicit
# device-to-device transfers disallowed for the whole process (the guard
# is set globally: the dispatcher is another thread), so a dispatch that
# broadcast the index or a filter again would raise.
SCRIPT_PLACEMENT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from chip_smoke import exact_topk, recall_at_k
from repro.data.vectors import make_manifold
from repro.serve.api import SearchParams
from repro.serve.engine import AnnEngine
from repro.serve.frontend import ServingFrontend

case = sys.argv[1]
assert len(jax.devices()) == 4
ds = make_manifold(jax.random.PRNGKey(0), n=3_000, d=24, nq=32,
                   intrinsic_dim=8)
eng = AnnEngine.build(jax.random.PRNGKey(1), ds.X, 16, train_iters=5)
fe = ServingFrontend(eng, policy="replica", default_deadline_ms=200.0)
fe.search(ds.Q, SearchParams(k=10))              # compiles and places
st = fe.stats
assert st["replica_placements"] == 1, st
jax.config.update("jax_transfer_guard_device_to_device", "disallow")


def same_as_local(**kw):
    r = fe.search(ds.Q, SearchParams(k=10, **kw))
    ids, sc = eng.search(ds.Q, k=10, **({"filter_ids": TENANT_IDS}
                                        if kw else {}))
    assert not r.degraded, "the replica dispatch fell back"
    assert np.array_equal(r.ids, ids), "replica ids != local"
    assert np.array_equal(r.scores, sc), "replica scores != local"
    return r.ids


def exact_recall(ids):
    # the exact brute force over the live set; 0.9 is the floor the chip
    # smoke holds this pipeline to, and the replica answers equal the
    # local ones bitwise, so recall only catches a stale replica (a
    # removed id served, an added one missed)
    keep = eng.index.alive[:eng.index.n_total].astype(bool)
    X = eng.index.rerank[:eng.index.n_total]
    rec = recall_at_k(ids, exact_topk(X, ds.Q, keep=keep))
    assert rec >= 0.9, rec
    assert keep[ids[ids >= 0]].all(), "a removed id was served"


TENANT_IDS = np.arange(0, 3_000, 2)
if case == "steady":
    for _ in range(3):
        same_as_local()
    assert st["replica_placements"] == 1 and st["replica_dispatches"] == 4
    packed = eng.index.pack()
    placed = fe._placed(fe._rep_index, packed)
    (shard0,) = [s for s in placed.rerank.addressable_shards
                 if s.device == jax.devices()[0]]
    assert (shard0.data.unsafe_buffer_pointer()
            == packed.rerank.unsafe_buffer_pointer()), "index held twice"
    size = sum(x.nbytes for x in jax.tree_util.tree_leaves(packed))
    assert st["replica_placed_bytes"] == 3 * size, st
elif case == "mutation":
    new = make_manifold(jax.random.PRNGKey(5), n=200, d=24, nq=1,
                        intrinsic_dim=8).X
    added = fe.add(new)
    exact_recall(same_as_local())
    assert st["replica_placements"] == 2, st
    fe.remove(np.concatenate([added[:50], np.arange(0, 3_000, 7)]))
    exact_recall(same_as_local())
    assert st["replica_placements"] == 3, st
elif case == "tenant":
    fe.register_tenant("t", ids=TENANT_IDS)
    for _ in range(3):
        ids = same_as_local(tenant="t")
        assert np.isin(ids[ids >= 0], TENANT_IDS).all()
    assert st["replica_placements"] == 2 and fe.tenants.fills == 1, st
fe.close()
assert st["failures"] == 0 and st["degraded"] == 0, st
print("OK")
"""


@pytest.mark.parametrize("case", ["steady", "mutation", "tenant"])
def test_replica_placement_multidevice(case):
    """steady: no implicit device-to-device copy after the warm-up, one
    placement, device 0's shard is the engine's own buffer; mutation: an
    add and a remove each place the new pack again; tenant: a tenant
    bitmap is placed once. Every answer is bitwise the local path's."""
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT_PLACEMENT, case], capture_output=True,
        text=True, env={"PYTHONPATH": "src:.", "PATH": "/usr/bin:/bin",
                        "JAX_PLATFORMS": "cpu"}, timeout=600)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "OK" in r.stdout
