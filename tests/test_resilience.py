"""Serving-tier resilience (ISSUE 9 tentpole, DESIGN.md §3.13).

Pins, per the acceptance criteria:

1. Error taxonomy: ServingError subclasses carry queued_us/engine_us and
   a retryable classification (`is_retryable`).
2. Fault-injection grammar: @N / @NxM firing windows, ";" multi-plan,
   modes error/transient/delay, and the repro.ckpt.faults shim sharing
   state with repro.faults.
3. Circuit breaker: CLOSED → OPEN → HALF_OPEN (single probe) → CLOSED
   walked with a fake clock; HealthTracker mask/shards_ok renderings.
4. Admission control: bounded queue rejects (OverloadedError) or sheds
   least-deadline-slack searches; mutations never shed and never evict
   searches.
5. Deadline enforcement: an explicitly-deadlined request that expires
   while queued fails with DeadlineExceededError (queued_us populated)
   WITHOUT consuming engine time; best-effort requests never expire.
6. Containment: an engine Exception fails only its group and the
   dispatcher keeps serving; transient faults are absorbed by bounded
   retry + backoff (SearchResult.retries); mutations never retry.
7. Stranded-Future regression: a BaseException out of the engine fails
   every pending/in-flight Future, poisons submit with
   FrontendClosedError, and close() still returns — zero hung Futures.
8. Shutdown ordering: close() during an in-flight mutation, submits
   racing close(), close(drain=False) failing queued work
   deterministically.
9. Durability composition: a WAL crash mid-mutation BEHIND the front-end
   recovers bitwise per the PR 7 contract.
10. Degraded fan-out (subprocess, 8 virtual devices): with_health
    all-healthy is bitwise the plain path; a dead shard's ids vanish
    while healthy shards' answers survive; the replica breaker falls
    back to bitwise-identical local serving flagged degraded, then
    heals through the half-open probe.
"""
import subprocess
import sys
import threading
import time
from concurrent.futures import Future

import jax
import numpy as np
import pytest

from repro import faults
from repro.data.vectors import make_manifold
from repro.faults import (FaultPlan, InjectedCrash, InjectedFault,
                          InjectedTransientFault)
from repro.serve.api import (DeadlineExceededError, FrontendClosedError,
                             OverloadedError, SearchParams, ServingError,
                             is_retryable)
from repro.serve.engine import AnnEngine
from repro.serve.frontend import ServingFrontend, _Request
from repro.serve.health import (CLOSED, HALF_OPEN, OPEN, CircuitBreaker,
                                HealthTracker, shards_ok_from_mask)

N, D, NQ = 2_000, 16, 16


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.uninstall()
    yield
    faults.uninstall()


@pytest.fixture(scope="module")
def ds():
    return make_manifold(jax.random.PRNGKey(0), n=N, d=D, nq=NQ,
                         intrinsic_dim=8)


@pytest.fixture()
def engine(ds):
    return AnnEngine.build(jax.random.PRNGKey(1), ds.X, 16,
                           spill_mode="soar", train_iters=5)


def _stall_search(fe, ds, ms):
    """Park the dispatcher inside a search dispatch for ~ms via a latency
    spike on engine:search (hit 1 only), so subsequent submits pile up in
    the queue deterministically. Returns the sacrificial future."""
    faults.inject("engine:search@1x1", mode="delay", delay_ms=ms)
    fut = fe.submit(ds.Q[:1], SearchParams(k=3))
    t0 = time.perf_counter()
    while fe._q and time.perf_counter() - t0 < 5.0:
        time.sleep(0.001)
    assert not fe._q, "dispatcher never picked up the stall request"
    return fut


def _stall_mutation(fe, ms):
    """Same, but inside a mutation (engine:add) — keeps the
    engine:search hit counter untouched for plans armed on it."""
    faults.inject("engine:add@1x1", mode="delay", delay_ms=ms)
    mfut: Future = Future()
    X = np.zeros((1, D), np.float32)
    fe._enqueue(_Request("add", mfut, payload=(X, None),
                         t_admit=time.perf_counter(), cost=1))
    t0 = time.perf_counter()
    while fe._q and time.perf_counter() - t0 < 5.0:
        time.sleep(0.001)
    assert not fe._q, "dispatcher never picked up the stall mutation"
    return mfut


# ------------------------------------------------------------ taxonomy
def test_error_taxonomy():
    e = OverloadedError("full", queued_us=5.0)
    assert isinstance(e, ServingError) and isinstance(e, RuntimeError)
    assert e.queued_us == 5.0 and e.engine_us == 0.0
    assert is_retryable(e)                       # the caller may back off
    assert not is_retryable(DeadlineExceededError("late"))
    assert not is_retryable(FrontendClosedError("closed"))
    assert is_retryable(InjectedTransientFault("x"))
    assert not is_retryable(InjectedFault("x"))
    # stdlib transient types classify retryable without the attribute
    assert is_retryable(TimeoutError())
    assert is_retryable(ConnectionError())
    assert not is_retryable(ValueError())


def test_deadline_param_bounds():
    assert SearchParams(deadline_ms=0.05).validate().deadline_ms == 0.05
    assert (SearchParams(deadline_ms=600_000).validate().deadline_ms
            == 600_000.0)
    assert SearchParams().validate().deadline_ms is None
    for bad in (0, 0.01, -5, 600_001, float("nan")):
        with pytest.raises(ValueError, match="deadline_ms"):
            SearchParams(deadline_ms=bad).validate()


# ------------------------------------------------------- fault grammar
def test_fault_window_grammar():
    plan = FaultPlan.parse("p@2x3", mode="error")
    assert (plan.point, plan.hits, plan.times) == ("p", 2, 3)
    faults.install("p@2x3", mode="error")
    fired = []
    for _ in range(6):
        try:
            faults.serve_point("p")
            fired.append(False)
        except InjectedFault:
            fired.append(True)
    assert fired == [False, True, True, True, False, False]


def test_fault_multi_plan_and_shim_share_state():
    faults.install("a@1;b@1", mode="transient")
    with pytest.raises(InjectedTransientFault):
        faults.serve_point("a")
    with pytest.raises(InjectedTransientFault):
        faults.serve_point("b")
    from repro.ckpt import faults as shim
    assert shim.InjectedCrash is faults.InjectedCrash
    assert shim.InjectedFault is faults.InjectedFault
    shim.inject("c@1", mode="error")             # append through the shim
    with pytest.raises(InjectedFault):
        faults.serve_point("c")                  # ...fires via the module


def test_fault_delay_mode_is_a_latency_spike():
    faults.install("d", mode="delay", delay_ms=30.0)
    t0 = time.perf_counter()
    faults.serve_point("d")                      # sleeps, does not raise
    assert time.perf_counter() - t0 >= 0.025


# ------------------------------------------------------ circuit breaker
def test_circuit_breaker_state_machine():
    t = [0.0]
    cb = CircuitBreaker(fail_threshold=2, reset_after_s=10.0,
                        clock=lambda: t[0])
    assert cb.state == CLOSED and cb.allow()
    cb.record_failure()
    assert cb.state == CLOSED                    # under threshold
    cb.record_failure()
    assert cb.state == OPEN and not cb.allow()
    t[0] = 9.9
    assert not cb.allow()                        # window not elapsed
    t[0] = 10.0
    assert cb.state == HALF_OPEN
    assert cb.allow()                            # the single probe
    assert not cb.allow()                        # concurrent caller denied
    cb.record_failure()                          # failed probe re-arms
    assert cb.state == OPEN
    t[0] = 20.0
    assert cb.allow()
    cb.record_success()
    assert cb.state == CLOSED and cb.allow()
    cb.record_failure()
    cb.record_success()                          # success resets the streak
    cb.record_failure()
    assert cb.state == CLOSED


def test_health_tracker_mask_and_shards_ok():
    h = HealthTracker(fail_threshold=1, reset_after_s=60.0)
    h.failure(2)
    m = h.mask(4)
    assert m.tolist() == [1, 1, 0, 1]
    assert shards_ok_from_mask(m) == (0, 1, 3)
    assert h.healthy(range(4)) == (0, 1, 3)
    assert h.snapshot()[2] == OPEN


# ---------------------------------------------------- admission control
def test_admission_reject(ds, engine):
    fe = ServingFrontend(engine, policy="local", max_queue=4,
                         overload="reject", max_delay_ms=1.0,
                         mutation_cost=2)
    try:
        _stall_search(fe, ds, 500.0)
        futs = [fe.submit(ds.Q[i:i + 1], SearchParams(k=4))
                for i in range(4)]               # fills the budget exactly
        with pytest.raises(OverloadedError):
            fe.submit(ds.Q[:1], SearchParams(k=4))
        # an over-budget mutation is rejected, never admitted by eviction
        with pytest.raises(OverloadedError):
            fe._enqueue(_Request("add", Future(), payload=(None, None),
                                 t_admit=time.perf_counter(), cost=2))
        assert fe.stats["rejected"] == 2
        for f in futs:                           # admitted work completes
            assert f.result(timeout=60).ids.shape == (1, 4)
    finally:
        fe.close()
    assert fe._cost == 0                         # cost accounting balances


def test_admission_shed_oldest(ds, engine):
    fe = ServingFrontend(engine, policy="local", max_queue=4,
                         overload="shed-oldest", max_delay_ms=1.0,
                         mutation_cost=2)
    try:
        _stall_search(fe, ds, 500.0)
        # least slack: the only request with an explicit deadline
        doomed = fe.submit(ds.Q[:1], SearchParams(k=4, deadline_ms=5_000.0))
        keep = [fe.submit(ds.Q[i:i + 1], SearchParams(k=4))
                for i in range(1, 4)]            # best-effort: inf slack
        newcomer = fe.submit(ds.Q[4:5], SearchParams(k=4))
        with pytest.raises(OverloadedError) as ei:
            doomed.result(timeout=5)
        assert ei.value.queued_us >= 0.0
        assert fe.stats["shed"] == 1
        # a mutation must NOT evict queued searches under shed-oldest
        with pytest.raises(OverloadedError):
            fe._enqueue(_Request("add", Future(), payload=(None, None),
                                 t_admit=time.perf_counter(), cost=2))
        assert fe.stats["rejected"] == 1
        for f in keep + [newcomer]:
            assert f.result(timeout=60).ids.shape == (1, 4)
    finally:
        fe.close()
    assert fe._cost == 0


# -------------------------------------------------- deadline enforcement
def test_deadline_expiry_sheds_queued(ds, engine):
    fe = ServingFrontend(engine, policy="local", max_delay_ms=1.0)
    try:
        fe.search(ds.Q[:1], SearchParams(k=4))   # warm the k=4 bucket
        _stall_search(fe, ds, 300.0)
        doomed = fe.submit(ds.Q[:1], SearchParams(k=4, deadline_ms=50.0))
        ok = fe.submit(ds.Q[1:2], SearchParams(k=4))  # best-effort
        with pytest.raises(DeadlineExceededError) as ei:
            doomed.result(timeout=30)
        assert ei.value.queued_us >= 50e3 * 0.9  # spent >= ~the budget
        assert ei.value.engine_us == 0.0         # never reached the engine
        r = ok.result(timeout=60)
        assert r.ids.shape == (1, 4)             # best-effort never expires
        assert fe.stats["expired"] == 1
    finally:
        fe.close()


# ------------------------------------------------ containment and retry
def test_transient_fault_absorbed_by_retry(ds, engine):
    want = engine.search_request(ds.Q[:2], SearchParams(k=4))
    faults.install("engine:search@1x2", mode="transient")
    fe = ServingFrontend(engine, policy="local", max_delay_ms=1.0,
                         retry_backoff_ms=0.5)
    try:
        r = fe.search(ds.Q[:2], SearchParams(k=4))
        assert r.retries == 2                    # two blips absorbed
        assert fe.stats["retries"] == 2
        assert fe.stats["failures"] == 0
        assert np.array_equal(r.ids, want.ids)
        assert np.array_equal(r.scores, want.scores)
    finally:
        fe.close()


def test_nonretryable_fault_fails_only_its_group(ds, engine):
    faults.install("engine:search@1x1", mode="error")
    fe = ServingFrontend(engine, policy="local", max_delay_ms=1.0)
    try:
        with pytest.raises(InjectedFault):
            fe.search(ds.Q[:1], SearchParams(k=4))
        assert fe.stats["failures"] == 1
        r = fe.search(ds.Q[:1], SearchParams(k=4))   # keeps serving
        assert r.ids.shape == (1, 4) and r.retries == 0
    finally:
        fe.close()


def test_retry_budget_is_bounded(ds, engine):
    faults.install("engine:search", mode="transient")   # permanently down
    fe = ServingFrontend(engine, policy="local", max_delay_ms=1.0,
                         max_retries=1, retry_backoff_ms=0.5)
    try:
        with pytest.raises(InjectedTransientFault):
            fe.search(ds.Q[:1], SearchParams(k=4))
        assert fe.stats["retries"] == 1 and fe.stats["failures"] == 1
        faults.uninstall()
        assert fe.search(ds.Q[:1], SearchParams(k=4)).ids.shape == (1, 4)
    finally:
        fe.close()


def test_mutations_never_retried(ds, engine):
    faults.install("engine:add@1x1", mode="transient")
    fe = ServingFrontend(engine, policy="local", max_delay_ms=1.0)
    try:
        with pytest.raises(InjectedTransientFault):
            fe.add(np.zeros((1, D), np.float32))
        assert fe.stats["retries"] == 0          # retryable, but a write
        assert fe.stats["failures"] == 1
        assert fe.search(ds.Q[:1], SearchParams(k=4)).ids.shape == (1, 4)
    finally:
        fe.close()


# ------------------------------------------- stranded-Future regression
def test_dispatcher_death_strands_no_futures(ds, engine):
    fe = ServingFrontend(engine, policy="local", max_delay_ms=1.0)
    mfut = _stall_mutation(fe, 400.0)
    faults.inject("engine:search@1", mode="raise")   # BaseException
    s1 = fe.submit(ds.Q[:1], SearchParams(k=3))      # dispatched first
    s2 = fe.submit(ds.Q[:1], SearchParams(k=4))      # queued behind it
    assert mfut.result(timeout=30) is not None       # stall add completed
    with pytest.raises(InjectedCrash):
        s1.result(timeout=30)                        # in-flight: the cause
    with pytest.raises(FrontendClosedError):
        s2.result(timeout=30)                        # queued: failed fast
    faults.uninstall()
    with pytest.raises(FrontendClosedError, match="closed"):
        fe.submit(ds.Q[:1], SearchParams(k=3))       # submit is poisoned
    fe.close()                                       # returns promptly
    assert not fe._thread.is_alive()
    assert fe._cost == 0


# ---------------------------------------------------- shutdown ordering
def test_close_during_inflight_mutation(ds, engine):
    fe = ServingFrontend(engine, policy="local", max_delay_ms=1.0)
    mfut = _stall_mutation(fe, 400.0)
    t0 = time.perf_counter()
    fe.close()                                       # mutation in flight
    assert time.perf_counter() - t0 < 30.0
    assert mfut.result(timeout=1) is not None        # the write finished
    assert not fe._thread.is_alive()


def test_close_without_drain_fails_queued_work(ds, engine):
    fe = ServingFrontend(engine, policy="local", max_delay_ms=1.0)
    _stall_search(fe, ds, 400.0)
    queued = [fe.submit(ds.Q[i:i + 1], SearchParams(k=4))
              for i in range(3)]
    fe.close(drain=False)
    for f in queued:
        with pytest.raises(FrontendClosedError):
            f.result(timeout=5)
    with pytest.raises(FrontendClosedError):
        fe.submit(ds.Q[:1], SearchParams(k=4))
    assert fe._cost == 0


def test_concurrent_submits_racing_close(ds, engine):
    fe = ServingFrontend(engine, policy="local", max_delay_ms=1.0)
    fe.search(ds.Q[:1], SearchParams(k=4))           # warm the bucket
    futs, lock = [], threading.Lock()

    def client():
        for i in range(30):
            try:
                f = fe.submit(ds.Q[i % NQ:i % NQ + 1], SearchParams(k=4))
            except FrontendClosedError:
                return
            with lock:
                futs.append(f)

    threads = [threading.Thread(target=client) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.005)
    fe.close()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    # every accepted Future completes — served or failed, never hung
    done = sum(1 for f in futs if f.result(timeout=30).ids.shape == (1, 4))
    assert done == len(futs)


# --------------------------------------------- durability composition
def test_wal_crash_behind_frontend_recovers_bitwise(ds, tmp_path):
    """PR 7 contract through the serving loop: a crash after the WAL
    record is durable ("wal:record") but before apply completes recovers
    to exactly the post-mutation state on reopen."""
    eng = AnnEngine.build(jax.random.PRNGKey(2), ds.X, 16, train_iters=5)
    p, pref = str(tmp_path / "live"), str(tmp_path / "ref")
    eng.save(p)
    eng.save(pref)
    add = np.linspace(-1, 1, 3 * D, dtype=np.float32).reshape(3, D)
    fe = ServingFrontend(AnnEngine.open(p, wal=True), policy="local",
                         max_delay_ms=1.0)
    fe.search(ds.Q[:2], SearchParams(k=5))
    faults.install("wal:record")
    with pytest.raises(InjectedCrash):
        fe.add(add)                              # crash mid-mutation
    faults.uninstall()
    with pytest.raises(FrontendClosedError):
        fe.submit(ds.Q[:1], SearchParams(k=5))   # front-end is dead
    fe.close()
    ref = AnnEngine.open(pref)                   # the committed state:
    ref.add(add)                                 # snapshot + the logged add
    want = ref.search(ds.Q, k=5)
    got = AnnEngine.open(p).search(ds.Q, k=5)    # WAL replay on open
    assert np.array_equal(got[0], want[0])
    assert np.array_equal(got[1], want[1])


# ------------------------------------------------- degraded fan-out
SCRIPT_HEALTH = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core.distributed import build_sharded_ivf, make_distributed_search
from repro.data.vectors import make_manifold
from repro.serve.health import HealthTracker, shards_ok_from_mask

ds = make_manifold(jax.random.PRNGKey(0), n=8_000, d=16, nq=16,
                   intrinsic_dim=8)
mesh = jax.make_mesh((8,), ("data",))
sharded = build_sharded_ivf(jax.random.PRNGKey(1), ds.X, n_shards=8,
                            n_partitions=16, spill_mode="soar",
                            train_iters=3)
plain = make_distributed_search(mesh, ("data",), top_t=8, final_k=10)
degr = make_distributed_search(mesh, ("data",), top_t=8, final_k=10,
                               with_health=True)
with jax.set_mesh(mesh):
    ids0, sc0 = jax.jit(plain)(sharded, jnp.asarray(ds.Q))
    ones = jnp.ones((8,), jnp.uint8)
    ids1, sc1 = jax.jit(degr)(sharded, jnp.asarray(ds.Q), ones)
    assert np.array_equal(np.asarray(ids0), np.asarray(ids1)), "healthy != plain"
    assert np.array_equal(np.asarray(sc0), np.asarray(sc1))
    h = HealthTracker(fail_threshold=1)
    h.failure(3)                        # shard 3 down
    mask = h.mask(8)
    assert shards_ok_from_mask(mask) == (0, 1, 2, 4, 5, 6, 7)
    ids2, sc2 = jax.jit(degr)(sharded, jnp.asarray(ds.Q), jnp.asarray(mask))
ids0, ids2 = np.asarray(ids0), np.asarray(ids2)
per = 8_000 // 8
lo, hi = 3 * per, 4 * per
assert ids2.min() >= 0                  # partial results, never sentinels
assert not ((ids2 >= lo) & (ids2 < hi)).any(), "dead shard leaked results"
# healthy shards' global answers all survive into the degraded top-k
keep = ~((ids0 >= lo) & (ids0 < hi))
for q in range(ids0.shape[0]):
    assert set(ids0[q][keep[q]].tolist()) <= set(ids2[q].tolist()), q
print("OK")
"""


SCRIPT_REPLICA_DEGRADED = r"""
import os, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro import faults
from repro.data.vectors import make_manifold
from repro.serve.api import SearchParams
from repro.serve.engine import AnnEngine
from repro.serve.frontend import ServingFrontend

ds = make_manifold(jax.random.PRNGKey(0), n=2_000, d=16, nq=16,
                   intrinsic_dim=8)
eng = AnnEngine.build(jax.random.PRNGKey(1), ds.X, 16, train_iters=5)
solo_ids, solo_sc = eng.search(ds.Q, k=6)
fe = ServingFrontend(eng, policy="replica", breaker_threshold=2,
                     breaker_reset_s=0.5)
plan = faults.install("replica:dispatch", mode="error")  # replicas down
r1 = fe.search(ds.Q, SearchParams(k=6))
assert r1.degraded, "fallback must be flagged"
assert np.array_equal(r1.ids, solo_ids)        # full-coverage local serve
assert np.array_equal(r1.scores, solo_sc)
r2 = fe.search(ds.Q, SearchParams(k=6))        # second failure trips it
assert r2.degraded and fe.health.state("replica") == "open"
r3 = fe.search(ds.Q, SearchParams(k=6))        # breaker open: no attempt
assert r3.degraded and plan._hit_count == 2
assert np.array_equal(r3.ids, solo_ids)
assert fe.stats["degraded"] == 3
assert fe.stats["replica_dispatches"] == 0
faults.uninstall()
time.sleep(0.6)                                # reset window elapses
r4 = fe.search(ds.Q, SearchParams(k=6))        # half-open probe heals it
assert not r4.degraded
assert fe.health.state("replica") == "closed"
assert fe.stats["replica_dispatches"] == 1
assert np.array_equal(r4.ids, solo_ids)        # replica path stays bitwise
fe.close()
print("OK")
"""


def _run(script):
    r = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "OK" in r.stdout


def test_degraded_shard_fanout_multidevice():
    _run(SCRIPT_HEALTH)


def test_replica_breaker_fallback_multidevice():
    _run(SCRIPT_REPLICA_DEGRADED)
