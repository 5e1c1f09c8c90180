"""The readers of the frontend's pipelining, `launch_ahead_share.online`,
`launch_ahead_share.bulk` and `complete_ms.online`: a number from a tiny
frontend traced on the CPU by the harness's own Tracer, and nothing where
the program has no `launched_ahead` counter or `frontend.complete` span."""
import os
import sys
import threading
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import harness, tracefile as tf  # noqa: E402

READERS = ("launch_ahead_share.online", "launch_ahead_share.bulk",
           "complete_ms.online")
SECONDS = 2.0


def read(name, ctx):
    return harness.plugin("metrics", name).read(ctx)


@pytest.fixture(scope="module")
def traced():
    """Four clients of single queries, each under a coalescing key of its
    own, so that groups ripen while others are in flight."""
    import jax
    from repro.data.vectors import make_manifold
    from repro.serve.api import SearchParams
    from repro.serve.engine import AnnEngine
    from repro.serve.frontend import ServingFrontend
    ds = make_manifold(jax.random.PRNGKey(0), n=3_000, d=24, nq=32,
                       intrinsic_dim=8)
    eng = AnnEngine.build(jax.random.PRNGKey(1), ds.X, 16, train_iters=5,
                          bq=16)
    fe = ServingFrontend(eng, policy="local", max_delay_ms=1.0)
    params = [SearchParams(k=4 + c) for c in range(4)]
    for p in params:
        fe.search(ds.Q[:1], p)
    tracer = harness.Tracer(fe, SECONDS)
    t0 = time.perf_counter()
    tracer.on_start(t0)

    def client(c):
        i = 0
        while time.perf_counter() < t0 + SECONDS:
            fe.search(ds.Q[i % 32:i % 32 + 1], params[c])
            i += 1

    threads = [threading.Thread(target=client, args=(c,)) for c in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    try:
        tr = tracer.finish()
    finally:
        fe.close()
    return types.SimpleNamespace(trace=tr, tracer=tracer)


def test_readers_read_a_traced_cpu_frontend(traced):
    n = traced.tracer.stats1["dispatches"] - traced.tracer.stats0["dispatches"]
    assert n > 0
    ahead = [read(name, traced) for name in READERS[:2]]
    assert ahead[0] == ahead[1] and 0.0 <= ahead[0] <= 100.0
    assert read("complete_ms.online", traced) > 0.0


def test_a_program_without_the_counter_or_the_span_reads_nothing(traced):
    parent = types.SimpleNamespace(
        stats0={k: v for k, v in traced.tracer.stats0.items()
                if k != "launched_ahead"},
        stats1={k: v for k, v in traced.tracer.stats1.items()
                if k != "launched_ahead"})
    host = [e for e in traced.trace.host if e.name != "frontend.complete"]
    bare = tf.Trace(traced.trace.ops, traced.trace.modules, host,
                    traced.trace.window)
    ctx = types.SimpleNamespace(trace=bare, tracer=parent)
    for name in READERS:
        assert read(name, ctx) is None, name
        assert read(name, types.SimpleNamespace(trace=None,
                                                tracer=None)) is None
