"""Spans inside the serving path (DESIGN.md §3.15).

1. Host spans: a tiny ServingFrontend runs under the JAX profiler; its
   trace, read back with the benchmark's own reduction
   (benchmarks/chip/tracefile.py), holds one `frontend.dispatch` span
   (the launch side) and one `frontend.complete` span (the collect side)
   per dispatch the frontend counted. Each dispatch span encloses its
   four launch steps once, in order, and each complete span its two
   collect steps; a batch is collected after its launch, and the two
   kinds of span never overlap. With two batches in flight the second
   dispatch span opens before the first batch's complete span. A replica
   dispatch on four devices encloses `replica.scatter` and
   `replica.launch` instead of the engine's steps, and `replica.place`
   where it places a new pack, with the bytes it copied; its complete
   span encloses `replica.gather`.
2. Device stages: every stage of the search program carries its
   `jax.named_scope` into the compiled HLO's `op_name` metadata, which is
   what a device trace reports as each operation's `tf_op`.
"""
import json
import os
import re
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT]

from benchmarks.chip import spans, tracefile  # noqa: E402
from repro.core.search import search_jit_batched  # noqa: E402
from repro.data.vectors import make_manifold  # noqa: E402
from repro.serve.api import SearchParams  # noqa: E402
from repro.serve.engine import AnnEngine  # noqa: E402
from repro.serve.frontend import ServingFrontend  # noqa: E402

N, D, NQ = 3_000, 24, 32
STAGES = ("probe", "gather", "lut", "score", "dedup", "rerank")


@pytest.fixture(scope="module")
def ds():
    return make_manifold(jax.random.PRNGKey(0), n=N, d=D, nq=NQ,
                         intrinsic_dim=8)


@pytest.fixture(scope="module")
def engine(ds):
    return AnnEngine.build(jax.random.PRNGKey(1), ds.X, 16,
                           spill_mode="soar", pq_subspaces=8, train_iters=5,
                           rerank_budget=32, bq=16)


# ------------------------------------------------------------ host spans
COMPLETE = "frontend.complete"
LAUNCH_STEPS = ("frontend.coalesce", "engine.prepare", "engine.upload",
                "engine.launch")
COLLECT_STEPS = ("engine.download", "frontend.resolve")


def _enclosed(tr, outer: str, names) -> list:
    """Each `outer` span of the trace, in time order, with the spans named
    in `names` that it encloses, in time order."""
    def by_start(evs):
        return sorted(evs, key=lambda e: e.start)
    return [(o, by_start(e for e in tr.host if e.name in names
                         and o.start <= e.start and e.end <= o.end))
            for o in by_start(e for e in tr.host if e.name == outer)]


def _check_launch_and_collect(tr, n, launch_steps, collect_steps):
    """n dispatch and n complete spans; each holds its steps once, in
    order; the i-th batch is collected after its launch; no two of these
    spans overlap (one dispatcher thread opens them all)."""
    launches = _enclosed(tr, spans.DISPATCH, launch_steps + collect_steps)
    collects = _enclosed(tr, COMPLETE, launch_steps + collect_steps)
    assert len(launches) == len(collects) == n
    for outer, inner in launches + collects:
        assert all(a.end <= b.start for a, b in zip(inner, inner[1:]))
    assert all(tuple(e.name for e in inner) == launch_steps
               for _, inner in launches)
    assert all(tuple(e.name for e in inner) == collect_steps
               for _, inner in collects)
    for (d, _), (c, _) in zip(launches, collects):
        assert d.end <= c.start
    both = sorted([o for o, _ in launches + collects], key=lambda e: e.start)
    assert all(a.end <= b.start for a, b in zip(both, both[1:]))
    return [d for d, _ in launches], [c for c, _ in collects]


def _traced(tmp_path, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracefile.WINDOW):
            body()
    finally:
        jax.profiler.stop_trace()
    return tracefile.load(str(tmp_path))


def test_host_spans_nest_in_each_dispatch_in_order(ds, engine, tmp_path):
    """Concurrent single-query clients (coalesced dispatches) and one
    request larger than a batch, all inside the traced window; the
    frontend is closed inside it, so every span has ended."""
    fe = ServingFrontend(engine, policy="local", default_deadline_ms=20.0)
    for nq in (1, 2, 4, 8, 16):            # every bucket compiles before
        fe.search(ds.Q[:nq])               # the trace
    stats = []

    def body():
        stats.append(dict(fe.stats))
        threads = [threading.Thread(
            target=fe.search, args=(ds.Q[i:i + 1], SearchParams(k=5)))
            for i in range(NQ)]
        for t in threads:
            t.start()
        fe.search(ds.Q)
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        fe.close()
        stats.append(dict(fe.stats))
    try:
        tr = _traced(tmp_path, body)
    finally:
        fe.close()

    n = stats[1]["dispatches"] - stats[0]["dispatches"]
    assert n >= 2
    _check_launch_and_collect(tr, n, LAUNCH_STEPS, COLLECT_STEPS)
    got = spans.dispatches(tr)             # the benchmark's reduction
    assert len(got) == n
    for d in got:
        assert set(d.steps) == set(LAUNCH_STEPS)


def test_a_second_dispatch_opens_before_the_first_completes(
        ds, engine, held, tmp_path):
    """Two groups of different keys, the first held on the device until
    the second is launched: the trace shows the launch ahead."""
    fe = ServingFrontend(engine, policy="local", max_delay_ms=1.0)
    held.release(2)                        # the two warm-up searches
    for k in (5, 6):
        fe.search(ds.Q[:2], SearchParams(k=k))

    def body():
        futs = [fe.submit(ds.Q[:2], SearchParams(k=k)) for k in (5, 6)]
        t0 = time.perf_counter()
        while len(fe._inflight) < 2:
            assert time.perf_counter() - t0 < 30
            time.sleep(0.001)
        held.release(2)
        for f in futs:
            f.result(timeout=30)
        fe.close()
    try:
        tr = _traced(tmp_path, body)
    finally:
        fe.close()
    assert fe.stats["launched_ahead"] == 1
    (d1, d2), (c1, c2) = _check_launch_and_collect(
        tr, 2, LAUNCH_STEPS, COLLECT_STEPS)
    assert d2.start < c1.start


SCRIPT_REPLICA_TRACE = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from benchmarks.chip import tracefile
from repro.data.vectors import make_manifold
from repro.serve.engine import AnnEngine
from repro.serve.frontend import ServingFrontend

ds = make_manifold(jax.random.PRNGKey(0), n=3_000, d=24, nq=32,
                   intrinsic_dim=8)
eng = AnnEngine.build(jax.random.PRNGKey(1), ds.X, 16, train_iters=5)
fe = ServingFrontend(eng, policy="replica", default_deadline_ms=200.0)
fe.search(ds.Q)                         # compiles and places, untraced
before = dict(fe.stats)
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
jax.profiler.start_trace(sys.argv[1], profiler_options=opts)
with jax.profiler.TraceAnnotation(tracefile.WINDOW):
    fe.add(ds.X[:64] * 0.5)             # a new pack: placed again
    fe.search(ds.Q)
    fe.search(ds.Q)
    fe.close()
jax.profiler.stop_trace()
size = sum(x.nbytes for x in jax.tree_util.tree_leaves(eng.index.pack()))
print(json.dumps({"before": before, "stats": fe.stats, "pack_bytes": size}))
"""


def test_replica_spans_nest_in_each_dispatch(tmp_path):
    from jax.profiler import ProfileData
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT_REPLICA_TRACE, str(tmp_path)],
        capture_output=True, text=True, cwd=ROOT, timeout=600,
        env={"PYTHONPATH": "src:.", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    out = json.loads(r.stdout.splitlines()[-1])
    assert out["stats"]["replica_dispatches"] == 3
    assert out["stats"]["replica_placements"] == 2
    tr = tracefile.load(str(tmp_path))
    got = spans.dispatches(tr)
    assert len(got) == 2
    for d in got:
        assert not d.steps.keys() & {"engine.prepare", "engine.upload",
                                     "engine.launch", "engine.download"}
    steps = ("replica.place", "replica.scatter", "replica.launch")
    collect = ("replica.gather", "frontend.resolve")
    _check_launch_and_collect(tr, 2, steps[1:], collect)
    launches = _enclosed(tr, spans.DISPATCH, steps)
    assert [tuple(e.name for e in inner) for _, inner in launches] \
        == [steps, steps[1:]]
    for _, inner in launches:
        assert all(a.end <= b.start for a, b in zip(inner, inner[1:]))
    (path,) = [os.path.join(dp, f) for dp, _, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    placed_bytes = [dict(e.stats).get("bytes")
                    for p in ProfileData.from_file(path).planes
                    for line in p.lines for e in line.events
                    if e.name == "replica.place"]
    assert placed_bytes == [3 * out["pack_bytes"]]
    assert placed_bytes[0] == (out["stats"]["replica_placed_bytes"]
                               - out["before"]["replica_placed_bytes"])


# ---------------------------------------------------------- device stages
def _scope_names(hlo: str) -> set:
    """Scopes on the op_name paths; the last part names the primitive
    (`gather`, `top_k`), not a scope."""
    return {part for path in re.findall(r'op_name="([^"]*)"', hlo)
            for part in path.split("/")[:-1]}


@pytest.mark.parametrize("case, expect", [
    ("pq", STAGES),
    ("pq_filtered", STAGES + ("escalate",)),
    ("exact", ("probe", "gather", "score", "dedup")),
    ("sharded", STAGES + ("merge",)),
])
def test_named_stages_reach_the_compiled_hlo(ds, engine, case, expect):
    Q = jnp.asarray(ds.Q[:16])
    if case == "sharded":
        from repro.core.distributed import (build_sharded_ivf,
                                            make_distributed_search)
        mesh = jax.make_mesh((1,), ("data",))
        sharded = build_sharded_ivf(jax.random.PRNGKey(1), ds.X, n_shards=1,
                                    n_partitions=16, pq_subspaces=8,
                                    train_iters=3)
        fn = make_distributed_search(mesh, ("data",), top_t=4, final_k=5,
                                     rerank_budget=32, bq=16)
        with jax.set_mesh(mesh):
            hlo = jax.jit(fn).lower(sharded, Q).compile().as_text()
    else:
        packed = engine.index.pack()
        if case == "exact":                  # no PQ stage: exact window
            packed = packed._replace(part_codes=None, part_codes2=None)
        filt = (jnp.asarray(np.ones(N, np.uint8)) if case == "pq_filtered"
                else None)
        hlo = search_jit_batched.lower(
            packed, Q, top_t=4, final_k=5, rerank_budget=32, bq=16,
            filter=filt, escalate=True).compile().as_text()
    names = _scope_names(hlo)
    assert not set(expect) - names, sorted(names)
