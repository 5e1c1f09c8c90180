"""Spans inside the serving path (DESIGN.md §3.15).

1. Host spans: a tiny ServingFrontend runs under the JAX profiler; its
   trace, read back with the benchmark's own reduction
   (benchmarks/chip/tracefile.py), holds one `frontend.dispatch` span per
   dispatch the frontend counted, each enclosing the six step spans once,
   in the order the dispatcher runs them. A replica dispatch on four
   devices encloses `replica.scatter`, `replica.launch` and
   `replica.gather` instead of the engine's steps, and `replica.place`
   where it places a new pack, with the bytes it copied.
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
def test_host_spans_nest_in_each_dispatch_in_order(ds, engine, tmp_path):
    """Concurrent single-query clients (coalesced dispatches) and one
    request larger than a batch, all inside the traced window; the
    frontend is closed inside it, so every dispatch span has ended."""
    fe = ServingFrontend(engine, policy="local", default_deadline_ms=20.0)
    for nq in (1, 2, 4, 8, 16):            # every bucket compiles before
        fe.search(ds.Q[:nq])               # the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracefile.WINDOW):
            stats0 = dict(fe.stats)
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
            stats1 = dict(fe.stats)
    finally:
        fe.close()
        jax.profiler.stop_trace()
    tr = tracefile.load(str(tmp_path))

    n = stats1["dispatches"] - stats0["dispatches"]
    assert n >= 2
    assert sum(e.name == spans.DISPATCH for e in tr.host) == n
    got = spans.dispatches(tr)
    assert len(got) == n
    for d in got:
        assert set(d.steps) == set(spans.STEPS)
        assert all(len(v) == 1 for v in d.steps.values())
        seq = sorted((v[0] for v in d.steps.values()), key=lambda e: e.start)
        assert tuple(e.name for e in seq) == spans.STEPS
        assert d.span.start <= seq[0].start
        assert all(a.end <= b.start for a, b in zip(seq, seq[1:]))
        assert seq[-1].end <= d.span.end


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
    steps = ("replica.place", "replica.scatter", "replica.launch",
             "replica.gather")
    for d, placed in zip(got, (True, False)):
        assert not d.steps.keys() & {"engine.prepare", "engine.upload",
                                     "engine.launch", "engine.download"}
        inner = sorted((e for e in tr.host if e.name in steps
                        and d.span.start <= e.start
                        and e.end <= d.span.end), key=lambda e: e.start)
        names = tuple(e.name for e in inner)
        assert names == steps[0 if placed else 1:], names
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
    ("sharded", ("merge",)),
])
def test_named_stages_reach_the_compiled_hlo(ds, engine, case, expect):
    Q = jnp.asarray(ds.Q[:16])
    if case == "sharded":
        from repro.core.distributed import (build_sharded_ivf_pq,
                                            make_distributed_search_pq)
        mesh = jax.make_mesh((1,), ("data",))
        sharded = build_sharded_ivf_pq(jax.random.PRNGKey(1), ds.X,
                                       n_shards=1, n_partitions=16,
                                       pq_subspaces=8, train_iters=3)
        fn = make_distributed_search_pq(mesh, ("data",), top_t=4, final_k=5,
                                        rerank_k=32, q_chunk=16)
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
