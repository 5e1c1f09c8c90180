"""A run's `correct` on the CPU at a tiny size: it holds for the sound
program and comes out false with the timed path broken underneath, once
for each fault a cell can have. The harness's look for a chip is skipped
(`require_tpu=False`); the rest of a run is driven as on the chip."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import harness  # noqa: E402

TINY = os.path.join(os.path.dirname(__file__), "fixtures", "tiny.json")


def tiny_cell(traffic: str, chips: int = 1):
    """The tiny configuration under one of the benchmark's traffic mixes,
    as a cell of its own (metrics that list cells are not read)."""
    with open(TINY) as f:
        t = json.load(f)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    w = {"name": f"tiny.{traffic}", "config": "tiny", "traffic": traffic,
         "chips": chips, "why": "tiny"}
    return bench, w, t["config"], t["traffic"][traffic]


@pytest.fixture
def run_tiny(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")

    def run(traffic):
        monkeypatch.setattr(harness, "load_cell",
                            lambda name: tiny_cell(traffic))
        result, checks = harness.run_cell(f"tiny.{traffic}", 2**31 + 77, 1.5,
                                          False, 0.0, require_tpu=False)
        return result, checks
    return run


def test_sound_program_is_correct(run_tiny):
    result, checks = run_tiny("online")
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 100
    assert list(result)[-1] == "checks"


def test_half_of_each_batch_left_out_is_not_correct(run_tiny, monkeypatch):
    import numpy as np
    from repro.serve.engine import AnnEngine
    real = AnnEngine.search_request

    def half(self, Q, params=None, **kw):
        r = real(self, Q[:max(len(Q) // 2, 1)], params, **kw)
        pad = len(Q) - len(r.ids)
        r.ids = np.concatenate([r.ids, np.full((pad, r.ids.shape[1]), -1,
                                               r.ids.dtype)])
        r.scores = np.concatenate([r.scores, np.full(
            (pad, r.ids.shape[1]), -np.inf, r.scores.dtype)])
        return r
    monkeypatch.setattr(AnnEngine, "search_request", half)
    result, checks = run_tiny("online")
    assert not result["correct"]
    assert checks["score_gap"]["value"] > checks["score_gap"]["limit"]
    json.dumps(result, allow_nan=False)


def test_an_answer_altered_where_it_is_produced_is_not_correct(
        run_tiny, monkeypatch):
    from repro.core import search
    real = search.search_jit_batched

    def altered(*a, **kw):
        ids, vals = real(*a, **kw)
        return ids.at[0, 0].set((ids[0, 0] + 1) % 6000), vals
    monkeypatch.setattr(search, "search_jit_batched", altered)
    result, checks = run_tiny("online")
    assert not result["correct"]
    assert checks["score_gap"]["value"] > checks["score_gap"]["limit"]


FOUR = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "src")]
sys.path.insert(0, os.path.dirname(sys.argv[2]))
from benchmarks.chip import harness
import test_bench_faults as t
harness.enable_compile_cache = lambda: "off"
harness.load_cell = lambda name: t.tiny_cell("bulk1024", chips=4)
if sys.argv[3] == "fault":
    import jax.numpy as jnp
    from repro.core import distributed
    real = distributed.make_replicated_search
    def only_first(mesh, *a, **kw):
        fn = real(mesh, *a, **kw)
        def run(*args):
            ids, vals = fn(*args)
            keep = ids.shape[0] // mesh.size
            return (ids.at[keep:].set(-1), vals.at[keep:].set(-jnp.inf))
        return run
    distributed.make_replicated_search = only_first
res, checks = harness.run_cell("tiny.bulk1024", 5, 1.5, False, 0.0,
                               require_tpu=False)
print("RESULT " + json.dumps({"correct": res["correct"],
                              "count": res["device"]["count"]}))
"""


@pytest.mark.parametrize("mode,want", [("sound", True), ("fault", False)])
def test_four_replicas_without_the_exchange_are_not_correct(mode, want):
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "JAX_PLATFORMS": "cpu", "HOME": os.environ.get("HOME", "/tmp")}
    r = subprocess.run([sys.executable, "-c", FOUR, ROOT, __file__, mode],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    line = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out == {"correct": want, "count": 4}
    assert "replica_dispatches" in r.stdout
