"""Static contract analyzer self-tests (ISSUE 10, DESIGN.md §3.14).

Every detector must catch its synthetic violation class AND pass the
clean equivalent: O(n) jaxpr intermediate, f64 leak, host-callback
primitive, jit-cache growth, unlocked `_locked` call, int falsy-default,
np.random global state, pickle in ckpt/, unvalidated engine edge — plus
the ratchet-baseline workflow and the CLI exit codes.
"""
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.check import main as check_main
from repro.analysis.contracts import (TraceSpec, check_contract,
                                      jaxpr_contract)
from repro.analysis.findings import (Finding, load_baseline,
                                     partition_findings, save_baseline)
from repro.analysis.jaxpr_walk import (jaxpr_primitives, jaxpr_shapes)
from repro.analysis.lint_ast import lint_source
from repro.analysis.sentinel import CacheWatch

N = 257  # prime, as in the real contracts


def _contract(build, **kw):
    """Register `build` in a throwaway registry, return its findings."""
    reg = {}
    jaxpr_contract("probe", registry=reg, **kw)(build)
    return check_contract(reg["probe"])


# ------------------------------------------------------------ jaxpr walker

def test_walker_matches_legacy_helper_semantics():
    def f(x):
        return jax.lax.scan(lambda c, xi: (c + xi.sum(), xi * 2.0),
                            0.0, x)
    closed = jax.make_jaxpr(f)(jnp.zeros((4, 3)))
    shapes = jaxpr_shapes(closed.jaxpr)
    assert (4, 3) in shapes          # scan-stacked ys, found recursively
    assert () in shapes              # carry


def test_walker_recurses_cond_branches():
    """The legacy copy-pasted helpers missed `branches` tuples — the
    shared walker must see inside lax.cond."""
    def f(x):
        return jax.lax.cond(x.sum() > 0,
                            lambda v: jnp.outer(v, v).sum(),
                            lambda v: v.sum(), x)
    closed = jax.make_jaxpr(f)(jnp.zeros(9))
    assert (9, 9) in jaxpr_shapes(closed.jaxpr)


# ------------------------------------------------------- contract checker

def test_o_n_intermediate_caught():
    def build():
        X = jnp.zeros((N, 8))
        return TraceSpec(fn=lambda x: (x @ x.T).sum(axis=0), args=(X,),
                         dims={"n": N})
    found = _contract(build, no_dims={"n"})
    assert any(f.rule == "jaxpr-dim" for f in found)


def test_candidate_local_equivalent_passes():
    def build():
        X = jnp.zeros((N, 8))
        # candidate-local: only a gathered window ever materializes
        return TraceSpec(
            fn=lambda x: x[:16].sum(axis=1), args=(X,), dims={"n": N})
    assert _contract(build, no_dims={"n"}) == []


def test_leading_n_view_allowed_but_trailing_n_flagged():
    def view(x):
        return (x * 2.0).sum()        # (n, d) elementwise view: legal
    def gram(x):
        return (x.T @ x @ x.T).sum(axis=0)   # (d, n): n trails — illegal
    X = jnp.zeros((N, 4))
    ok = _contract(lambda: TraceSpec(fn=view, args=(X,), dims={"n": N}),
                   no_dims={"n"})
    bad = _contract(lambda: TraceSpec(fn=gram, args=(X,), dims={"n": N}),
                    no_dims={"n"})
    assert ok == [] and any(f.rule == "jaxpr-dim" for f in bad)


def test_f64_leak_caught_and_f32_passes():
    X = jnp.zeros((8, 4), jnp.float32)
    with jax.enable_x64(True):
        bad = _contract(lambda: TraceSpec(
            fn=lambda x: x.astype(jnp.float64).sum(), args=(X,), dims={}))
    ok = _contract(lambda: TraceSpec(
        fn=lambda x: (x * 2.0).sum(), args=(X,), dims={}))
    assert any(f.rule == "jaxpr-dtype" for f in bad)
    assert ok == []


def test_host_callback_primitive_caught():
    def noisy(x):
        jax.debug.print("x={x}", x=x.sum())
        return x * 2.0
    X = jnp.zeros(4)
    found = _contract(lambda: TraceSpec(fn=noisy, args=(X,), dims={}))
    assert any(f.rule == "jaxpr-callback" for f in found)
    closed = jax.make_jaxpr(noisy)(X)
    # jax.debug.print binds its own primitive, which the contract must list
    assert "debug_print" in jaxpr_primitives(closed.jaxpr)


def test_cache_growth_contract_caught_and_stable_passes():
    @jax.jit
    def toy(x):
        return (x * 2.0).sum()

    calls = {"n": 0}

    def storm():
        calls["n"] += 1
        toy(jnp.zeros(calls["n"]))   # fresh shape every call → recompiles

    bad = _contract(lambda: TraceSpec(
        fn=lambda x: x.sum(), args=(jnp.zeros(3),), dims={},
        jit_fn=toy, call=storm))
    ok = _contract(lambda: TraceSpec(
        fn=lambda x: x.sum(), args=(jnp.zeros(3),), dims={},
        jit_fn=toy, call=lambda: toy(jnp.zeros(7))))
    assert any(f.rule == "cache-growth" for f in bad)
    assert not any(f.rule == "cache-growth" for f in ok)


# ---------------------------------------------------- recompile sentinel

def test_cache_watch_flags_recompile_storm():
    @jax.jit
    def toy(x):
        return x + 1.0

    toy(jnp.zeros(1))
    with pytest.raises(AssertionError, match="cache grew"):
        with CacheWatch(toy):
            for nq in range(2, 6):     # per-shape traces: the storm
                toy(jnp.zeros(nq))


def test_cache_watch_passes_bucketed_traffic():
    @jax.jit
    def toy(x):
        return x + 1.0

    toy(jnp.zeros(8))                  # warm the single bucket
    with CacheWatch(toy):
        for _ in range(5):
            toy(jnp.zeros(8))


# ------------------------------------------------------------- AST lints

SERVE = "src/repro/serve/_synthetic.py"
CORE = "src/repro/core/_synthetic.py"
CKPT = "src/repro/ckpt/_synthetic.py"


def _rules(src, relpath):
    return {f.rule for f in lint_source(textwrap.dedent(src), relpath)}


def test_unlocked_call_caught_and_locked_passes():
    bad = """\
        class F:
            def poll(self):
                self._expire_locked()
    """
    ok = """\
        class F:
            def poll(self):
                with self._cond:
                    self._expire_locked()

            def _admit_locked(self):
                self._expire_locked()   # caller holds the lock
    """
    assert "lock-discipline" in _rules(bad, SERVE)
    assert "lock-discipline" not in _rules(ok, SERVE)


def test_falsy_int_default_caught_and_sentinel_passes():
    assert "falsy-int-default" in _rules(
        "def f(self, top_t=None):\n    return top_t or self.top_t\n", CORE)
    assert "falsy-int-default" in _rules(
        "def f(c=None, n=0):\n    return c or max(4, n // 256)\n", CORE)
    assert "falsy-int-default" not in _rules(
        "def f(self, top_t=None):\n"
        "    return self.top_t if top_t is None else top_t\n", CORE)
    # string coalescing is NOT the int bug class
    assert "falsy-int-default" not in _rules(
        "def f(name=None):\n    return name or 'default'\n", CORE)


def test_np_random_global_caught_and_generator_passes():
    assert "np-random-global" in _rules(
        "import numpy as np\nx = np.random.randint(0, 4)\n", CORE)
    assert "np-random-global" not in _rules(
        "import numpy as np\nrng = np.random.default_rng(0)\n", CORE)


def test_pickle_in_ckpt_caught():
    assert "pickle-ckpt" in _rules("import pickle\n", CKPT)
    assert "pickle-ckpt" in _rules(
        "import numpy as np\nx = np.load('f.npy', allow_pickle=True)\n",
        CKPT)
    # pickle outside the durability layer is some other module's business
    assert "pickle-ckpt" not in _rules("import pickle\n", CORE)


def test_validate_routing_transitive_and_missing():
    ok = """\
        class Engine:
            def search(self, Q):
                return self.search_request(Q)

            def search_request(self, Q, params=None):
                p = (params or SearchParams()).validate()
                return p
    """
    bad = """\
        class Engine:
            def search(self, Q, k=10):
                return self._go(Q, k)

            def _go(self, Q, k):
                return Q[:k]
    """
    assert "validate-routing" not in _rules(ok, SERVE)
    assert "validate-routing" in _rules(bad, SERVE)


# ------------------------------------------------------- ratchet baseline

def test_baseline_grandfathers_by_fingerprint(tmp_path):
    f_old = Finding("falsy-int-default", "src/repro/x.py", "m", line=10,
                    context="f", snippet="a or 1")
    f_new = Finding("falsy-int-default", "src/repro/x.py", "m", line=20,
                    context="g", snippet="b or 2")
    path = str(tmp_path / "baseline.json")
    save_baseline([f_old], path)
    bl = load_baseline(path)
    new, old = partition_findings([f_old, f_new], bl)
    assert old == [f_old] and new == [f_new]
    # line drift does not resurrect a grandfathered finding
    moved = Finding("falsy-int-default", "src/repro/x.py", "m", line=99,
                    context="f", snippet="a or 1")
    assert moved in bl


def test_empty_baseline_blocks_everything(tmp_path):
    bl = load_baseline(str(tmp_path / "missing.json"))
    f = Finding("lock-discipline", "src/repro/serve/x.py", "m")
    new, old = partition_findings([f], bl)
    assert new == [f] and old == []


# -------------------------------------------------------------------- CLI

def test_cli_lint_pass_clean_on_repo():
    assert check_main(["--only", "lint", "-q"]) == 0


@pytest.mark.parametrize("cls", ["o-n-intermediate", "f64-leak",
                                 "cache-growth", "unlocked-call",
                                 "falsy-default"])
def test_cli_injected_violations_exit_nonzero(cls):
    assert check_main(["--only", "lint", "--inject", cls, "-q"]) != 0


def test_cli_one_real_contract_runs_clean():
    # lloyd_sweep: the cheapest registered contract (no index build)
    from repro.analysis.contracts import REGISTRY
    assert check_contract(REGISTRY["lloyd_sweep"]) == []
