"""Declarative jaxpr contracts for every jit entry point (DESIGN.md §3.14).

Each contract is a trace-spec builder decorated with `@jaxpr_contract`:
the builder constructs a tiny-but-representative workload (index, queries,
codebooks) and returns a `TraceSpec`; the checker traces it with
`jax.make_jaxpr`, walks the jaxpr (analysis/jaxpr_walk.py) and enforces:

  no_dims={"n"}       no equation output is (n,)-shaped or carries n in a
                      non-leading axis — the SOAR candidate-local invariant
                      (no per-query intermediate scales with the database;
                      a leading-n axis is allowed: build-path ops stream
                      over all points by design, e.g. (n, d) input views).
  no_dims_1d={"n"}    only 1-D (n,) outputs are forbidden — the Lloyd
                      "no second-pass vector" rule.
  no_products={"n*c"} no output's element count reaches the named dims'
                      product — the "nothing dense in (points × centroids)"
                      build-path rule.
  forbid_dtypes       no output aval carries the dtype (f64 leak guard —
                      load-bearing under JAX_ENABLE_X64 hosts).
  forbid_primitives   no host-callback / debug primitives in the trace
                      (they would stall the serving pipeline on a host
                      round-trip).
  max_cache_growth=0  re-invoking the entry point with the same-bucket
                      concrete args adds no jit cache entries.

Trace sizes are deliberately prime (N_TRACE=3001) so a forbidden dim can't
collide with a legitimate product of small axes.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.analysis.findings import Finding
from repro.analysis.jaxpr_walk import (jaxpr_outvals,  # noqa: F401
                                       jaxpr_primitives, jaxpr_shapes)

# Primitives that bounce through the host mid-trace. None may appear in a
# serving or build trace: a host round-trip inside a jit region serializes
# the pipeline behind Python.
HOST_CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "outside_call", "host_callback_call", "callback",
})

# Shared tiny-fixture scale. N_TRACE and C_TRACE sized so contract checks
# run in seconds; N_TRACE prime so no product of smaller axes equals it.
N_TRACE, D_TRACE, C_TRACE = 3001, 16, 24
NQ_TRACE, TOP_T, FINAL_K = 5, 6, 5


@dataclass
class TraceSpec:
    """One traceable workload: `fn` closes over all static args and takes
    only array (pytree) positionals; `args` are those arrays. `dims` maps
    the contract's symbolic dim names to this trace's concrete sizes.
    `jit_fn`/`call` (optional) drive the cache-growth check: `call`
    executes the real entry point with concrete args, `jit_fn` is the
    underlying jit wrapper whose `_cache_size()` is observed."""
    fn: Callable
    args: Tuple
    dims: Dict[str, int] = field(default_factory=dict)
    jit_fn: Optional[Callable] = None
    call: Optional[Callable] = None


@dataclass
class JaxprContract:
    name: str
    build: Callable[[], TraceSpec]
    no_dims: frozenset = frozenset()
    no_dims_1d: frozenset = frozenset()
    no_products: frozenset = frozenset()
    forbid_dtypes: frozenset = frozenset({"float64"})
    forbid_primitives: frozenset = HOST_CALLBACK_PRIMITIVES
    max_cache_growth: Optional[int] = 0


REGISTRY: Dict[str, JaxprContract] = {}


def jaxpr_contract(name: Optional[str] = None, *, no_dims=(), no_dims_1d=(),
                   no_products=(), forbid_dtypes=("float64",),
                   forbid_primitives=HOST_CALLBACK_PRIMITIVES,
                   max_cache_growth: Optional[int] = 0,
                   registry: Optional[Dict[str, JaxprContract]] = None):
    """Declare + register a contract over a trace-spec builder."""
    def deco(build):
        cname = name or build.__name__.lstrip("_")
        contract = JaxprContract(
            cname, build, frozenset(no_dims), frozenset(no_dims_1d),
            frozenset(no_products), frozenset(forbid_dtypes),
            frozenset(forbid_primitives), max_cache_growth)
        (REGISTRY if registry is None else registry)[cname] = contract
        return build
    return deco


# ------------------------------------------------------------------ checker

def _dim_violation(shape, v: int) -> bool:
    """The candidate-local predicate: (v,) exactly, or v in any
    non-leading axis (a leading-v axis is a streamed-over-points view).
    Leading size-1 axes are stripped first — inside shard_map the local
    index view arrives as (1, n_local, d), the shard axis in front of the
    same legitimate leading-n database view."""
    while len(shape) > 1 and shape[0] == 1:
        shape = shape[1:]
    if shape == (v,):
        return True
    return len(shape) >= 2 and v in shape[1:]


def _product_threshold(spec_dims: Dict[str, int], prod: str) -> int:
    """Parse "n*c" / "2*n*d": tokens are dim names or integer literals."""
    out = 1
    for tok in prod.split("*"):
        out *= int(tok) if tok.isdigit() else spec_dims[tok]
    return out


def check_contract(contract: JaxprContract) -> List[Finding]:
    import jax

    spec = contract.build()
    path = f"contract:{contract.name}"
    closed = jax.make_jaxpr(spec.fn)(*spec.args)
    vals = jaxpr_outvals(closed.jaxpr)
    findings: List[Finding] = []

    for dim in sorted(contract.no_dims):
        v = spec.dims[dim]
        bad = sorted({o.shape for o in vals if _dim_violation(o.shape, v)})
        if bad:
            findings.append(Finding(
                "jaxpr-dim", path, context=contract.name,
                snippet=f"{dim}={v}:{bad}",
                message=(f"intermediates carry forbidden dim {dim}={v}: "
                         f"{bad}")))
    for dim in sorted(contract.no_dims_1d):
        v = spec.dims[dim]
        bad = sorted({o.shape for o in vals
                      if len(o.shape) == 1 and o.shape[0] >= v})
        if bad:
            findings.append(Finding(
                "jaxpr-dim", path, context=contract.name,
                snippet=f"{dim}(1d)={v}:{bad}",
                message=f"1-D intermediates of forbidden dim {dim}: {bad}"))
    for prod in sorted(contract.no_products):
        v = _product_threshold(spec.dims, prod)
        bad = sorted({o.shape for o in vals
                      if int(np.prod(o.shape, dtype=np.int64)) >= v})
        if bad:
            findings.append(Finding(
                "jaxpr-dim", path, context=contract.name,
                snippet=f"{prod}>={v}:{bad}",
                message=(f"intermediates reach forbidden size "
                         f"{prod}={v}: {bad}")))
    for o in vals:
        if o.dtype in contract.forbid_dtypes:
            findings.append(Finding(
                "jaxpr-dtype", path, context=contract.name,
                snippet=f"{o.primitive}:{o.dtype}{list(o.shape)}",
                message=(f"forbidden dtype {o.dtype} leaks from "
                         f"`{o.primitive}` (shape {list(o.shape)})")))
    # collect from every equation, not just outvals: effect-only
    # primitives like debug_callback bind zero outputs
    prims = jaxpr_primitives(closed.jaxpr)
    for p in sorted(prims & contract.forbid_primitives):
        findings.append(Finding(
            "jaxpr-callback", path, context=contract.name, snippet=p,
            message=f"host-callback primitive `{p}` in the trace"))

    if (contract.max_cache_growth is not None and spec.call is not None
            and hasattr(spec.jit_fn, "_cache_size")):
        spec.call()                       # first call may compile: allowed
        before = spec.jit_fn._cache_size()
        spec.call()
        spec.call()
        growth = spec.jit_fn._cache_size() - before
        if growth > contract.max_cache_growth:
            findings.append(Finding(
                "cache-growth", path, context=contract.name,
                snippet=f"growth={growth}",
                message=(f"repeat same-shape calls grew the jit cache by "
                         f"{growth} (> {contract.max_cache_growth})")))
    return findings


def check_all_contracts(names=None) -> List[Finding]:
    findings: List[Finding] = []
    for name, c in sorted(REGISTRY.items()):
        if names and name not in names:
            continue
        findings.extend(check_contract(c))
    return findings


# ------------------------------------------------------- shared tiny fixture

@functools.lru_cache(maxsize=None)
def _tiny_dataset():
    rng = np.random.default_rng(7)
    X = rng.standard_normal((N_TRACE, D_TRACE)).astype(np.float32)
    Q = rng.standard_normal((NQ_TRACE, D_TRACE)).astype(np.float32)
    return X, Q


@functools.lru_cache(maxsize=None)
def _tiny_index():
    import jax
    from repro.core.ivf import build_ivf
    from repro.core.search import pack_ivf
    X, _ = _tiny_dataset()
    idx = build_ivf(jax.random.PRNGKey(0), X, C_TRACE, spill_mode="soar",
                    pq_subspaces=8, train_iters=3)
    return idx, pack_ivf(idx)


@functools.lru_cache(maxsize=None)
def _tiny_mesh():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:1]), ("a",))


# ------------------------------------------------------------ serving traces

@jaxpr_contract("search_jit", no_dims={"n"})
def _spec_search_jit():
    import jax.numpy as jnp
    from repro.core.search import search_jit
    _, Q = _tiny_dataset()
    _, packed = _tiny_index()
    jQ = jnp.asarray(Q)
    kw = dict(top_t=TOP_T, final_k=FINAL_K, rerank_budget=64,
              multiplicity=2)
    return TraceSpec(
        fn=lambda p, q: search_jit(p, q, **kw), args=(packed, jQ),
        dims={"n": N_TRACE}, jit_fn=search_jit,
        call=lambda: search_jit(packed, jQ, **kw))


@jaxpr_contract("search_jit_batched", no_dims={"n"})
def _spec_search_jit_batched():
    import jax.numpy as jnp
    from repro.core.search import pad_queries, search_jit_batched
    _, Q = _tiny_dataset()
    _, packed = _tiny_index()
    Qp, _, bq = pad_queries(Q, 128)
    jQ = jnp.asarray(Qp)
    kw = dict(top_t=TOP_T, final_k=FINAL_K, rerank_budget=64,
              multiplicity=2, bq=bq)
    return TraceSpec(
        fn=lambda p, q: search_jit_batched(p, q, **kw), args=(packed, jQ),
        dims={"n": N_TRACE}, jit_fn=search_jit_batched,
        call=lambda: search_jit_batched(packed, jQ, **kw))


@jaxpr_contract("search_jit_batched_filtered", no_dims={"n"})
def _spec_search_jit_batched_filtered():
    import jax.numpy as jnp
    from repro.core.search import pad_queries, search_jit_batched
    _, Q = _tiny_dataset()
    _, packed = _tiny_index()
    rng = np.random.default_rng(3)
    filt = jnp.asarray((rng.random(N_TRACE) < 0.3).astype(np.uint8))
    Qp, _, bq = pad_queries(Q, 128)
    jQ = jnp.asarray(Qp)
    kw = dict(top_t=TOP_T, final_k=FINAL_K, rerank_budget=64,
              multiplicity=2, bq=bq, escalate=True)
    return TraceSpec(
        fn=lambda p, q, f: search_jit_batched(p, q, filter=f, **kw),
        args=(packed, jQ, filt), dims={"n": N_TRACE},
        jit_fn=search_jit_batched,
        call=lambda: search_jit_batched(packed, jQ, filter=filt, **kw))


@jaxpr_contract("tree_route")
def _spec_tree_route():
    import jax.numpy as jnp
    from repro.kernels.tree_route import tree_route
    rng = np.random.default_rng(11)
    S, cmax = 5, 17
    SC = jnp.asarray(rng.standard_normal((S, D_TRACE)), jnp.float32)
    CC = jnp.asarray(rng.standard_normal((S, cmax, D_TRACE)), jnp.float32)
    CH = jnp.asarray(rng.integers(0, S * cmax, (S, cmax)), jnp.int32)
    _, Q = _tiny_dataset()
    jQ = jnp.asarray(Q)
    from repro.kernels.tree_route import tree_route_ref
    return TraceSpec(
        fn=lambda q, sc, cc, ch: tree_route(q, sc, cc, ch, t_route=2),
        args=(jQ, SC, CC, CH), dims={}, jit_fn=tree_route_ref,
        call=lambda: tree_route(jQ, SC, CC, CH, t_route=2))


# -------------------------------------------------------------- build traces

@jaxpr_contract("lloyd_sweep", no_dims_1d={"n"}, no_products={"n*c"})
def _spec_lloyd_sweep():
    import jax.numpy as jnp
    from repro.kernels.lloyd import lloyd_sweep
    X, _ = _tiny_dataset()
    rng = np.random.default_rng(5)
    C = jnp.asarray(X[rng.choice(N_TRACE, C_TRACE, replace=False)])
    jX = jnp.asarray(X)
    return TraceSpec(
        fn=lambda x, c: lloyd_sweep(x, c, C_TRACE, chunk=512),
        args=(jX, C), dims={"n": N_TRACE, "c": C_TRACE}, jit_fn=lloyd_sweep,
        call=lambda: lloyd_sweep(jX, C, C_TRACE, chunk=512))


@jaxpr_contract("assign_fused", no_dims_1d={"n"}, no_products={"n*c"})
def _spec_assign_fused():
    import jax.numpy as jnp
    from repro.kernels.soar_assign import assign_fused
    X, _ = _tiny_dataset()
    rng = np.random.default_rng(6)
    C = jnp.asarray(X[rng.choice(N_TRACE, C_TRACE, replace=False)])
    jX = jnp.asarray(X)
    return TraceSpec(
        fn=lambda x, c: assign_fused(x, c, lam=1.0, n_spills=1, chunk=512),
        args=(jX, C), dims={"n": N_TRACE, "c": C_TRACE},
        call=lambda: assign_fused(jX, C, lam=1.0, n_spills=1, chunk=512))


@jaxpr_contract("pq_encode", no_products={"2*n*d"})
def _spec_pq_encode():
    # threshold 2·n·d: the streamed encoder's largest legitimate buffers
    # are O(n·d) views of X (codes are n·m ≪ n·d); a dense all-subspace
    # distance matrix (n, m, 16) = 8·n·d trips the bound
    import jax.numpy as jnp
    from repro.quant.pq import pq_encode
    idx, _ = _tiny_index()
    X, _ = _tiny_dataset()
    jX = jnp.asarray(X)
    cb = idx.pq
    return TraceSpec(
        fn=lambda c, x: pq_encode(c, x, chunk=512), args=(cb, jX),
        dims={"n": N_TRACE, "d": D_TRACE}, jit_fn=pq_encode,
        call=lambda: pq_encode(cb, jX, chunk=512))


# -------------------------------------------------------- distributed makers

def _distributed_search_spec(pq_subspaces: int):
    import jax
    import jax.numpy as jnp
    from repro.core.distributed import build_sharded_ivf, \
        make_distributed_search
    X, Q = _tiny_dataset()
    sivf = build_sharded_ivf(jax.random.PRNGKey(2), X, 1, C_TRACE,
                             train_iters=3, pq_subspaces=pq_subspaces)
    fn = make_distributed_search(_tiny_mesh(), ("a",), top_t=TOP_T,
                                 final_k=FINAL_K, rerank_budget=32,
                                 bq=NQ_TRACE, multiplicity=2)
    return TraceSpec(fn=fn, args=(sivf, jnp.asarray(Q)),
                     dims={"n": N_TRACE})


@jaxpr_contract("distributed_search", no_dims={"n"})
def _spec_distributed_search():
    return _distributed_search_spec(0)


@jaxpr_contract("distributed_search_pq", no_dims={"n"})
def _spec_distributed_search_pq():
    return _distributed_search_spec(8)


@jaxpr_contract("replicated_search", no_dims={"n"})
def _spec_replicated_search():
    import jax.numpy as jnp
    from repro.core.distributed import make_replicated_search
    _, Q = _tiny_dataset()
    _, packed = _tiny_index()
    fn = make_replicated_search(_tiny_mesh(), ("a",), top_t=TOP_T,
                                final_k=FINAL_K, rerank_budget=64,
                                multiplicity=2)
    return TraceSpec(fn=fn, args=(packed, jnp.asarray(Q)),
                     dims={"n": N_TRACE})


@jaxpr_contract("sharded_assign", no_dims_1d={"n"}, no_products={"n*c"})
def _spec_sharded_assign():
    import jax.numpy as jnp
    from repro.core.distributed import make_sharded_assign
    X, _ = _tiny_dataset()
    rng = np.random.default_rng(8)
    C = jnp.asarray(X[rng.choice(N_TRACE, C_TRACE, replace=False)])
    # shard_map in_specs require the sharded rows divisible by the mesh
    # axis (size 1 here) — N_TRACE prime is fine on the 1-device mesh
    fn = make_sharded_assign(_tiny_mesh(), ("a",), chunk=512)
    return TraceSpec(fn=fn, args=(jnp.asarray(X), C),
                     dims={"n": N_TRACE, "c": C_TRACE})
