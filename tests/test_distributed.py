"""Multi-device semantics tests (8 virtual CPU devices via subprocess, so
the main pytest process keeps its single-device view), and the one-shard
search on the main process's one device."""
import functools
import subprocess
import sys

import pytest

SCRIPT_ANN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core.distributed import build_sharded_ivf, make_distributed_search
from repro.core import true_neighbors
from repro.data.vectors import make_manifold

ds = make_manifold(jax.random.PRNGKey(0), n=16_000, d=32, nq=64, intrinsic_dim=8)
tn = true_neighbors(ds.X, ds.Q, k=10)
mesh = jax.make_mesh((8,), ("data",))
sharded = build_sharded_ivf(jax.random.PRNGKey(1), ds.X, n_shards=8,
                            n_partitions=16, spill_mode="soar", train_iters=5)
search = make_distributed_search(mesh, ("data",), top_t=8, final_k=10)
with jax.set_mesh(mesh):
    ids, scores = jax.jit(search)(sharded, jnp.asarray(ds.Q))
ids = np.asarray(ids)
rec = (ids[:, :, None] == tn[:, None, :]).any(-1).mean()
assert rec > 0.80, f"distributed recall {rec}"
# global ids must be valid and deduplicated
assert ids.min() >= 0 and ids.max() < 16_000
for row in ids:
    assert len(set(row.tolist())) == len(row)
print("OK recall", rec)
"""

SCRIPT_ELASTIC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.ckpt.checkpoint import save, restore

tree = {"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.ones(8)}
d = tempfile.mkdtemp()
p = d + "/ck"
save(p, tree, step=3)
# restore onto a 2x4 mesh with w sharded over both axes — elastic re-mesh
mesh = jax.make_mesh((2, 4), ("a", "b"))
sh = {"w": NamedSharding(mesh, P("a", "b")), "b": NamedSharding(mesh, P("b"))}
back, step, _ = restore(p, tree, shardings=sh)
assert step == 3
assert back["w"].sharding == sh["w"]
np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(tree["w"]))
print("OK")
"""

SCRIPT_TRAIN_SPMD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.data.pipeline import for_model
from repro.launch.mesh import build_rules, make_test_mesh
from repro.models.layers import set_logical_rules
from repro.models import transformer as T
from repro.train import optimizer as opt
from repro.train.train_loop import make_train_step

cfg = get_config("granite-3-2b").smoke_config()
mesh = make_test_mesh((2, 4), ("data", "model"))
rules = build_rules({}, batch_size=8)
rules["heads"] = None  # 4 smoke heads won't split 4-way AND kv too; keep simple
set_logical_rules(rules)
pipe = for_model(cfg, seq_len=32, global_batch=8)
params = T.init_params(jax.random.PRNGKey(0), cfg)
lr_fn = opt.warmup_cosine(1e-3, 5, 100)
step = make_train_step(cfg, lr_fn, accum=2)
with jax.set_mesh(mesh):
    pspec = T.param_pspecs(cfg, rules)
    params = jax.device_put(params, jax.tree.map(
        lambda s: jax.NamedSharding(mesh, s), pspec))
    ostate = opt.init(params)
    jstep = jax.jit(step)
    for i in range(3):
        params, ostate, m = jstep(params, ostate, pipe.batch_at(i))
loss = float(m["loss"])
assert np.isfinite(loss)
# compare against single-device reference for step equivalence
set_logical_rules({})
params_ref = T.init_params(jax.random.PRNGKey(0), cfg)
ostate_ref = opt.init(params_ref)
jref = jax.jit(make_train_step(cfg, lr_fn, accum=2))
for i in range(3):
    params_ref, ostate_ref, mr = jref(params_ref, ostate_ref, pipe.batch_at(i))
ref = float(mr["loss"])
assert abs(loss - ref) / max(abs(ref), 1e-6) < 5e-2, (loss, ref)
print("OK", loss, ref)
"""


def _run(script):
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                                       # force CPU: the image ships libtpu, and
                                       # probing it burns 60s+ per subprocess
                                       "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "OK" in r.stdout


SCRIPT_ANN_PQ = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core.distributed import build_sharded_ivf, make_distributed_search
from repro.core import true_neighbors
from repro.data.vectors import make_manifold

ds = make_manifold(jax.random.PRNGKey(0), n=16_000, d=32, nq=64, intrinsic_dim=8)
tn = true_neighbors(ds.X, ds.Q, k=10)
mesh = jax.make_mesh((8,), ("data",))
sharded = build_sharded_ivf(jax.random.PRNGKey(1), ds.X, n_shards=8,
                            n_partitions=16, pq_subspaces=8,
                            spill_mode="soar", train_iters=5)
search = make_distributed_search(mesh, ("data",), top_t=8, final_k=10,
                                 rerank_budget=128, bq=32)
with jax.set_mesh(mesh):
    ids, scores = jax.jit(search)(sharded, jnp.asarray(ds.Q))
ids = np.asarray(ids)
rec = (ids[:, :, None] == tn[:, None, :]).any(-1).mean()
assert rec > 0.75, f"distributed PQ recall {rec}"
assert ids.min() >= 0 and ids.max() < 16_000
print("OK recall", rec)
"""


SCRIPT_ANN_FILTERED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core.distributed import (build_sharded_ivf,
                                    make_distributed_search, shard_filters)
from repro.data.vectors import make_manifold

ds = make_manifold(jax.random.PRNGKey(0), n=8_000, d=32, nq=32, intrinsic_dim=8)
mask = np.random.default_rng(0).random(8_000) < 0.2
alive = np.flatnonzero(mask)
sc = ds.Q.astype(np.float32) @ ds.X[alive].T
tn = alive[np.argsort(-sc, axis=1)[:, :10]]        # FILTERED exact top-10
mesh = jax.make_mesh((8,), ("data",))
filt = shard_filters(mask, [1000] * 8)
sharded = build_sharded_ivf(jax.random.PRNGKey(1), ds.X, n_shards=8,
                            n_partitions=16, spill_mode="soar", train_iters=4)
search = make_distributed_search(mesh, ("data",), top_t=10, final_k=10,
                                 bq=32)
with jax.set_mesh(mesh):
    ids, _ = jax.jit(search)(sharded, jnp.asarray(ds.Q), filt)
ids = np.asarray(ids)
rec = (ids[:, :, None] == tn[:, None, :]).any(-1).mean()
assert rec > 0.9, f"filtered distributed recall {rec}"
assert mask[ids[ids >= 0]].all(), "result violated the subset filter"
shardedpq = build_sharded_ivf(jax.random.PRNGKey(1), ds.X, n_shards=8,
                              n_partitions=16, pq_subspaces=8,
                              spill_mode="soar", train_iters=4)
searchpq = make_distributed_search(mesh, ("data",), top_t=10, final_k=10,
                                   rerank_budget=128, bq=32)
with jax.set_mesh(mesh):
    idsp, _ = jax.jit(searchpq)(shardedpq, jnp.asarray(ds.Q), filt)
idsp = np.asarray(idsp)
recp = (idsp[:, :, None] == tn[:, None, :]).any(-1).mean()
assert recp > 0.85, f"filtered distributed PQ recall {recp}"
assert mask[idsp[idsp >= 0]].all()
print("OK recall", rec, recp)
"""


def test_distributed_ann_search():
    _run(SCRIPT_ANN)


def test_distributed_ann_search_pq():
    _run(SCRIPT_ANN_PQ)


def test_distributed_ann_search_filtered():
    _run(SCRIPT_ANN_FILTERED)


def test_elastic_checkpoint_remesh():
    _run(SCRIPT_ELASTIC)


def test_spmd_train_step_matches_single_device():
    _run(SCRIPT_TRAIN_SPMD)


SCRIPT_ANN_ROUTER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core.build import build_ivf_sharded
from repro.core.distributed import (make_distributed_search,
                                    sharded_from_indexes,
                                    stack_tree_routers)
from repro.core import true_neighbors
from repro.data.vectors import make_manifold

# each shard builds its own index AND its own two-level router (like its
# own codebook); the search takes the stacked tables as its `router`
# argument and probes through them shard-locally. The shards are PQ-built;
# the exact case drops their codes.
ds = make_manifold(jax.random.PRNGKey(0), n=8_000, d=32, nq=32, intrinsic_dim=8)
tn = true_neighbors(ds.X, ds.Q, k=10)
nl = 1_000
idxs = [build_ivf_sharded(jax.random.fold_in(jax.random.PRNGKey(1), s),
                          ds.X[s * nl:(s + 1) * nl], 16, spill_mode="soar",
                          train_iters=4, pq_subspaces=8, router="tree",
                          router_kw=dict(n_super=4, t_route=3))
        for s in range(8)]
srt = stack_tree_routers([i.router for i in idxs])
mesh = jax.make_mesh((8,), ("data",))
shardedpq = sharded_from_indexes(idxs)
sharded = shardedpq._replace(packed=shardedpq.packed._replace(
    part_codes=None, pq=None))
search = make_distributed_search(mesh, ("data",), top_t=8, final_k=10,
                                 rerank_budget=128, bq=32, t_route=3)
with jax.set_mesh(mesh):
    ids, _ = jax.jit(search)(sharded, jnp.asarray(ds.Q), router=srt)
ids = np.asarray(ids)
rec = (ids[:, :, None] == tn[:, None, :]).any(-1).mean()
assert rec > 0.70, f"tree-routed distributed recall {rec}"
assert ids.max() < 8_000
with jax.set_mesh(mesh):
    idsp, _ = jax.jit(search)(shardedpq, jnp.asarray(ds.Q), router=srt)
idsp = np.asarray(idsp)
recp = (idsp[:, :, None] == tn[:, None, :]).any(-1).mean()
assert recp > 0.65, f"tree-routed distributed PQ recall {recp}"
print("OK recall", rec, recp)
"""


def test_distributed_ann_search_tree_routed():
    _run(SCRIPT_ANN_ROUTER)


# ------------------------------------------------------------ one pass
@functools.lru_cache(maxsize=None)
def _one_shard(pq_subspaces):
    import jax
    from repro.core.distributed import build_sharded_ivf
    from repro.data.vectors import make_manifold
    ds = make_manifold(jax.random.PRNGKey(0), n=2_000, d=16, nq=40,
                       intrinsic_dim=8)
    return ds, build_sharded_ivf(jax.random.PRNGKey(1), ds.X, n_shards=1,
                                 n_partitions=16, train_iters=3,
                                 pq_subspaces=pq_subspaces)


@pytest.mark.parametrize("filtered", [False, True],
                         ids=["unfiltered", "filtered"])
@pytest.mark.parametrize("pq_subspaces", [0, 8], ids=["exact", "pq"])
def test_one_shard_runs_the_single_host_pass(pq_subspaces, filtered):
    """A one-shard make_distributed_search on a one-device mesh answers
    bitwise as search_jit_batched (no escalation) on that shard's
    PackedIVF: the sharded path runs the single-host pass, tiles and all
    (40 queries in tiles of 16, the last one padded)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.distributed import make_distributed_search
    from repro.core.search import search_jit_batched
    ds, sharded = _one_shard(pq_subspaces)
    packed = jax.tree_util.tree_map(lambda x: x[0], sharded.packed)
    mask = (np.random.default_rng(0).random(2_000) < 0.3).astype(np.uint8)
    kw = dict(top_t=4, final_k=10, rerank_budget=32, bq=16, multiplicity=2)
    mesh = jax.make_mesh((1,), ("data",))
    Q = jnp.asarray(ds.Q)
    with jax.set_mesh(mesh):
        got = jax.jit(make_distributed_search(mesh, ("data",), **kw))(
            sharded, Q, filter=jnp.asarray(mask[None]) if filtered else None)
    want = search_jit_batched(packed, Q, escalate=False,
                              filter=jnp.asarray(mask) if filtered else None,
                              **kw)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    if filtered:
        ids = np.asarray(got[0])
        assert mask[ids[ids >= 0]].all()
