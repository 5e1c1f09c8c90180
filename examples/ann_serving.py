"""Distributed SOAR serving demo: shard a vector database over 8 (virtual)
devices, search with the shard_map engine, compare spill modes.

    PYTHONPATH=src python examples/ann_serving.py
(sets XLA_FLAGS itself — run as a standalone script.)
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time                                                    # noqa: E402

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402

from repro.core import true_neighbors                          # noqa: E402
from repro.core.distributed import (build_sharded_ivf,         # noqa: E402
                                    make_distributed_search)
from repro.data.vectors import make_manifold                   # noqa: E402


def main():
    n, d, nq = 64_000, 64, 256
    ds = make_manifold(jax.random.PRNGKey(0), n=n, d=d, nq=nq,
                       intrinsic_dim=10)
    tn = true_neighbors(ds.X, ds.Q, k=10)
    mesh = jax.make_mesh((8,), ("data",))
    print(f"database {ds.X.shape} sharded over {mesh.shape} mesh")

    for mode in ("none", "soar"):
        t0 = time.time()
        sharded = build_sharded_ivf(jax.random.PRNGKey(1), ds.X, n_shards=8,
                                    n_partitions=32, spill_mode=mode,
                                    train_iters=6)
        build_s = time.time() - t0
        search = make_distributed_search(mesh, ("data",), top_t=6, final_k=10)
        with jax.set_mesh(mesh):
            jsearch = jax.jit(search)
            ids, _ = jsearch(sharded, jnp.asarray(ds.Q))   # compile
            t0 = time.time()
            for _ in range(3):
                ids, _ = jsearch(sharded, jnp.asarray(ds.Q))
            ids.block_until_ready()
            dt = (time.time() - t0) / 3 / nq
        rec = (np.asarray(ids)[:, :, None] == tn[:, None, :]).any(-1).mean()
        print(f"  {mode:5s} build {build_s:5.1f}s  recall@10={rec:.3f}  "
              f"{dt*1e6:.0f} us/query (8-way, incl. global merge)")


if __name__ == "__main__":
    main()
