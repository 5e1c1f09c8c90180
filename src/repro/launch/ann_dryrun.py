import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Dry-run of the DISTRIBUTED SOAR SERVING step on the production meshes —
the paper's own workload at big-ann-benchmarks scale (SPACEV-like: ~0.5B
vectors), sharded over all mesh axes.

    PYTHONPATH=src python -m repro.launch.ann_dryrun [--mesh single|multi|both]

Per shard: 1M vectors, 2500 partitions (the paper's 400 pts/partition),
f32 rerank data. 256 shards (single pod) / 512 (multi) → 256M / 512M
vectors total. The search step is lowered + compiled with
ShapeDtypeStructs; memory/cost/collective analysis goes to
artifacts/dryrun/ann_serve_<mesh>.json.
"""
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.distributed import (abstract_sharded_ivf,  # noqa: E402
                                    abstract_sharded_ivf_pq,
                                    make_distributed_search,
                                    make_distributed_search_pq,
                                    sharded_ivf_pq_pspecs,
                                    sharded_ivf_pspecs)
from repro.launch.dryrun import (HBM_BW, ICI_BW, PEAK_FLOPS,  # noqa: E402
                                 fmt_summary)
from repro.launch.hlo_analysis import analyze  # noqa: E402
from repro.launch.mesh import (make_production_mesh,  # noqa: E402
                               to_shardings)
from repro.utils import enable_compile_cache  # noqa: E402

N_LOCAL = 1_000_000
C_LOCAL = 2_500
PMAX = 1_000          # ~2x mean partition size (spilled)
D = 100
NQ = 1_024
TOP_T = 40
FINAL_K = 10


def run(multi_pod: bool, pq: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n_shards = 512 if multi_pod else 256
    q = jax.ShapeDtypeStruct((NQ, D), jnp.float32)
    from jax.sharding import PartitionSpec as P
    if pq:
        m = D // 4   # s=4 dims/subspace
        ivf = abstract_sharded_ivf_pq(n_shards, N_LOCAL, C_LOCAL, PMAX, D, m)
        search = make_distributed_search_pq(mesh, axes, top_t=TOP_T,
                                            final_k=FINAL_K)
        in_sh = (sharded_ivf_pq_pspecs(axes), P())
    else:
        ivf = abstract_sharded_ivf(n_shards, N_LOCAL, C_LOCAL, PMAX, D)
        search = make_distributed_search(mesh, axes, top_t=TOP_T,
                                         final_k=FINAL_K)
        in_sh = (sharded_ivf_pspecs(axes), P())
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jax.jit(search, in_shardings=to_shardings(mesh, in_sh),
                          out_shardings=to_shardings(mesh, (P(), P()))
                          ).lower(ivf, q)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
    an = analyze(compiled.as_text())
    terms = {
        "compute_s": an["flops"] / PEAK_FLOPS,
        "memory_s": an["hbm_bytes"] / HBM_BW,
        "collective_s": an["collective_bytes_total"] / ICI_BW,
    }
    result = dict(
        arch="soar-ann-serve" + ("-pq" if pq else ""),
        shape=f"{n_shards}x{N_LOCAL//1000}k_q{NQ}",
        mesh="multi" if multi_pod else "single",
        compile_s=round(time.time() - t0, 1),
        memory=dict(argument_bytes=mem.argument_size_in_bytes,
                    temp_bytes=mem.temp_size_in_bytes,
                    output_bytes=mem.output_size_in_bytes,
                    peak_bytes=mem.argument_size_in_bytes + mem.temp_size_in_bytes),
        collectives={k: v for k, v in an["collectives"].items() if v["count"]},
        collective_bytes_total=an["collective_bytes_total"],
        roofline=dict(**{k: float(f"{v:.6g}") for k, v in terms.items()},
                      dominant=max(terms, key=terms.get),
                      model_flops_total=0, model_flops_per_device=0,
                      useful_flops_ratio=0,
                      bound_step_s=max(terms.values())),
        n_chips=n_shards,
    )
    os.makedirs("artifacts/dryrun", exist_ok=True)
    tag = "ann_serve_pq" if pq else "ann_serve"
    with open(f"artifacts/dryrun/{tag}_{result['mesh']}.json", "w") as f:
        json.dump(result, f, indent=1)
    return result


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--variant", default="both",
                    choices=["baseline", "pq", "both"])
    args = ap.parse_args()
    variants = {"baseline": [False], "pq": [True],
                "both": [False, True]}[args.variant]
    for mp in {"single": [False], "multi": [True],
               "both": [False, True]}[args.mesh]:
        for pq in variants:
            r = run(mp, pq=pq)
            print(fmt_summary(r))


if __name__ == "__main__":
    main()
