import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Per-cell roofline profile: top HBM and collective contributors from the
trip-count-aware HLO analysis (the §Perf iteration tool).

    PYTHONPATH=src python -m repro.launch.profile_cell --arch xlstm-350m \
        --shape train_4k [--mesh single]
"""
import argparse          # noqa: E402

import jax               # noqa: E402

from repro.configs import ARCH_IDS, get_config, get_rule_overrides  # noqa: E402
from repro.launch import specs as S                                 # noqa: E402
from repro.launch.dryrun import HBM_BW, ICI_BW, PEAK_FLOPS          # noqa: E402
from repro.launch.hlo_analysis import analyze                       # noqa: E402
from repro.launch.mesh import (build_rules, make_production_mesh,  # noqa: E402
                               to_shardings)
from repro.models.config import SHAPES                              # noqa: E402
from repro.models.layers import set_logical_rules                   # noqa: E402
from repro.utils import enable_compile_cache  # noqa: E402


def profile(arch: str, shape: str, multi_pod: bool = False, top_n: int = 12):
    cfg = get_config(arch)
    cell = SHAPES[shape]
    rules = build_rules(dict(get_rule_overrides(arch)), multi_pod=multi_pod,
                        batch_size=cell.global_batch)
    if cell.kind == "decode":
        rules = S.serve_rules(cfg, rules)
    mesh = make_production_mesh(multi_pod=multi_pod)
    set_logical_rules(rules)
    if cell.kind == "train":
        fn, args, insh, outsh = S.train_cell_specs(cfg, cell, rules, multi_pod)
        donate = (0, 1)
    elif cell.kind == "prefill":
        fn, args, insh, outsh = S.prefill_cell_specs(cfg, cell, rules)
        donate = ()
    else:
        fn, args, insh, outsh = S.decode_cell_specs(cfg, cell, rules)
        donate = (2,)
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=to_shardings(mesh, insh),
                           out_shardings=to_shardings(mesh, outsh),
                           donate_argnums=donate).lower(*args).compile()
        mem = compiled.memory_analysis()
    r = analyze(compiled.as_text(), top_n=top_n)
    print(f"== {arch} {shape} {'multi' if multi_pod else 'single'}")
    print(f"terms: compute {r['flops']/PEAK_FLOPS:.3f}s  "
          f"memory {r['hbm_bytes']/HBM_BW:.3f}s  "
          f"collective {r['collective_bytes_total']/ICI_BW:.3f}s")
    print(f"peak mem: args {mem.argument_size_in_bytes/2**30:.2f} + temp "
          f"{mem.temp_size_in_bytes/2**30:.2f} GiB")
    print("-- top HBM contributors:")
    for c in r["top_hbm"]:
        print(f"  {c['bytes']:.3g}B x{c['mult']:.0f} {c['op'][:14]:14s} "
              f"{c['comp'][:34]:34s} {c['type']}")
    print("-- top collective contributors:")
    for c in r.get("top_coll", []):
        print(f"  {c['bytes']:.3g}B x{c['mult']:.0f} {c['op'][:14]:14s} "
              f"{c['comp'][:34]:34s} {c['type']}")
    return r, mem


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args()
    profile(args.arch, args.shape, args.mesh == "multi", args.top)


if __name__ == "__main__":
    main()
