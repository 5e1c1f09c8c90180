import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry run: lower + compile every (arch × shape × mesh) cell and
extract memory / cost / collective analysis for the roofline report.

    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-2b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all

Artifacts: artifacts/dryrun/<arch>_<shape>_<mesh>.json
"""
import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import numpy as np       # noqa: E402

from repro.configs import ARCH_IDS, get_config, get_rule_overrides  # noqa: E402
from repro.launch.mesh import (build_rules, make_production_mesh,  # noqa: E402
                               to_shardings)
from repro.launch import specs as S                                 # noqa: E402
from repro.launch.hlo_analysis import analyze                       # noqa: E402
from repro.models.config import SHAPES, cell_applicable             # noqa: E402
from repro.models.layers import set_logical_rules                   # noqa: E402
from repro.utils import enable_compile_cache  # noqa: E402

# v5e hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link

def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rules_extra: dict | None = None, save: bool = True) -> dict:
    cfg = get_config(arch)
    cell = SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    ok, why = cell_applicable(cfg, cell)
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if not ok:
        result["skipped"] = why
        return result

    n_chips = 512 if multi_pod else 256
    overrides = dict(get_rule_overrides(arch))
    if rules_extra:
        overrides.update(rules_extra)
    rules = build_rules(overrides, multi_pod=multi_pod,
                        batch_size=cell.global_batch)
    if cell.kind == "decode":
        # H2 (EXPERIMENTS §Perf): per-STEP param re-gather dominates decode;
        # prefill amortizes the gather over the whole sequence, so it keeps
        # FSDP (replication there only raises peak memory).
        rules = S.serve_rules(cfg, rules)
    mesh = make_production_mesh(multi_pod=multi_pod)
    set_logical_rules(rules)

    if cell.kind == "train":
        fn, args, in_sh, out_sh = S.train_cell_specs(cfg, cell, rules, multi_pod)
        donate = (0, 1)         # params + optimizer state update in place
    elif cell.kind == "prefill":
        fn, args, in_sh, out_sh = S.prefill_cell_specs(cfg, cell, rules)
        donate = ()
    else:
        fn, args, in_sh, out_sh = S.decode_cell_specs(cfg, cell, rules)
        donate = (2,)           # KV cache updated in place

    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=to_shardings(mesh, in_sh),
                          out_shardings=to_shardings(mesh, out_sh),
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # trip-count-aware static analysis (XLA's cost_analysis counts loop
    # bodies once — see hlo_analysis.py); per-device program values.
    an = analyze(hlo)
    flops = float(an["flops"])
    bytes_acc = float(an["hbm_bytes"])
    colls = {k: v for k, v in an["collectives"].items() if v["count"]}
    coll_total = float(an["collective_bytes_total"])
    mf = S.model_flops(cfg, cell)

    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_acc / HBM_BW
    # collective term: bytes leaving/entering ONE device over its ICI links
    collective_s = coll_total / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)

    result.update(dict(
        rules={k: str(v) for k, v in rules.items()},
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        per_device=dict(
            flops=flops, bytes_accessed=bytes_acc,
            output_bytes=float(cost.get("bytes accessed output", 0.0)),
        ),
        memory=dict(
            argument_bytes=getattr(mem, "argument_size_in_bytes", 0),
            output_bytes=getattr(mem, "output_size_in_bytes", 0),
            temp_bytes=getattr(mem, "temp_size_in_bytes", 0),
            peak_bytes=(getattr(mem, "temp_size_in_bytes", 0)
                        + getattr(mem, "argument_size_in_bytes", 0)),
        ),
        collectives=colls,
        collective_bytes_total=coll_total,
        roofline=dict(
            **{k: float(f"{v:.6g}") for k, v in terms.items()},
            dominant=dominant,
            model_flops_total=mf,
            model_flops_per_device=mf / n_chips,
            useful_flops_ratio=float(f"{(mf / n_chips) / max(flops, 1):.4g}"),
            bound_step_s=float(f"{max(terms.values()):.6g}"),
        ),
        n_chips=n_chips,
    ))
    if save:
        os.makedirs("artifacts/dryrun", exist_ok=True)
        path = f"artifacts/dryrun/{arch}_{shape_name}_{mesh_name}.json"
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
    return result


def fmt_summary(r: dict) -> str:
    if "skipped" in r:
        return (f"{r['arch']:22s} {r['shape']:12s} {r['mesh']:6s} "
                f"SKIP ({r['skipped']})")
    rf = r["roofline"]
    mem_gb = r["memory"]["peak_bytes"] / 2**30
    return (f"{r['arch']:22s} {r['shape']:12s} {r['mesh']:6s} "
            f"compile {r['compile_s']:6.1f}s mem {mem_gb:6.2f}GiB "
            f"compute {rf['compute_s']:.3g}s mem-term {rf['memory_s']:.3g}s "
            f"coll {rf['collective_s']:.3g}s → {rf['dominant']}"
            f" useful={rf['useful_flops_ratio']:.2f}")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS))
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    cells = []
    archs = list(ARCH_IDS) if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    failures = 0
    for a, s, mp in cells:
        try:
            r = run_cell(a, s, mp)
            print(fmt_summary(r), flush=True)
        except Exception as e:
            failures += 1
            print(f"{a:22s} {s:12s} {'multi' if mp else 'single':6s} "
                  f"FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cells failed")
    print("all cells passed")


if __name__ == "__main__":
    main()
