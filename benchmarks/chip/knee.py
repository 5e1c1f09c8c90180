"""Find the knee of an open-loop cell: one sweep over fixed rates.

    python3 benchmarks/chip/knee.py --workload glove100.online --seed 3 \
        --rates 500,1000,2000,4000 --seconds 5 [--out knee.json]

Builds the cell once, then offers its traffic at each rate in turn for
`--seconds`, waiting for every answer before the next rate. For each rate
it prints the p50, p95 and p99 latency from the due time, the requests
that failed, how late the generator ran, and the backlog growth: the
median latency of the last quarter of requests less that of the first
quarter. The knee is the highest rate whose p99 stays within `--limit-ms`
(the repository's default deadline, 50 ms) with a backlog growth under
`--growth-ms`. The cell's traffic file is then set to four fifths of it.
A runtime stall can fail a rate below the knee on its p99 alone (its p50
and backlog growth stay put), so read the rows, not just the knee.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import harness  # noqa: E402


def sweep(workload, seed, rates, seconds, limit_ms, growth_ms):
    bench, cell, cfg, traffic = harness.load_cell(workload)
    devs = harness.chips(cell["chips"], harness.load_json(harness.HERE,
                                                          "peaks.json"))
    harness.log(f"device kind={devs[0].device_kind} count={len(devs)}")
    harness.enable_compile_cache()
    loop = harness.plugin("loops", traffic["kind"])
    s = harness.prepare(cfg, traffic, seed, loop)
    rows, knee = [], None
    try:
        for i, rate in enumerate(rates):
            tr = dict(traffic, rate_per_s=rate)
            w = loop.run(s.fe, s.pool, tr, seed + i, seconds,
                         lambda name: contextlib.nullcontext(), lambda t: None)
            lat = w.latency_s() * 1e3
            q = max(len(lat) // 4, 1)
            fin = lat[np.isfinite(lat)]
            row = {"rate_per_s": rate, "requests": len(lat),
                   "failed": int((~np.isfinite(lat)).sum()),
                   "p50_ms": float(np.percentile(fin, 50)) if fin.size else None,
                   "p95_ms": float(np.percentile(lat, 95)),
                   "p99_ms": float(np.percentile(lat, 99)),
                   "growth_ms": float(np.median(lat[-q:]) - np.median(lat[:q])),
                   "lateness_ms": loop.lateness_ms(w)}
            row["ok"] = bool(row["failed"] == 0 and row["p99_ms"] <= limit_ms
                             and row["growth_ms"] < growth_ms)
            harness.log("knee " + json.dumps(row))
            rows.append(row)
            if row["ok"]:
                knee = rate
            elif len(rows) > 1 and not rows[-2]["ok"]:
                break
    finally:
        s.fe.close()
    return {"workload": workload, "seed": seed, "seconds": seconds,
            "limit_ms": limit_ms, "growth_ms": growth_ms, "knee": knee,
            "rows": rows}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--limit-ms", type=float, default=50.0)
    ap.add_argument("--growth-ms", type=float, default=10.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    t = time.perf_counter()
    try:
        out = sweep(args.workload, args.seed,
                    [float(r) for r in args.rates.split(",")], args.seconds,
                    args.limit_ms, args.growth_ms)
    except harness.NoChip as e:
        print(f"knee: {e}", file=sys.stderr)
        return 2
    out["total_s"] = time.perf_counter() - t
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({"knee": out["knee"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
