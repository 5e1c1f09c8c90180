"""The control of a cell's comparison: the plain reference put in the
program's place, one precision step below the configuration's.

    python3 benchmarks/chip/control.py --workload glove100.online \
        --seeds 101,102,103

For each seed it makes the cell's corpus and query pool as a run does,
answers every pool query with the brute-force top-10 computed in
three-pass bfloat16 (what `Precision.HIGH` computes; the configuration
states float32 at `HIGHEST`), and reads the numbers the run's comparison
reads (reference.readings) over those answers. A run asks for rows of
that pool, and repeated rows get the same answer, so the pool covers what
a run compares. The control has to fail the comparison: its widest score
gap sets the upper reading of the `score_gap` limit.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import corpus, harness, reference  # noqa: E402


def readings(cfg: dict, seed: int) -> dict:
    """The control's readings for one seed."""
    X, pool = corpus.make(cfg, seed)
    exact_ids, _ = reference.exact_topk_device(X, pool)
    ids, scores = reference.control_topk_device(X, pool)
    qidx = np.arange(len(pool))
    out = reference.readings(X, pool, qidx, ids, scores, exact_ids)
    ok, _ = reference.check(out, cfg["limits"], {})
    return dict(out, seed=seed, correct=bool(ok))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    _, cell, cfg, _ = harness.load_cell(args.workload)
    try:
        harness.chips(1, harness.load_json(harness.HERE, "peaks.json"))
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        rows.append(readings(cfg, seed))
        harness.log("control " + json.dumps(rows[-1])
                    + f" seconds={time.perf_counter() - t:.3f}")
    print(json.dumps({"workload": args.workload,
                      "score_gap_min": min(r["score_gap"] for r in rows),
                      "any_correct": any(r["correct"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
