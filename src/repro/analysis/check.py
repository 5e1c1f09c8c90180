"""CLI: `python -m repro.analysis.check` — run the static contract
analyzer (jaxpr contracts + recompile sentinel + AST lints) against the
repo and exit nonzero on any finding not grandfathered by the committed
ratchet baseline (DESIGN.md §3.14).

    python -m repro.analysis.check                 # full run
    python -m repro.analysis.check --skip sentinel # passes are skippable
    python -m repro.analysis.check --report findings.json
    python -m repro.analysis.check --update-baseline   # re-ratchet
    python -m repro.analysis.check --inject f64-leak   # self-test: must
                                                       # exit nonzero

--inject runs a synthetic violation of the named class through the SAME
pass machinery (not a fabricated finding), so CI can verify each detector
actually detects: o-n-intermediate | f64-leak | cache-growth |
unlocked-call | falsy-default.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import textwrap
from typing import List, Optional

from repro.analysis.findings import (Finding, load_baseline,
                                     partition_findings, save_baseline)

PASSES = ("lint", "contracts", "sentinel")
INJECT_CLASSES = ("o-n-intermediate", "f64-leak", "cache-growth",
                  "unlocked-call", "falsy-default")


def _repo_root(explicit: Optional[str] = None) -> str:
    if explicit:
        return os.path.abspath(explicit)
    here = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))
    if os.path.isdir(os.path.join(here, "src", "repro")):
        return here
    return os.getcwd()


# ------------------------------------------------------------- injections
# Each injector drives a deliberately-violating synthetic target through
# the real pass, proving the detector fires (acceptance criterion: the CLI
# exits nonzero on every class).

def _inject_o_n_intermediate() -> List[Finding]:
    import jax.numpy as jnp
    import numpy as np
    from repro.analysis.contracts import TraceSpec, jaxpr_contract, \
        check_contract

    reg: dict = {}

    @jaxpr_contract("injected_o_n", no_dims={"n"}, registry=reg)
    def _spec():
        X = jnp.asarray(np.zeros((521, 8), np.float32))
        # (n, n) similarity matrix: exactly the database-sized
        # intermediate the candidate-local pipeline forbids
        return TraceSpec(fn=lambda x: (x @ x.T).sum(axis=0), args=(X,),
                         dims={"n": 521})

    return check_contract(reg["injected_o_n"])


def _inject_f64_leak() -> List[Finding]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.analysis.contracts import TraceSpec, jaxpr_contract, \
        check_contract

    reg: dict = {}

    @jaxpr_contract("injected_f64", registry=reg)
    def _spec():
        X = jnp.asarray(np.zeros((16, 8), np.float32))
        return TraceSpec(fn=lambda x: x.astype(jnp.float64).sum(),
                         args=(X,), dims={})

    with jax.enable_x64(True):
        return check_contract(reg["injected_f64"])


def _inject_cache_growth() -> List[Finding]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.analysis.sentinel import cache_growth, snapshot_caches

    @jax.jit
    def toy(x):
        return (x * 2.0).sum()

    # the classic recompile storm: every distinct nq keys a fresh trace
    # (the bug class pad_queries' power-of-two buckets eliminate)
    toy(jnp.zeros((1,)))
    fns = {"injected_toy": toy}
    before = snapshot_caches(fns)
    for nq in range(2, 7):
        toy(jnp.asarray(np.zeros(nq, np.float32)))
    after = snapshot_caches(fns)
    return [Finding("cache-growth", "sentinel:injected", context=name,
                    snippet=name,
                    message=f"injected recompile storm grew cache {b}->{a}")
            for name, (b, a) in cache_growth(before, after).items()]


_UNLOCKED_SRC = textwrap.dedent("""\
    class Frontend:
        def _expire_locked(self):
            pass

        def poll(self):
            self._expire_locked()       # no lock held: must be flagged
""")

_FALSY_SRC = textwrap.dedent("""\
    def probe(self, top_t=None):
        top_t = top_t or self.top_t     # explicit 0 silently coalesced
        return top_t
""")


def _inject_unlocked_call() -> List[Finding]:
    from repro.analysis.lint_ast import lint_source
    return lint_source(_UNLOCKED_SRC, "src/repro/serve/_injected.py")


def _inject_falsy_default() -> List[Finding]:
    from repro.analysis.lint_ast import lint_source
    return lint_source(_FALSY_SRC, "src/repro/core/_injected.py")


_INJECTORS = {
    "o-n-intermediate": _inject_o_n_intermediate,
    "f64-leak": _inject_f64_leak,
    "cache-growth": _inject_cache_growth,
    "unlocked-call": _inject_unlocked_call,
    "falsy-default": _inject_falsy_default,
}


# -------------------------------------------------------------------- main

def run_passes(root: str, passes, verbose: bool = False) -> List[Finding]:
    findings: List[Finding] = []
    if "lint" in passes:
        from repro.analysis.lint_ast import lint_paths
        found = lint_paths(root)
        if verbose:
            print(f"[lint] {len(found)} finding(s)")
        findings.extend(found)
    if "contracts" in passes:
        from repro.analysis.contracts import REGISTRY, check_all_contracts
        found = check_all_contracts()
        if verbose:
            print(f"[contracts] {len(REGISTRY)} contract(s), "
                  f"{len(found)} finding(s)")
        findings.extend(found)
    if "sentinel" in passes:
        from repro.analysis.sentinel import run_serving_workload
        found = run_serving_workload(verbose=verbose)
        if verbose:
            print(f"[sentinel] {len(found)} finding(s)")
        findings.extend(found)
    return findings


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.check",
        description="Static contract analyzer (DESIGN.md §3.14)")
    ap.add_argument("--root", default=None, help="repo root (default: "
                    "inferred from this module's location)")
    ap.add_argument("--skip", action="append", default=[],
                    choices=PASSES, help="skip a pass (repeatable)")
    ap.add_argument("--only", action="append", default=[],
                    choices=PASSES, help="run only these passes")
    ap.add_argument("--report", default=None,
                    help="write the findings report (JSON) here")
    ap.add_argument("--baseline", default=None,
                    help="ratchet baseline path (default: committed "
                    "src/repro/analysis/baseline.json)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="grandfather all current findings and exit 0")
    ap.add_argument("--inject", choices=INJECT_CLASSES, default=None,
                    help="self-test: add a synthetic violation of this "
                    "class (the run must then exit nonzero)")
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    passes = [p for p in (args.only or PASSES) if p not in args.skip]
    root = _repo_root(args.root)
    findings = run_passes(root, passes, verbose=not args.quiet)
    if args.inject:
        injected = _INJECTORS[args.inject]()
        if not injected:
            print(f"INJECTION FAILED: synthetic `{args.inject}` violation "
                  f"was not detected", file=sys.stderr)
            return 2
        findings.extend(injected)

    baseline = load_baseline(args.baseline)
    new, grandfathered = partition_findings(findings, baseline)

    if args.report:
        with open(args.report, "w") as fh:
            json.dump({
                "passes": passes,
                "new": [f.to_dict() for f in new],
                "grandfathered": [f.to_dict() for f in grandfathered],
            }, fh, indent=2)
            fh.write("\n")

    for f in grandfathered:
        print(f.render(grandfathered=True))
    for f in new:
        print(f.render())
    if args.update_baseline:
        save_baseline(findings, args.baseline)
        print(f"baseline updated: {len(findings)} finding(s) "
              f"grandfathered")
        return 0
    if not args.quiet or new:
        print(f"repro.analysis.check: {len(new)} new finding(s), "
              f"{len(grandfathered)} grandfathered, passes: "
              f"{', '.join(passes)}")
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
