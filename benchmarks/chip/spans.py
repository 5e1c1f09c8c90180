"""The serving program's own host spans in a trace, reduced per dispatch.

The frontend's dispatcher thread opens a `frontend.dispatch` span for each
coalesced search group; inside it come `frontend.coalesce`, the engine's
`engine.prepare`, `engine.upload`, `engine.launch` and `engine.download`,
and `frontend.resolve` (DESIGN.md §3.15). `jax.profiler.TraceAnnotation`
writes them into the profiler's trace, on the clock of the device events.

A step span belongs to the dispatch span that encloses it in time (one
dispatcher thread: dispatch spans never overlap). Per-dispatch readings
count only dispatches that lie wholly inside the traced stretch; the
device idle time inside dispatches is the interval measure of the idle
gaps that overlap an open dispatch, clipped to the stretch. A trace of a
program without these spans reads nothing (None), not zero.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Callable, Dict, List, Optional

from benchmarks.chip import tracefile
from benchmarks.chip.tracefile import Ev, Trace

DISPATCH = "frontend.dispatch"
STEPS = ("frontend.coalesce", "engine.prepare", "engine.upload",
         "engine.launch", "engine.download", "frontend.resolve")


@dataclasses.dataclass
class Dispatch:
    span: Ev
    steps: Dict[str, List[Ev]]        # step name -> its spans, in order

    def total_ns(self, step: str) -> Optional[int]:
        """Summed length of `step`'s spans (a replica call that fell back
        to the local path leaves two), or None where there are none."""
        evs = self.steps.get(step)
        return sum(e.dur for e in evs) if evs else None

    def end_of(self, step: str) -> Optional[int]:
        evs = self.steps.get(step)
        return evs[-1].end if evs else None


def dispatches(tr: Trace) -> List[Dispatch]:
    """Dispatch spans wholly inside the traced stretch, in time order,
    each with the step spans it encloses."""
    lo, hi = tr.window
    out = [Dispatch(e, {}) for e in sorted(
        (e for e in tr.host
         if e.name == DISPATCH and lo <= e.start and e.end <= hi),
        key=lambda e: e.start)]
    starts = [d.span.start for d in out]
    for e in sorted((e for e in tr.host if e.name in STEPS),
                    key=lambda e: e.start):
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.end <= out[i].span.end:
            out[i].steps.setdefault(e.name, []).append(e)
    return out


def mean_ms(tr: Optional[Trace],
            per_dispatch: Callable[[Dispatch], Optional[int]]
            ) -> Optional[float]:
    """Mean over the dispatches of the stretch of `per_dispatch` (ns; None
    leaves the dispatch out), in ms; None where nothing is left."""
    if tr is None:
        return None
    vals = [v for v in map(per_dispatch, dispatches(tr)) if v is not None]
    return sum(vals) / len(vals) * 1e-6 if vals else None


def _overlap_ns(a, b) -> int:
    """Total overlap of two sorted lists of disjoint intervals."""
    tot, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        tot += max(min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]), 0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_in_dispatch_share(tr: Trace, devs) -> Optional[float]:
    """Share (%) of the traced stretch in which a device of `devs` ran no
    operation while a `frontend.dispatch` span was open, the mean over
    `devs`; None without devices or dispatch spans."""
    if not devs:
        return None
    lo, hi = tr.window
    open_ = tracefile.union(((e.start, e.end) for e in tr.host
                             if e.name == DISPATCH), lo, hi)
    if not open_:
        return None
    idle = [_overlap_ns(tracefile.idle_gaps(tr, d), open_) for d in devs]
    return 100.0 * sum(idle) / len(idle) / (hi - lo)
