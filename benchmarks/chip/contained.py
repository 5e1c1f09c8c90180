"""Per-dispatch means of host spans that `spans.STEPS` does not name,
such as the replica fan-out's `replica.scatter` and `replica.gather`
(DESIGN.md §3.15).

A span belongs to the `frontend.dispatch` span that encloses it in time,
among the dispatches that `spans.dispatches` finds wholly inside the
traced stretch. A trace of a program without the span reads nothing
(None), not zero.
"""
from __future__ import annotations

import bisect
from typing import Optional

from benchmarks.chip import spans
from benchmarks.chip.tracefile import Trace


def mean_ms(tr: Optional[Trace], name: str) -> Optional[float]:
    """Mean over the dispatches that enclose a `name` span of the summed
    length (ms) of the `name` spans each encloses; None where none does."""
    if tr is None:
        return None
    ds = spans.dispatches(tr)
    starts = [d.span.start for d in ds]
    per = {}
    for e in tr.host:
        if e.name != name:
            continue
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.end <= ds[i].span.end:
            per[i] = per.get(i, 0) + e.dur
    return sum(per.values()) / len(per) * 1e-6 if per else None
