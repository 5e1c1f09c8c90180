"""Mean host wall time (ms) of one engine dispatch, `SearchResult.engine_us`
(pad, upload, search step, download), over the dispatches that served
requests due in the traced stretch."""
import numpy as np


def read(ctx):
    if ctx.tracer is None:
        return None
    w = ctx.window
    sel = np.flatnonzero(w.in_interval(ctx.tracer.lo, ctx.tracer.hi))
    per_dispatch = {w.results[i].engine_us for i in sel}
    if not per_dispatch:
        return None
    return float(np.mean(sorted(per_dispatch)) / 1e3)
