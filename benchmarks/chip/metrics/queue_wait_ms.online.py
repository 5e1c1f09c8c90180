"""Mean frontend queue wait (ms), `SearchResult.queued_us`, of the
requests due in the traced stretch."""
import numpy as np


def read(ctx):
    if ctx.tracer is None:
        return None
    w = ctx.window
    sel = np.flatnonzero(w.in_interval(ctx.tracer.lo, ctx.tracer.hi))
    if not sel.size:
        return None
    return float(np.mean([w.results[i].queued_us for i in sel]) / 1e3)
