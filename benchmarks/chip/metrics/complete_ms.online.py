"""Mean length (ms) of the `frontend.complete` span, the dispatcher's
collection of one launched batch once its results are computed: their
copy back (`engine.download`) and the resolution of its Futures
(`frontend.resolve`), over the spans wholly inside the traced stretch;
nothing on a trace without the span."""
COMPLETE = "frontend.complete"


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    lo, hi = tr.window
    d = [e.dur for e in tr.host
         if e.name == COMPLETE and lo <= e.start and e.end <= hi]
    return sum(d) / len(d) * 1e-6 if d else None
