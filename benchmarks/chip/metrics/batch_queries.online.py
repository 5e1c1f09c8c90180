"""Mean requests per engine dispatch over the traced stretch, from the
frontend's `requests` and `dispatches` counters."""


def read(ctx):
    t = ctx.tracer
    if t is None:
        return None
    n = t.stats1["dispatches"] - t.stats0["dispatches"]
    if n <= 0:
        return None
    return (t.stats1["requests"] - t.stats0["requests"]) / n
