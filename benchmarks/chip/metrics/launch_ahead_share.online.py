"""Share (%) of the engine dispatches of the traced stretch that the
frontend launched while another batch was still in flight, from its
`launched_ahead` and `dispatches` counters; nothing on a program without
the counter."""


def read(ctx):
    t = ctx.tracer
    if t is None or "launched_ahead" not in t.stats1:
        return None
    n = t.stats1["dispatches"] - t.stats0["dispatches"]
    if n <= 0:
        return None
    return 100.0 * (t.stats1["launched_ahead"]
                    - t.stats0["launched_ahead"]) / n
