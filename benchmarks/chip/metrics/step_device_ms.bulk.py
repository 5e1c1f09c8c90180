"""Device time (ms) of one execution of the search program, the program
that holds the window-scoring kernel, on the slowest chip: each request
of the closed loop runs the program once on every chip, so the slowest
chip's execution bounds the request."""
KERNEL = r"pq_score_window"


def read(ctx):
    from benchmarks.chip import tracefile
    tr = ctx.trace
    if tr is None:
        return None
    per_chip = []
    for dev in sorted(tr.ops):
        ex = tracefile.executions_inside(tr, dev, KERNEL)
        if ex:
            per_chip.append(sum(e.dur for e in ex) / len(ex))
    return max(per_chip) * 1e-6 if per_chip else None
