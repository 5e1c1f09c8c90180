"""Share (%) of the traced stretch in which the chip ran no operation."""


def read(ctx):
    from benchmarks.chip import tracefile
    tr = ctx.trace
    if tr is None or not tr.ops:
        return None
    dev = min(tr.ops)
    return 100.0 * (1.0 - tracefile.busy_s(tr, dev) / tr.window_s)
