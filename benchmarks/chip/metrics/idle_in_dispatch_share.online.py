"""Share (%) of the traced stretch in which the chip ran no operation while
the dispatcher had a `frontend.dispatch` span open: the part of
`device_idle_share.online` spent waiting on the serial dispatch rather
than on traffic or the flush timer (spans.py)."""


def read(ctx):
    from benchmarks.chip import spans
    tr = ctx.trace
    if tr is None or not tr.ops:
        return None
    return spans.idle_in_dispatch_share(tr, [min(tr.ops)])
