"""Share (%) of the traced stretch in which a chip ran no operation while
the dispatcher had a `frontend.dispatch` span open, the mean over the
chips whose trace is complete, as `device_idle_share.bulk` takes them
(spans.py)."""


def read(ctx):
    from benchmarks.chip import spans, tracefile
    tr = ctx.trace
    if tr is None or not tr.ops:
        return None
    return spans.idle_in_dispatch_share(tr, tracefile.complete_devices(tr))
