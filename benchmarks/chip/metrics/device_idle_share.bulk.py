"""Share (%) of the traced stretch in which a chip ran no operation, the
mean over the chips of the cell. A chip whose trace holds fewer than half
the program executions of the fullest one has lost events (the profiler
drops some of chip 0's on four chips) and is left out, so that lost
events do not read as idle time."""


def read(ctx):
    from benchmarks.chip import tracefile
    tr = ctx.trace
    if tr is None or not tr.ops:
        return None
    chips = tracefile.complete_devices(tr)
    busy = [tracefile.busy_s(tr, d) for d in chips]
    return 100.0 * (1.0 - sum(busy) / len(busy) / tr.window_s)
