"""90th percentile latency (ms) over every request of the window, each
from its due time to the resolution of its Future; a request never
answered counts as infinitely late. The 90th and not a higher percentile:
a host stall of about 110 ms hits this cell a few times a minute, and the
backlog it leaves drains slowly at 0.8 of the knee, so the 95th and 99th
percentiles swing with the number of stalls in a run (PERF.md)."""
import numpy as np


def read(ctx):
    lat = ctx.window.latency_s()
    if not lat.size or not np.isfinite(lat).all():
        return None
    return float(np.percentile(lat, 90) * 1e3)
