"""Queries answered inside the window per second of the window."""
import numpy as np


def read(ctx):
    w = ctx.window
    ok = np.flatnonzero(w.answered() & (w.t_done <= w.t1))
    return sum(len(w.qidx[i]) for i in ok) / (w.t1 - w.t0)
