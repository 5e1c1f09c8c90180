"""Mean length (ms) of the `replica.gather` span of one replica dispatch:
from the launch's return until ids and scores of every replica are on
the host, that is the wait for the slowest replica plus the copy back,
per dispatch of the traced stretch (contained.py)."""


def read(ctx):
    from benchmarks.chip import contained
    return contained.mean_ms(ctx.trace, "replica.gather")
