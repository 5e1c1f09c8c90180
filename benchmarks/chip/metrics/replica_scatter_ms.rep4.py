"""Mean length (ms) of the `replica.scatter` span of one replica dispatch:
padding the coalesced batch and putting its rows straight onto the
replicas, split row-wise, per dispatch of the traced stretch
(contained.py)."""


def read(ctx):
    from benchmarks.chip import contained
    return contained.mean_ms(ctx.trace, "replica.scatter")
