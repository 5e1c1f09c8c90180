"""Mean length (ms) of the `engine.download` spans of one dispatch: the
host waiting for the device to finish the search program, plus the copy
of ids and scores back, per dispatch of the traced stretch (spans.py)."""


def read(ctx):
    from benchmarks.chip import spans
    return spans.mean_ms(ctx.trace, lambda d: d.total_ns("engine.download"))
