"""Mean length (ms) of the `frontend.resolve` span of one dispatch: slicing
the results and resolving every request's Future, with the callbacks that
runs in the dispatcher thread, per dispatch of the traced stretch
(spans.py)."""


def read(ctx):
    from benchmarks.chip import spans
    return spans.mean_ms(ctx.trace, lambda d: d.total_ns("frontend.resolve"))
