"""Mean host time (ms) from the start of a `frontend.dispatch` span to the
end of its `engine.launch` span: coalescing, the engine's validation,
filter, pack and padding, the query upload and the launch of the search
program, per dispatch of the traced stretch (spans.py)."""


def read(ctx):
    from benchmarks.chip import spans

    def prep(d):
        end = d.end_of("engine.launch")
        return None if end is None else end - d.span.start
    return spans.mean_ms(ctx.trace, prep)
