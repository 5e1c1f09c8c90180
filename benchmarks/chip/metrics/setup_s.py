"""Seconds from process start to the start of the window: corpus, build,
warm-up of every shape the window uses, and compilation where the cache
misses."""


def read(ctx):
    return ctx.setup_s
