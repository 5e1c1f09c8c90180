"""Device bytes in use after set-up, with the harness's own device arrays
released, per live vector of the index."""


def read(ctx):
    return ctx.hbm_bytes / ctx.index.n_alive
