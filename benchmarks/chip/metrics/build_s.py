"""Host seconds of `AnnEngine.build` and `pack()`, ending when the packed
index is on the device (block_until_ready)."""


def read(ctx):
    return ctx.parts["build_s"]
