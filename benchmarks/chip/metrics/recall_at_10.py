"""Mean recall@10 over every query answered in the window, against the
exact top-10 over the same vectors (reference.py)."""


def read(ctx):
    return ctx.quality["recall_at_10"]
