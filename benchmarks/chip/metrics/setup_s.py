"""Process start to the start of the window: import, weights, warmup and
the warm-in traffic."""


def read(ctx):
    return ctx.setup_s
