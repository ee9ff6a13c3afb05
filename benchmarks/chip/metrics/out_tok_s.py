"""Output tokens generated inside the window over the window's seconds."""

from benchlib import readings


def read(ctx):
    return readings.out_tok_s(ctx)
