"""Useful decode FLOPs over the decode programs' device time at peak: each
active row's trunk and head, and its attention over its context.  Padding
rows and idle slots count for nothing."""

from benchlib import readings


def read(ctx):
    return readings.decode_mfu(ctx)
