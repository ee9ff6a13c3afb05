"""95th percentile of the gaps between consecutive output tokens of one
request, over every gap that ends in the window."""

from benchlib import readings


def read(ctx):
    return readings.itl_ms(ctx, 95)
