"""Median time from when a request was due to its first token, over every
request due in the window (one still waiting at the close counts its wait)."""

from benchlib import readings


def read(ctx):
    return readings.ttft_ms(ctx, 50)
