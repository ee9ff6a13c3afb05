"""90th percentile of the time from due to slot admission, over every
request due in the window (one not admitted at the close counts its wait)."""

from benchlib import readings


def read(ctx):
    return readings.queue_wait_ms(ctx, 90)
