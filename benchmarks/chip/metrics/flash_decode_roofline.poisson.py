"""The flash-decode kernel's share of its roofline in the decode programs:
the least time (the active rows' live K/V read once at peak bandwidth) over
the summed kernel time."""

from benchlib import readings


def read(ctx):
    return readings.flash_decode_roofline(ctx)
