"""Share of the traced window with no operation on the device, leaving out
the spans in which the engine held no request."""

from benchlib import readings


def read(ctx):
    return readings.device_idle_pct(ctx)
