"""Mean host time per engine tick: each bench.tick span less the device
busy time inside it, over the traced window."""

from benchlib import readings


def read(ctx):
    return readings.host_ms_per_tick(ctx)
