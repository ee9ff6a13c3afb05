"""Host time after each decode step with the device idle: the engine's
engine.readback, engine.pick and engine.commit spans (their union less the
device's busy time), median over the traced window's decode ticks, so
that the profiler's start stall in one tick does not carry it."""

from benchlib import engine_spans


def read(ctx):
    r = engine_spans.reading(ctx)
    return None if r is None else engine_spans.token_host_ms(r)
