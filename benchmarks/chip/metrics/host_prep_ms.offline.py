"""Host time before each step with the device idle: the engine's
engine.admit, engine.schedule, engine.stage and engine.dispatch spans
(their union less the device's busy time), mean over the traced window's
ticks."""

from benchlib import engine_spans


def read(ctx):
    r = engine_spans.reading(ctx)
    return None if r is None else engine_spans.host_prep_ms(r)
