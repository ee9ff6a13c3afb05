"""Device time per decode step in the leaf operations under the step
program's "mamba" scope (the Mamba-2 mixers, their norms and residual
adds), each instant counted once, over the traced window."""

from benchlib import engine_spans


def read(ctx):
    r = engine_spans.reading(ctx)
    return None if r is None else engine_spans.decode_kind_ms(r, "mamba")
